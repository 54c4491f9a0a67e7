"""Attention flavors for the arch zoo: GQA, MLA, cross-attention.

Memory-efficient chunked attention, pure XLA
-------------------------------------------
Long-context prefill/train cannot materialize (S, S) score matrices.  We use
a flash-style streaming softmax implemented as a single `lax.scan` over a
**static chunk-pair schedule**: the list of (q-chunk i, kv-chunk j) pairs
that are not fully masked (causality + sliding window) is computed at trace
time, so — unlike the common full-rectangle-with-mask approach — FLOPs are
*exact* for causal attention (no 2× upper-triangle waste; window layers pay
at most one partially-masked extra chunk).  The carry holds running
(max, denom, accumulator) per q-chunk row and flushes into the output buffer
with `dynamic_update_slice`.  Everything is differentiable (plain scan), so
the same code path serves train and prefill.

KV expansion is a per-chunk callback, which lets MLA keep its cache
compressed (rank + rope dims) and GQA repeat KV heads chunk-locally instead
of materializing (B, S, H, hd).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .common import Initializer, apply_rope, dense_init, rms_norm, rope_angles

__all__ = [
    "make_pair_schedule", "chunked_attention",
    "init_gqa_params", "gqa_forward", "gqa_decode",
    "init_mla_params", "mla_forward", "mla_decode",
    "init_cross_params", "cross_forward", "cross_decode",
    "KVCache", "MLACache",
]

NEG_INF = jnp.float32(-1e30)


# =============================================================== scheduling
def make_pair_schedule(nq: int, nk: int, *, cq: int, ck: int, causal: bool,
                       window: int = 0,
                       q_pos_offset: int = 0) -> tuple[np.ndarray, ...]:
    """Static (i, j, new_row) arrays of chunk pairs with any live entry.

    Predicates are in *positions*, not chunk indices, so mixed chunk sizes
    (cq != ck) stay exact: q chunk i spans [off+i·cq, off+(i+1)·cq) and kv
    chunk j spans [j·ck, (j+1)·ck).  Row-major in i so the streaming-softmax
    carry is valid.
    """
    i_l, j_l, n_l = [], [], []
    for i in range(nq):
        q_lo = q_pos_offset + i * cq
        q_hi = q_pos_offset + (i + 1) * cq - 1
        first = True
        for j in range(nk):
            k_lo = j * ck
            k_hi = (j + 1) * ck - 1
            if causal and k_lo > q_hi:
                continue          # entirely in the future
            if causal and window and k_hi <= q_lo - window:
                continue          # entirely outside the window
            i_l.append(i)
            j_l.append(j)
            n_l.append(first)
            first = False
        if first:
            raise ValueError("empty schedule row")
    return (np.asarray(i_l, np.int32), np.asarray(j_l, np.int32),
            np.asarray(n_l, np.bool_))


# ========================================================= chunked attention
def chunked_attention(
    q: jnp.ndarray,                  # (B, S, H, dk)
    kv_raw: jnp.ndarray,             # (B, Skv, raw) compressed/stacked kv
    expand_fn: Callable,             # (kv_chunk (B,ck,raw), j) -> (k,v)
    *,
    chunk_q: int,
    chunk_k: int,
    causal: bool,
    window: int = 0,                 # 0 = unlimited
    q_pos_offset: int = 0,
    out_dim: Optional[int] = None,   # v head dim (defaults to dk)
    scale: Optional[float] = None,
    kv_valid_len: Optional[int] = None,  # mask padded kv tail
) -> jnp.ndarray:
    B, S, H, dk = q.shape
    Skv = kv_raw.shape[1]
    dv = out_dim or dk
    cq, ck = min(chunk_q, S), min(chunk_k, Skv)
    if S % cq or Skv % ck:
        raise ValueError(f"S={S}/{Skv} not divisible by chunks {cq}/{ck}")
    nq, nk = S // cq, Skv // ck
    i_arr, j_arr, new_arr = make_pair_schedule(
        nq, nk, cq=cq, ck=ck, causal=causal, window=window,
        q_pos_offset=q_pos_offset)
    sc = scale if scale is not None else dk ** -0.5

    def body(carry, xs):
        m, l, acc, out = carry
        i, j, new_row = xs
        qc = jax.lax.dynamic_slice_in_dim(q, i * cq, cq, axis=1)
        kvc = jax.lax.dynamic_slice_in_dim(kv_raw, j * ck, ck, axis=1)
        kc, vc = expand_fn(kvc, j)                      # (B,ck,H,dk/dv)
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                       preferred_element_type=jnp.float32) * sc
        qpos = (q_pos_offset + i * cq
                + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 0))
        kpos = j * ck + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
        live = jnp.ones((cq, ck), bool)
        if causal:
            live &= kpos <= qpos
        if window:
            live &= kpos > qpos - window
        if kv_valid_len is not None and kv_valid_len < Skv:
            live &= kpos < kv_valid_len
        s = jnp.where(live[None, None], s, NEG_INF)

        # reset the row state on a new q row
        m = jnp.where(new_row, NEG_INF, m)
        l = jnp.where(new_row, 0.0, l)
        acc = jnp.where(new_row, 0.0, acc)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))     # (B,H,cq)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])               # (B,H,cq,ck)
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        norm = acc / jnp.maximum(l, 1e-30)[..., None]   # (B,H,cq,dv)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.transpose(norm, (0, 2, 1, 3)).astype(out.dtype),
            i * cq, axis=1)
        return (m_new, l, acc, out), None

    carry = (
        jnp.full((B, H, cq), NEG_INF, jnp.float32),
        jnp.zeros((B, H, cq), jnp.float32),
        jnp.zeros((B, H, cq, dv), jnp.float32),
        jnp.zeros((B, S, H, dv), q.dtype),
    )
    xs = (jnp.asarray(i_arr), jnp.asarray(j_arr), jnp.asarray(new_arr))
    (_, _, _, out), _ = jax.lax.scan(body, carry, xs)
    return out


def _decode_attention(q1, k_all, v_all, live, scale):
    """Single-position attention: q (B,1,H,dk) vs full caches (B,W,Hk,·)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q1, k_all,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_all.dtype), v_all,
                   preferred_element_type=jnp.float32)
    return o.astype(q1.dtype)


# ======================================================================= GQA
class KVCache(NamedTuple):
    """Ring-buffer KV cache (window layers wrap; full layers W = max_seq)."""

    k: jnp.ndarray          # (B, W, Hkv, hd)
    v: jnp.ndarray          # (B, W, Hkv, hd)
    pos: jnp.ndarray        # (W,) int32 absolute positions, -1 = empty


def init_gqa_params(init: Initializer, cfg, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    p = {
        "wq": dense_init(init, d, cfg.num_heads * hd, dtype),
        "wk": dense_init(init, d, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(init, d, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(init, cfg.num_heads * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _gqa_qkv(p, x, positions, *, cfg, theta):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, hd)
    k = (x @ p["wk"]).reshape(B, S, cfg.num_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    sin, cos = rope_angles(positions, hd, theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    return q, k, v


def gqa_forward(p, x, *, cfg, theta: float, window: int,
                chunk_q: int = 1024, chunk_k: int = 1024,
                return_kv: bool = False):
    """Train/prefill GQA over the full sequence."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    q, k, v = _gqa_qkv(p, x, positions, cfg=cfg, theta=theta)
    groups = cfg.num_heads // cfg.num_kv_heads
    kv_raw = jnp.concatenate(
        [k.reshape(B, S, -1), v.reshape(B, S, -1)], axis=-1)

    def expand(kvc, j):
        ck = kvc.shape[1]
        kk = kvc[..., : cfg.num_kv_heads * hd].reshape(
            B, ck, cfg.num_kv_heads, hd)
        vv = kvc[..., cfg.num_kv_heads * hd:].reshape(
            B, ck, cfg.num_kv_heads, hd)
        return (jnp.repeat(kk, groups, axis=2), jnp.repeat(vv, groups, axis=2))

    out = chunked_attention(q, kv_raw, expand, chunk_q=chunk_q,
                            chunk_k=chunk_k, causal=True, window=window)
    out = out.reshape(B, S, -1) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def gqa_init_cache(cfg, batch: int, max_len: int, window: int,
                   dtype) -> KVCache:
    W = min(window, max_len) if window else max_len
    hd = cfg.resolved_head_dim
    return KVCache(
        k=jnp.zeros((batch, W, cfg.num_kv_heads, hd), dtype),
        v=jnp.zeros((batch, W, cfg.num_kv_heads, hd), dtype),
        pos=jnp.full((W,), -1, jnp.int32),
    )


def gqa_decode(p, x1, cache: KVCache, pos: jnp.ndarray, *, cfg,
               theta: float, window: int, flash_mesh=None):
    """One decode step; writes the new KV at ``pos % W`` (ring buffer).

    ``flash_mesh``: enable the flash-decoding path — cache sharded over the
    sequence dim inside a shard_map region; each shard computes local
    softmax stats, combined with one small psum; the ring-buffer write is
    owner-local.  This removes the full-cache all-gather the GSPMD
    partitioner otherwise emits (EXPERIMENTS.md §Perf cell A).
    """
    B = x1.shape[0]
    hd = cfg.resolved_head_dim
    q, k, v = _gqa_qkv(p, x1, pos[None, None], cfg=cfg, theta=theta)
    if flash_mesh is not None:
        o, new_cache = _flash_decode(
            q, k, v, cache, pos, cfg=cfg, window=window, mesh=flash_mesh)
        return o.reshape(B, 1, -1) @ p["wo"], new_cache
    W = cache.k.shape[1]
    slot = pos % W
    ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v, slot, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache.pos, pos[None].astype(jnp.int32), slot, axis=0)
    live = (cpos >= 0) & (cpos <= pos)
    if window:
        live &= cpos > pos - window
    groups = cfg.num_heads // cfg.num_kv_heads
    k_all = jnp.repeat(ck, groups, axis=2)
    v_all = jnp.repeat(cv, groups, axis=2)
    o = _decode_attention(q, k_all, v_all,
                          jnp.broadcast_to(live[None], (B, W)), hd ** -0.5)
    return o.reshape(B, 1, -1) @ p["wo"], KVCache(ck, cv, cpos)


def _flash_decode(q, k_new, v_new, cache: KVCache, pos, *, cfg, window,
                  mesh, model_axis: str = "model"):
    """Sequence-sharded decode attention (flash-decoding on the TP axis)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import _data_axes

    B, _, H, hd = q.shape
    W = cache.k.shape[1]
    S = mesh.shape[model_axis] if model_axis in mesh.axis_names else 1
    daxes = _data_axes(mesh)
    dlead = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    from numpy import prod
    dsz = int(prod([mesh.shape[a] for a in daxes])) if daxes else 1
    bspec = dlead if (dsz and B % max(dsz, 1) == 0) else None
    if W % S:
        raise ValueError(f"window {W} not divisible by model axis {S}")
    Wl = W // S
    groups = cfg.num_heads // cfg.num_kv_heads
    scale = hd ** -0.5

    def body(q, k_new, v_new, ck, cv, cpos):
        me = jax.lax.axis_index(model_axis)
        slot = pos % W
        owner = slot // Wl
        local = slot % Wl
        upd_k = jax.lax.dynamic_update_slice_in_dim(ck, k_new, local, axis=1)
        upd_v = jax.lax.dynamic_update_slice_in_dim(cv, v_new, local, axis=1)
        upd_p = jax.lax.dynamic_update_slice_in_dim(
            cpos, pos[None].astype(jnp.int32), local, axis=0)
        mine = me == owner
        ck = jnp.where(mine, upd_k, ck)
        cv = jnp.where(mine, upd_v, cv)
        cpos = jnp.where(mine, upd_p, cpos)

        live = (cpos >= 0) & (cpos <= pos)
        if window:
            live &= cpos > pos - window
        k_all = jnp.repeat(ck, groups, axis=2)          # (B, Wl, H, hd)
        v_all = jnp.repeat(cv, groups, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_all,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(live[None, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)                          # (B,H,1) local max
        m_g = jax.lax.pmax(m, model_axis)
        p_ = jnp.exp(s - m_g[..., None])
        l = jnp.sum(p_, axis=-1)
        acc = jnp.einsum("bhqk,bkhd->bhqd", p_.astype(v_all.dtype), v_all,
                         preferred_element_type=jnp.float32)
        l_g = jax.lax.psum(l, model_axis)                # (B,H,1) tiny
        acc_g = jax.lax.psum(acc, model_axis)            # (B,H,1,hd) tiny
        o = (acc_g / jnp.maximum(l_g, 1e-30)[..., None])
        o = jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)  # (B,1,H,hd)
        return o, ck, cv, cpos

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec),
                  P(bspec, model_axis), P(bspec, model_axis),
                  P(model_axis)),
        out_specs=(P(bspec), P(bspec, model_axis), P(bspec, model_axis),
                   P(model_axis)),
        check_vma=False)
    o, ck, cv, cpos = fn(q, k_new, v_new, cache.k, cache.v, cache.pos)
    return o, KVCache(ck, cv, cpos)


# ======================================================================= MLA
class MLACache(NamedTuple):
    """Compressed cache: latent c_kv + shared rope key (the MLA point)."""

    c_kv: jnp.ndarray       # (B, W, rank)
    k_rope: jnp.ndarray     # (B, W, rope_dim)
    pos: jnp.ndarray        # (W,)


def init_mla_params(init: Initializer, cfg, dtype) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": dense_init(init, d, H * qd, dtype),
        "w_dkv": dense_init(init, d, m.kv_lora_rank + m.qk_rope_head_dim,
                            dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "w_uk": dense_init(init, m.kv_lora_rank, H * m.qk_nope_head_dim,
                           dtype),
        "w_uv": dense_init(init, m.kv_lora_rank, H * m.v_head_dim, dtype),
        "wo": dense_init(init, H * m.v_head_dim, d, dtype),
    }


def _mla_q(p, x, positions, cfg):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, qd)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    sin, cos = rope_angles(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, sin, cos)
    return q_nope, q_rope


def _mla_compress(p, x, positions, cfg):
    m = cfg.mla
    ckv = x @ p["w_dkv"]                                 # (B,S,rank+rope)
    c, k_rope = ckv[..., : m.kv_lora_rank], ckv[..., m.kv_lora_rank:]
    c = rms_norm(c, p["kv_norm"], cfg.norm_eps)
    sin, cos = rope_angles(positions, m.qk_rope_head_dim, cfg.rope_theta)
    k_rope = apply_rope(k_rope[..., None, :], sin, cos)[..., 0, :]
    return c, k_rope


def mla_forward(p, x, *, cfg, chunk_q: int = 1024, chunk_k: int = 1024,
                return_kv: bool = False):
    """Train/prefill MLA; k/v expanded chunk-locally from the latent."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    q_nope, q_rope = _mla_q(p, x, positions, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    c, k_rope = _mla_compress(p, x, positions, cfg)
    kv_raw = jnp.concatenate([c, k_rope], axis=-1)

    def expand(kvc, j):
        ck = kvc.shape[1]
        cc = kvc[..., : m.kv_lora_rank]
        kr = kvc[..., m.kv_lora_rank:]
        k_nope = (cc @ p["w_uk"]).reshape(B, ck, H, m.qk_nope_head_dim)
        v = (cc @ p["w_uv"]).reshape(B, ck, H, m.v_head_dim)
        kr = jnp.broadcast_to(kr[..., None, :],
                              (B, ck, H, m.qk_rope_head_dim))
        return jnp.concatenate([k_nope, kr], axis=-1), v

    dk = m.qk_nope_head_dim + m.qk_rope_head_dim
    out = chunked_attention(q, kv_raw, expand, chunk_q=chunk_q,
                            chunk_k=chunk_k, causal=True,
                            out_dim=m.v_head_dim, scale=dk ** -0.5)
    out = out.reshape(B, S, -1) @ p["wo"]
    if return_kv:
        return out, (c, k_rope)
    return out


def mla_init_cache(cfg, batch: int, max_len: int, dtype) -> MLACache:
    m = cfg.mla
    return MLACache(
        c_kv=jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        k_rope=jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
        pos=jnp.full((max_len,), -1, jnp.int32),
    )


def mla_decode(p, x1, cache: MLACache, pos: jnp.ndarray, *, cfg):
    """Decode with weight absorption — scores live in the latent space."""
    m = cfg.mla
    B = x1.shape[0]
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(p, x1, pos[None, None], cfg)
    c1, kr1 = _mla_compress(p, x1, pos[None, None], cfg)
    W = cache.c_kv.shape[1]
    slot = pos % W
    cc = jax.lax.dynamic_update_slice_in_dim(cache.c_kv, c1, slot, axis=1)
    ckr = jax.lax.dynamic_update_slice_in_dim(cache.k_rope, kr1, slot, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache.pos, pos[None].astype(jnp.int32), slot, axis=0)
    live = (cpos >= 0) & (cpos <= pos)

    # absorb W_uk into q: (B,1,H,rank)
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk,
                       preferred_element_type=jnp.float32).astype(x1.dtype)
    s = (jnp.einsum("bqhr,bkr->bhqk", q_lat, cc,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhn,bkn->bhqk", q_rope, ckr,
                      preferred_element_type=jnp.float32))
    dk = m.qk_nope_head_dim + m.qk_rope_head_dim
    s = s * dk ** -0.5
    s = jnp.where(live[None, None, None, :], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", pattn.astype(cc.dtype), cc,
                       preferred_element_type=jnp.float32)   # (B,1,H,rank)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bqhr,rhv->bqhv", o_lat.astype(x1.dtype), w_uv,
                   preferred_element_type=jnp.float32).astype(x1.dtype)
    return (o.reshape(B, 1, -1) @ p["wo"],
            MLACache(cc, ckr, cpos))


# ============================================================ cross-attention
def init_cross_params(init: Initializer, cfg, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return {
        "wq": dense_init(init, d, cfg.num_heads * hd, dtype),
        "wk": dense_init(init, d, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(init, d, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(init, cfg.num_heads * hd, d, dtype),
        "gate": jnp.zeros((), dtype),     # llama3.2-style tanh gate, init 0
        "q_norm": jnp.zeros((hd,), dtype),
        "k_norm": jnp.zeros((hd,), dtype),
    }


def _cross_kv(p, media, cfg):
    B, T, _ = media.shape
    hd = cfg.resolved_head_dim
    k = (media @ p["wk"]).reshape(B, T, cfg.num_kv_heads, hd)
    v = (media @ p["wv"]).reshape(B, T, cfg.num_kv_heads, hd)
    k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return k, v


def cross_forward(p, x, media, *, cfg, chunk_q: int = 1024):
    """Text queries attend to (stub) vision tokens — no rope, gated."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, hd)
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    k, v = _cross_kv(p, media, cfg)
    T = k.shape[1]
    groups = cfg.num_heads // cfg.num_kv_heads
    # pad vision tokens to a chunk multiple with masked (NEG_INF via pos) slots
    ck = min(1024, 1 << (T - 1).bit_length())
    Tp = -(-T // ck) * ck
    kv_raw = jnp.concatenate([k.reshape(B, T, -1), v.reshape(B, T, -1)], -1)
    kv_raw = jnp.pad(kv_raw, ((0, 0), (0, Tp - T), (0, 0)))

    def expand(kvc, j):
        cc = kvc.shape[1]
        kk = kvc[..., : cfg.num_kv_heads * hd].reshape(
            B, cc, cfg.num_kv_heads, hd)
        vv = kvc[..., cfg.num_kv_heads * hd:].reshape(
            B, cc, cfg.num_kv_heads, hd)
        return (jnp.repeat(kk, groups, axis=2), jnp.repeat(vv, groups, axis=2))

    out = chunked_attention(
        q, kv_raw, expand, chunk_q=min(chunk_q, S), chunk_k=ck,
        causal=False, window=0, kv_valid_len=T)
    out = out.reshape(B, S, -1) @ p["wo"]
    g = jnp.tanh(p["gate"].astype(jnp.float32)).astype(out.dtype)
    return out * g


def cross_decode(p, x1, k_cache, v_cache, *, cfg):
    """Decode: media KV precomputed at prefill; no new writes."""
    B = x1.shape[0]
    hd = cfg.resolved_head_dim
    q = (x1 @ p["wq"]).reshape(B, 1, cfg.num_heads, hd)
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    groups = cfg.num_heads // cfg.num_kv_heads
    k_all = jnp.repeat(k_cache, groups, axis=2)
    v_all = jnp.repeat(v_cache, groups, axis=2)
    T = k_all.shape[1]
    live = jnp.ones((B, T), bool)
    o = _decode_attention(q, k_all, v_all, live, hd ** -0.5)
    g = jnp.tanh(p["gate"].astype(jnp.float32)).astype(o.dtype)
    return (o.reshape(B, 1, -1) @ p["wo"]) * g
