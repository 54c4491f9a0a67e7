"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler ships with jax and compiles for a described ``v5e:2x2``
topology.  It refuses what the chip would refuse: a program that does not
fit HBM, a layout Mosaic cannot lower, a computation it cannot partition.

* The composed search path (``DQFConfig()``'s default: no Pallas) must
  compile at deployment widths, on one chip and sharded over four.
* The Pallas kernels do not compile for the chip yet.  Their tests are
  strict xfails whose reason is the compiler's refusal; a change that
  makes a kernel compile flips its test.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test runner's workers all import every test file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import beam_search as bs
from repro.core.decision_tree import TreeArrays
from repro.core.dynamic_search import config_kwargs, dynamic_search
from repro.core.types import DQFConfig, PoolState, SearchStats
from repro.kernels.ref import HopState
from repro.serving import paged as pg

D, B, R = 128, 64, 32            # SIFT-class rows, a 64-lane wave, R=32
N_FULL = 1_000_000               # rows for the HBM-fit compile
N = 50_000                       # rows of the chip smoke's deployment
HBM_BYTES = 16e9                 # one v5e chip
CFG = DQFConfig()
L = CFG.full_pool
TREE_NODES = 2 ** (CFG.tree_depth + 1) - 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding):
    """ShapeDtypeStruct factory placed on ``sharding``."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _tree(S):
    T = TREE_NODES
    return TreeArrays(S((T,), jnp.int32), S((T,), jnp.float32),
                      S((T,), jnp.int32), S((T,), jnp.int32),
                      S((T,), jnp.float32))


def _stats(S, b):
    return SearchStats(S((b,), jnp.int32), S((b,), jnp.int32),
                       S((b,), jnp.int32), S((b,), jnp.bool_))


def test_dynamic_search_fits_one_chip_at_1m_rows(one_chip):
    S = _spec(one_chip)
    H = N_FULL // 200                         # index_ratio 0.005
    compiled = dynamic_search.lower(
        S((N_FULL + 1, D), jnp.float32), S((N_FULL + 1, R), jnp.int32),
        S((H + 1, D), jnp.float32), S((H + 1, R), jnp.int32),
        S((H + 1,), jnp.int32), S((CFG.n_entry,), jnp.int32), _tree(S),
        S((B, D), jnp.float32), live_pad=S((N_FULL + 1,), jnp.bool_),
        **config_kwargs(CFG)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= (N_FULL + 1) * (D + R) * 4
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert "tpu_custom_call" not in compiled.as_text()    # no Pallas


def test_expand_step_paged_compiles_at_capacity_64(one_chip):
    S = _spec(one_chip)
    page_cols = pg.DEFAULT_PAGE_COLS
    ppl = -(-(N + 1) // page_cols)
    state = bs.BeamState(
        PoolState(S((B, L), jnp.int32), S((B, L), jnp.float32),
                  S((B, L), jnp.bool_)),
        S(((B + 1) * ppl, page_cols), jnp.bool_), _stats(S, B),
        S((B,), jnp.bool_))
    step = jax.jit(pg.expand_step_paged, static_argnames=("page_shift",))
    compiled = step.lower(
        S((N + 1, D), jnp.float32), S((N + 1, R), jnp.int32),
        S((B, D), jnp.float32), state, S((B, ppl), jnp.int32),
        page_shift=page_cols.bit_length() - 1,
        live_pad=S((N + 1,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_sharded_stacked_search_partitions_over_four_chips(topo):
    """The one-jit sharded search keeps each shard's tables on its own
    chip: per-device arguments are one shard, and no collective moves a
    table row.  (The compiler also puts one scalar all-reduce in each
    vmapped while loop's condition, the any-over-shards of ``active``.)"""
    from repro.sharding import ShardConfig, ShardedDQF

    x = np.random.default_rng(0).standard_normal((400, 16)).astype(
        np.float32)
    sd = ShardedDQF(CFG, ShardConfig(num_shards=4, use_mesh=False)).build(x)
    fn = sd._build_stacked_fn()
    mesh = Mesh(np.asarray(topo.devices[:4]), ("shard",))
    Ss = _spec(NamedSharding(mesh, P("shard")))
    Sr = _spec(NamedSharding(mesh, P()))
    cap = N_FULL // 4
    H = cap // 200
    tables = [Ss((4, cap + 1, D), jnp.float32), Ss((4, cap + 1, R), jnp.int32),
              Ss((4, cap + 1), jnp.bool_), Ss((4, cap + 1), jnp.int32),
              Ss((4, H + 1, D), jnp.float32), Ss((4, H + 1, R), jnp.int32),
              Ss((4, H + 1), jnp.int32), Ss((4, CFG.n_entry), jnp.int32)]
    compiled = fn.lower(*tables, _tree(Sr), Sr((B, D), jnp.float32)).compile()
    stacked = sum(int(np.prod(t.shape)) * t.dtype.itemsize for t in tables)
    mem = compiled.memory_analysis()              # bytes on each device
    assert mem.argument_size_in_bytes < stacked / 3
    text = compiled.as_text()
    assert f"f32[1,{cap + 1},{D}]" in text        # one shard per device
    collectives = [ln for ln in text.splitlines()
                   if any(f" {op}(" in ln or f" {op}-start(" in ln
                          for op in ("all-gather", "all-reduce",
                                     "all-to-all", "collective-permute"))]
    assert collectives                            # the cross-shard merge
    assert not [ln for ln in collectives if str(cap + 1) in ln]


# ------------------------------------------------------------ Pallas kernels
_ARGMAX = ("Mosaic refuses the argmax over a bool mask in the hop body "
           "(fused_hop.py frontier select): NotImplementedError: Only "
           "float32 is supported")


def _hop_state(S, seen_shape):
    return HopState(
        ids=S((B, L), jnp.int32), dists=S((B, L), jnp.float32),
        expanded=S((B, L), jnp.bool_), seen=S(seen_shape, jnp.bool_),
        active=S((B,), jnp.bool_), dist_count=S((B,), jnp.int32),
        update_count=S((B,), jnp.int32), hops=S((B,), jnp.int32),
        terminated=S((B,), jnp.bool_), evals_done=S((B,), jnp.int32),
        stop_at=S((B,), jnp.int32))


def _table(S, mode):
    """The kernel's (t0, t1, t2) score-table operands, as ``ops.table_spec``
    unpacks them from an ``x_pad`` / ``SQTable`` / ``PQView``."""
    if mode == "f32":
        return S((N + 1, D), jnp.float32), None, None
    if mode == "sq8":
        return S((N + 1, D), jnp.int8), S((D,), jnp.float32), \
            S((D,), jnp.float32)
    m = CFG.quant.pq_m
    return S((N + 1, m), jnp.uint8), S((B, m, 256), jnp.float32), None


def _hop_kwargs():
    return dict(hops=8, max_hops=CFG.max_hops, k=CFG.k,
                eval_gap=CFG.eval_gap, add_step=0, tree_depth=CFG.tree_depth)


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=_ARGMAX)
@pytest.mark.parametrize("mode", ["f32", "sq8", "pq"])
def test_fused_hop_kernel_compiles(one_chip, mode):
    from repro.kernels.fused_hop import fused_hop_pallas
    S = _spec(one_chip)
    t0, t1, t2 = _table(S, mode)
    fused_hop_pallas.lower(
        _hop_state(S, (B, N + 1)), S((N + 1, R), jnp.int32),
        S((B, D), jnp.float32), S((N + 1,), jnp.bool_), mode, t0, t1, t2,
        _tree(S), S((B,), jnp.float32), S((B,), jnp.float32),
        **_hop_kwargs()).compile()


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=_ARGMAX)
def test_fused_hop_paged_kernel_compiles(one_chip):
    from repro.kernels.fused_hop import fused_hop_paged_pallas
    S = _spec(one_chip)
    page_cols = pg.DEFAULT_PAGE_COLS
    ppl = -(-(N + 1) // page_cols)
    fused_hop_paged_pallas.lower(
        _hop_state(S, ((B + 1) * ppl, page_cols)), S((B, ppl), jnp.int32),
        S((N + 1, R), jnp.int32), S((B, D), jnp.float32),
        S((N + 1,), jnp.bool_), "f32", S((N + 1, D), jnp.float32), None,
        None, _tree(S), S((B,), jnp.float32), S((B,), jnp.float32),
        **_hop_kwargs()).compile()


@pytest.mark.xfail(
    strict=True, reason="Mosaic refuses the scorer's running top-k merge: "
    "MosaicError: infer-vector-layout: unsupported shape cast")
def test_fused_topk_l2_kernel_compiles(one_chip):
    from repro.kernels.fused_scorer import fused_topk_l2_pallas
    S = _spec(one_chip)
    hot = N // 200
    fused_topk_l2_pallas.lower(S((B, D), jnp.float32),
                               S((hot, D), jnp.float32),
                               k=CFG.hot_pool).compile()
