"""GPipe-style pipeline parallelism over a mesh axis (the ``pod`` axis).

At multi-pod scale the inter-pod DCN link favors pipeline traffic
(activations, point-to-point) over gradient all-reduce.  This module maps
stages onto the ``pod`` axis with ``shard_map`` + ``ppermute``:

* stage s holds layers [s·L/S, (s+1)·L/S);
* the classic GPipe schedule runs ``M + S − 1`` ticks over ``M``
  microbatches; each tick every stage processes one resident microbatch and
  ppermutes its activation to the next stage;
* bubble fraction = (S − 1)/(M + S − 1) — reported by
  :func:`bubble_fraction` and validated in tests.

This is the launcher-selectable alternative to pod-level DP (see
launch/mesh.py); the dry-run exercises pod-DP by default, and
tests/test_pipeline.py proves the PP schedule's numerics on a faked 2-pod
mesh.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_forward", "bubble_fraction"]


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def pipeline_forward(stage_fn: Callable, stage_params, x: jnp.ndarray,
                     mesh: Mesh, *, axis: str = "pod",
                     num_microbatches: int | None = None) -> jnp.ndarray:
    """Run ``stage_fn(params_s, h) -> h`` through S pipeline stages.

    ``stage_params`` leaves have a leading stage axis (S, ...) sharded over
    ``axis``; ``x`` is (M, mb, ...) microbatched input (M ≥ S recommended).
    Returns the pipeline output (M, mb, ...) — numerically identical to
    applying the stages sequentially (validated in tests).
    """
    S = mesh.shape[axis]
    M = num_microbatches or x.shape[0]
    if x.shape[0] != M:
        raise ValueError("leading dim of x must be the microbatch count")

    def body(params, xs):
        # params: (1, ...) this stage's slice; xs: (M, mb, ...) replicated
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)                     # 0..S-1
        mb_shape = xs.shape[1:]
        buf = jnp.zeros_like(xs[0])                          # resident act
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (when valid)
            feed = jnp.where(t < M, t, M - 1)
            injected = jnp.where(stage == 0, 1.0, 0.0)
            h = buf * (1.0 - injected) + xs[feed] * injected
            h = stage_fn(params, h)
            # last stage emits microbatch (t - S + 1)
            emit_idx = jnp.clip(t - S + 1, 0, M - 1)
            do_emit = (stage == S - 1) & (t >= S - 1)
            outs = jax.lax.cond(
                do_emit,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, h[None], emit_idx, axis=0),
                lambda o: o, outs)
            # hand activation to the next stage
            h_next = jax.lax.ppermute(
                h, axis, [(i, (i + 1) % S) for i in range(S)])
            return (h_next, outs)

        buf, outs = jax.lax.fori_loop(0, M + S - 1, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast via masked psum
        if S > 1:
            mask = (stage == S - 1).astype(outs.dtype)
            outs = jax.lax.psum(outs * mask, axis)
        return outs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x)
