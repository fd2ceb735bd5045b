"""Pipeline parallelism over a mesh axis via shard_map + collective_permute.

GPipe-style schedule: stage s holds its own layer-group parameters (stacked
leading dim sharded over the ``stage`` axis). Microbatches stream through the
pipeline; each tick every stage computes its resident activation and passes
it to the next stage with ``ppermute`` (ring). Total ticks =
n_microbatches + n_stages - 1; bubble fraction = (S-1)/(M+S-1), reported by
``bubble_fraction``.

This is the TPU-native mapping of the paper's *streamed, fully pipelined*
FPGA dataflow (DESIGN.md §hardware-adaptation #3): pipeline fill/drain ≙
line-buffer warm-up, stage registers ≙ per-pod activations. It is exercised
as a beyond-paper option for the multi-pod mesh (stages = pods).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(stage_params, x, fn: Callable, mesh: Mesh, *,
                   axis: str = "pod", n_micro: int = 4):
    """Run ``fn(params_s, h) -> h`` through all stages of ``axis``.

    stage_params: pytree with leading dim == n_stages (sharded over ``axis``).
    x: (batch, ...) global input; split into ``n_micro`` microbatches.
    Returns y: (batch, ...) after every stage has processed every microbatch.
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    xs = x.reshape(n_micro, mb, *x.shape[1:])

    other_axes = [a for a in mesh.axis_names if a != axis]

    def stage_fn(params_local, xs_local):
        # params_local: (1, ...) this stage's slice; xs_local: full microbatches
        params_me = jax.tree_util.tree_map(lambda a: a[0], params_local)
        sidx = jax.lax.axis_index(axis)
        T = n_micro + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (when available); others use state
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = xs_local[mb_idx]
            h_in = jnp.where(sidx == 0, inject, state)
            h_out = fn(params_me, h_in)
            # last stage records its output for microbatch t-(S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            is_valid = jnp.logical_and(sidx == n_stages - 1,
                                       t >= n_stages - 1)
            outputs = jax.lax.cond(
                is_valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h_out, out_idx, 0),
                lambda o: o, outputs)
            state = jax.lax.ppermute(h_out, axis, fwd_perm)
            return (state, outputs), None

        state0 = jnp.zeros_like(xs_local[0])
        out0 = jnp.zeros_like(xs_local)
        (state, outputs), _ = jax.lax.scan(
            tick, (state0, out0), jnp.arange(T))
        # broadcast the last stage's outputs to every stage: only the last
        # stage wrote non-zeros, so a psum over the axis is a broadcast
        return jax.lax.psum(outputs, axis)

    pp = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(P(axis), P(*([None] * xs.ndim))),
        out_specs=P(*([None] * xs.ndim)),
        check_vma=False,
    )
    ys = pp(stage_params, xs)
    return ys.reshape(B, *x.shape[1:])
