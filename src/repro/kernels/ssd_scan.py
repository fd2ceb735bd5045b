"""ssd_scan — Mamba2 SSD chunked-scan Pallas kernel.

The SSD recurrence is reformulated as chunk-local dense algebra (MXU-friendly
matmuls over (Q x Q) and (Q x n) tiles) plus a tiny cross-chunk state
recurrence. The carried state (hp x n) lives in VMEM scratch and persists
across the *sequential* chunk grid dimension — the TPU-native replacement for
the GPU kernel's warp-level scan in the original paper's lineage.

Layout (flattened by the ops wrapper): per (batch*head) row —
  x  (BH, S, hp)   inputs per head
  dt (BH, S, 1)    post-softplus timestep (broadcast over hp)
  A  (BH, 1)       per-head decay rate (negative), scalar-prefetched block
  B, C (BH, S, n)  input/output projections (ngroups broadcast upstream)
Returns y (BH, S, hp) and final_state (BH, hp, n).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fs_ref, state_ref, *, Q, nc):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)      # (Q, hp)
    dt = dt_ref[0].astype(jnp.float32)    # (Q, 1)
    A = a_ref[0, 0].astype(jnp.float32)   # scalar
    B = b_ref[0].astype(jnp.float32)      # (Q, n)
    C = c_ref[0].astype(jnp.float32)      # (Q, n)

    dA = dt * A                            # (Q, 1), negative
    dA_cs = jnp.cumsum(dA, axis=0)         # inclusive (Q, 1)

    # intra-chunk: y_q += sum_{s<=q} exp(dA_cs[q]-dA_cs[s]) * (C_q.B_s) dt_s x_s
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    L = jnp.exp(dA_cs - dA_cs.reshape(1, Q))                      # (Q, Q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(rows >= cols, L, 0.0)
    u = x * dt                                                     # (Q, hp)
    y = jax.lax.dot_general(CB * L, u, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # carried-state contribution: y_q += exp(dA_cs[q]) * C_q . state^T
    y += jnp.exp(dA_cs) * jax.lax.dot_general(
        C, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: state = exp(dA_total) * state + sum_s exp(dA_cs[-1]-dA_cs[s]) u_s B_s
    decay_states = jnp.exp(dA_cs[Q - 1] - dA_cs)                   # (Q, 1)
    new_contrib = jax.lax.dot_general(u * decay_states, B, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)  # (hp, n)
    state_ref[...] = jnp.exp(dA_cs[Q - 1]) * state_ref[...] + new_contrib

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(c == nc - 1)
    def _final():
        fs_ref[0] = state_ref[...].astype(fs_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 128,
             interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (BH, S, hp); dt: (BH, S); A: (BH,); B, C: (BH, S, n)."""
    BH, S, hp = x.shape
    n = B.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    kern = functools.partial(_kernel, Q=Q, nc=nc)
    y, fs = pl.pallas_call(
        kern,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, Q, hp), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, Q, 1), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1), lambda bh, c: (bh, 0)),
            pl.BlockSpec((1, Q, n), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, Q, n), lambda bh, c: (bh, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, hp), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, hp, n), lambda bh, c: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hp), x.dtype),
            jax.ShapeDtypeStruct((BH, hp, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hp, n), jnp.float32)],
        interpret=interpret,
    )(x, dt[..., None], A[:, None], B, C)
    return y, fs
