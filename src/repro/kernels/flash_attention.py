"""Tiled (flash) causal attention kernel with GQA + sliding-window support.

VMEM-blocked: the (Sq x Sk) score matrix never materializes; each grid step
holds one (bq x hd) query tile, one (bk x hd) KV tile, and running
(max, sum, acc) statistics in VMEM scratch. Fully-masked KV tiles — beyond
the causal frontier or behind the sliding window — are *skipped* via
``pl.when`` (no MXU issue, the same tile-level gating idea as morph_matmul).

Layout: q (BH, Sq, hd), k/v (BKV, Sk, hd) pre-flattened by the ops wrapper;
GQA maps query-head block bh -> kv row bh // group.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq, bk, nk, scale, causal, window):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk
    # tile-level gating: skip fully-masked KV tiles
    live = jnp.asarray(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + bq - 1)
    if window > 0:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.zeros((bq, bk), jnp.float32)
        if causal:
            mask = jnp.where(cols > rows, NEG_INF, mask)
        if window > 0:
            mask = jnp.where(cols <= rows - window, NEG_INF, mask)
        s = s + mask
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _write():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    group: int = 1, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q: (BH, Sq, hd); k, v: (BKV, Sk, hd) with BH == BKV * group."""
    BH, Sq, hd = q.shape
    BKV, Sk, _ = k.shape
    assert BH == BKV * group, (q.shape, k.shape, group)
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)
    kern = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
                             causal=causal, window=window)
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik: (bh // group, ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik: (bh // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
