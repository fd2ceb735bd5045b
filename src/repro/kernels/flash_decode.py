"""flash_decode — single-token attention over an (optionally int8) KV cache.

The §Perf decode analysis (EXPERIMENTS.md Cell 2) leaves dequantization
materialization + layout churn as the residual memory-term gap: XLA
materializes the dequantized bf16 cache per layer. This kernel consumes the
int8 cache *directly* — dequantizing tile-by-tile in VMEM — and carries the
running (max, sum, acc) softmax statistics across KV tiles, so HBM sees only
the 1-byte cache stream.

Tile-level gating (same clock-gating idea as morph_matmul): ``kv_len``
arrives via scalar prefetch and tiles beyond the live cache length are
skipped entirely.

Layout: q (BH, hd); k/v (BKV, S, hd) int8 or bf16/f32; scales (BKV, S, 1)
when quantized. GQA: query row bh reads kv row bh // group.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bk, nk, scale, quant):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[0]
    live = ik * bk < kv_len  # tile-level gating on the live cache length

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (1, hd) block
        k = k_ref[0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        if quant:
            k = k * ks_ref[0].astype(jnp.float32)
            v = v * vs_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (1, bk)
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(cols < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _write():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "bk", "interpret"))
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 kv_len, k_scale: Optional[jnp.ndarray] = None,
                 v_scale: Optional[jnp.ndarray] = None, *, group: int = 1,
                 bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q: (BH, hd); k/v: (BKV, S, hd); scales (BKV, S, 1) iff int8 cache.

    ``kv_len`` is a dynamic scalar: positions >= kv_len are masked and whole
    tiles beyond it are skipped.
    """
    BH, hd = q.shape
    BKV, S, _ = k.shape
    assert BH == BKV * group
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    nk = S // bk
    quant = k.dtype == jnp.int8
    if quant:
        assert k_scale is not None and v_scale is not None
    else:
        k_scale = jnp.zeros((BKV, S, 1), jnp.float32)
        v_scale = jnp.zeros((BKV, S, 1), jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    kern = functools.partial(_kernel, bk=bk, nk=nk, scale=scale, quant=quant)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, nk),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda bh, ik, s: (bh, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, ik, s: (bh // group, ik, 0)),
            pl.BlockSpec((1, bk, 1), lambda bh, ik, s: (bh // group, ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, ik, s: (bh // group, ik, 0)),
            pl.BlockSpec((1, bk, 1), lambda bh, ik, s: (bh // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda bh, ik, s: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((BH, 1, hd),
                                       q.dtype if q.dtype != jnp.int8 else jnp.float32),
        interpret=interpret,
    )(jnp.asarray(kv_len, jnp.int32).reshape(1), q[:, None, :], k, k_scale, v, v_scale)
    return out[:, 0, :]


def flash_decode_ref(q, k, v, kv_len, k_scale=None, v_scale=None, *, group=1):
    """Pure-jnp oracle (also serves as the dequant reference)."""
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k.dtype == jnp.int8:
        kf = kf * k_scale.astype(jnp.float32)
        vf = vf * v_scale.astype(jnp.float32)
    kf = jnp.repeat(kf, group, axis=0)
    vf = jnp.repeat(vf, group, axis=0)
    s = jnp.einsum("bh,bsh->bs", q.astype(jnp.float32), kf) / math.sqrt(q.shape[-1])
    mask = jnp.arange(k.shape[1])[None, :] < kv_len
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bs,bsh->bh", w, vf).astype(q.dtype)
