"""Pure-jnp oracles for every Pallas kernel (tests assert_allclose vs these)."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def morph_matmul_ref(x, w, active_n=None, active_k=None):
    """Zero-filled beyond active_n; contraction truncated at active_k.

    ``active_n`` / ``active_k`` may be per-batch sequences (len B) when x is
    (B, M, K) — each batch row is sliced at its own active widths, mirroring
    the kernel's per-row width masks."""
    K = x.shape[-1]
    N = w.shape[-1]

    def _per_batch(a):
        # sized sequence or >=1-d array (0-d arrays report __len__ but are
        # unsized scalars — treat them like python ints)
        return a is not None and (isinstance(a, (list, tuple))
                                  or getattr(a, "ndim", 0) >= 1)

    if x.ndim == 3 and (_per_batch(active_n) or _per_batch(active_k)):
        B = x.shape[0]
        ans = list(active_n) if _per_batch(active_n) else [active_n] * B
        aks = list(active_k) if _per_batch(active_k) else [active_k] * B
        return jnp.stack([morph_matmul_ref(x[b], w, ans[b], aks[b])
                          for b in range(B)])
    an = N if active_n is None else int(active_n)
    ak = K if active_k is None else int(active_k)
    y = jnp.einsum("...mk,kn->...mn", x[..., :, :ak].astype(jnp.float32),
                   w[:ak, :an].astype(jnp.float32))
    pad = [(0, 0)] * (y.ndim - 1) + [(0, N - an)]
    return jnp.pad(y, pad).astype(x.dtype)


def flash_attention_ref(q, k, v, *, group: int = 1, causal: bool = True, window: int = 0):
    """q: (BH, Sq, hd); k, v: (BKV, Sk, hd)."""
    BH, Sq, hd = q.shape
    k = jnp.repeat(k, group, axis=0)
    v = jnp.repeat(v, group, axis=0)
    s = jnp.einsum("bqh,bsh->bqs", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    rows = jnp.arange(Sq)[:, None]
    cols = jnp.arange(k.shape[1])[None, :]
    mask = jnp.zeros((Sq, k.shape[1]), jnp.float32)
    if causal:
        mask = jnp.where(cols > rows, -1e30, mask)
    if window > 0:
        mask = jnp.where(cols <= rows - window, -1e30, mask)
    w_ = jax.nn.softmax(s + mask, axis=-1)
    return jnp.einsum("bqs,bsh->bqh", w_, v.astype(jnp.float32)).astype(q.dtype)


def ssd_scan_ref(x, dt, A, B, C) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential SSD oracle. x: (BH,S,hp); dt: (BH,S); A: (BH,); B,C: (BH,S,n)."""

    def step(state, inp):
        x_t, dt_t, b_t, c_t, a = inp  # (hp,), (), (n,), (n,), ()
        decay = jnp.exp(dt_t * a)
        state = state * decay + jnp.outer(x_t * dt_t, b_t)
        return state, state @ c_t

    def per_row(x_r, dt_r, b_r, c_r, a):
        s0 = jnp.zeros((x_r.shape[-1], b_r.shape[-1]), jnp.float32)
        fs, ys = jax.lax.scan(
            step, s0,
            (x_r.astype(jnp.float32), dt_r.astype(jnp.float32),
             b_r.astype(jnp.float32), c_r.astype(jnp.float32),
             jnp.broadcast_to(a, dt_r.shape).astype(jnp.float32)))
        return ys, fs

    y, fs = jax.vmap(per_row)(x, dt, B, C, A)
    return y.astype(x.dtype), fs
