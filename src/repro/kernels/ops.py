"""jit'd model-facing wrappers around the Pallas kernels.

On this CPU container the kernels run in interpret mode (the kernel body
executes as Python/jnp on CPU — bit-accurate semantics, no TPU codegen); on a
real TPU backend ``interpret=False`` compiles to Mosaic. ``default_interpret``
picks automatically.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.morph_matmul import BLOCK as _MM_BLOCK
from repro.kernels.morph_matmul import morph_matmul as _morph_matmul
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def morph_matmul(x, w, active_n=None, active_k=None, *, block=_MM_BLOCK,
                 interpret: Optional[bool] = None, impl: str = "pallas"):
    itp = default_interpret() if interpret is None else interpret
    return _morph_matmul(x, w, active_n, active_k, block=block, interpret=itp,
                         impl=impl)


def flash_attention_bshd(q, k, v, *, causal=True, window: int = 0,
                         bq: int = 128, bk: int = 128,
                         interpret: Optional[bool] = None):
    """Model-layout wrapper. q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd) -> (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, k.shape[1], hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, v.shape[1], hd)
    itp = default_interpret() if interpret is None else interpret
    o = _flash(qf, kf, vf, group=group, causal=causal, window=window,
               bq=bq, bk=bk, interpret=itp)
    return o.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)


def ssd_scan_bshn(x, dt, A, B_, C_, *, chunk: int = 128,
                  interpret: Optional[bool] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Model-layout wrapper. x: (b,s,h,p); dt: (b,s,h); A: (h,); B,C: (b,s,g,n).

    Returns (y (b,s,h,p), final_state (b,h,p,n)) — matches models.ssm.ssd_chunked.
    """
    b, s, h, p = x.shape
    g, n = B_.shape[2], B_.shape[3]
    rep = h // g
    Bh = jnp.repeat(B_, rep, axis=2)
    Ch = jnp.repeat(C_, rep, axis=2)
    xf = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(b * h, s)
    Af = jnp.broadcast_to(A, (b, h)).reshape(b * h)
    Bf = Bh.transpose(0, 2, 1, 3).reshape(b * h, s, n)
    Cf = Ch.transpose(0, 2, 1, 3).reshape(b * h, s, n)
    itp = default_interpret() if interpret is None else interpret
    y, fs = _ssd_scan(xf, dtf, Af, Bf, Cf, chunk=chunk, interpret=itp)
    return (y.reshape(b, h, s, p).transpose(0, 2, 1, 3),
            fs.reshape(b, h, p, n))
