"""morph_matmul — width-morphable blocked matmul (NeuroMorph clock-gate analogue).

The FPGA design clock-gates de-activated filters. A TPU MXU cannot gate
lanes, but a Pallas kernel *can* skip whole tiles: ``active_n`` / ``active_k``
arrive via scalar prefetch, and every (bm x bn) output tile or (bk) reduction
step that lies beyond the active width issues **no MXU op** (``pl.when``).
Because the grid is fixed at compile time, ONE executable serves every width
— switching morph modes at runtime is just a different scalar operand.

The kernel grid is natively batched: x may be (B, M, K) and ``active_n`` /
``active_k`` may be per-batch ``(B,)`` vectors, so a continuous-batching
serving engine can decode slots running *different* width modes in a single
launch (the grid's leading dimension walks the batch; each batch row reads
its own active widths from scalar prefetch). Tiles straddling the active
boundary are column/row-masked in-register, so results are exact for any
(not necessarily tile-aligned) active width.

Two implementations share one contract:

* ``impl="pallas"`` — the tile-skipping Pallas kernel (TPU fast path;
  ``interpret=True`` runs it on CPU for tests).
* ``impl="ref"`` — a fused jnp fallback (single masked dot, no per-row
  ``vmap``/``pallas_call`` recursion) used off-TPU on the serving hot path,
  where interpret-mode Pallas overhead would swamp a one-token decode.
* ``impl="auto"`` picks "pallas" on TPU backends and "ref" elsewhere.

Padding for non-tile-divisible dims happens in the *unjitted* wrapper, so a
given logical shape traces the jitted core exactly once (the old pad path
recursively re-entered the jit wrapper, tracing twice per shape).

``trace_count()`` exposes how many times the jitted core has been traced —
benchmarks and tests use it to *measure* the single-executable claim.

Layout: x (M, K) or (B, M, K) @ w (K, N) -> (M, N) / (B, M, N), zero-filled
beyond active_n. Block shapes default to MXU-native (128, 128, 128) tiles.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ActiveDim = Union[int, jnp.ndarray, None]

# Incremented inside the jitted core, so it advances only when jax *traces*
# (i.e. compiles a new executable), never on cached dispatches.
_TRACES = {"n": 0}


def trace_count() -> int:
    """Number of times the jitted core has been traced since import/reset."""
    return _TRACES["n"]


def reset_trace_count() -> None:
    _TRACES["n"] = 0


def default_impl() -> str:
    """"pallas" on TPU backends, fused "ref" everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _kernel(an_ref, ak_ref, x_ref, w_ref, o_ref, acc_ref, *, bm, bk, bn, nk):
    b = pl.program_id(0)
    j = pl.program_id(2)
    k = pl.program_id(3)
    active_n = an_ref[b]
    active_k = ak_ref[b]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_live = j * bn < active_n  # this output tile has live columns
    k_live = k * bk < active_k  # this reduction step has live rows

    @pl.when(jnp.logical_and(n_live, k_live))
    def _compute():
        x_blk = x_ref[0]
        w_blk = w_ref[...]
        # mask the partial boundary block of the contraction dim
        k_ids = k * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        w_blk = jnp.where(k_ids < active_k, w_blk, jnp.zeros_like(w_blk))
        acc_ref[...] += jnp.dot(x_blk, w_blk, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _write():
        n_ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        out = jnp.where(n_ids < active_n, acc_ref[...], jnp.zeros_like(acc_ref))
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "impl"))
def _morph_matmul_core(x, w, an, ak, *, block, interpret, impl):
    """Jitted core over tile-aligned (B, M, K) @ (K, N). an/ak: (B,) int32."""
    _TRACES["n"] += 1  # runs at trace time only — the compile counter
    B, M, K = x.shape
    N = w.shape[1]
    bm, bk, bn = block

    if impl == "ref":
        # fused fallback: one masked dot, batch-broadcast active widths
        k_ids = jax.lax.broadcasted_iota(jnp.int32, (1, 1, K), 2)
        xm = jnp.where(k_ids < ak[:, None, None], x, jnp.zeros_like(x))
        y = jax.lax.dot_general(
            xm, w.astype(x.dtype), (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        n_ids = jax.lax.broadcasted_iota(jnp.int32, (1, 1, N), 2)
        return jnp.where(n_ids < an[:, None, None], y,
                         jnp.zeros_like(y)).astype(x.dtype)

    nk = K // bk
    grid = (B, M // bm, N // bn, nk)
    kern = functools.partial(_kernel, bm=bm, bk=bk, bn=bn, nk=nk)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b, i, j, k, an_, ak_: (b, i, k)),
            pl.BlockSpec((bk, bn), lambda b, i, j, k, an_, ak_: (k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, k, an_, ak_: (b, i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kern, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((B, M, N), x.dtype),
        interpret=interpret,
    )(an, ak, x, w)


def _as_active(a: ActiveDim, full: int, batch: int) -> jnp.ndarray:
    """Normalize an active-dim operand to a (batch,) int32 vector."""
    if a is None:
        a = full
    a = jnp.asarray(a, jnp.int32)
    if a.ndim == 0:
        return jnp.broadcast_to(a, (batch,))
    if a.shape != (batch,):
        raise ValueError(f"active dim shape {a.shape} != ({batch},)")
    return a


def morph_matmul(x: jnp.ndarray, w: jnp.ndarray,
                 active_n: ActiveDim = None,
                 active_k: ActiveDim = None,
                 *, block: Tuple[int, int, int] = (128, 128, 128),
                 interpret: bool = False,
                 impl: str = "pallas") -> jnp.ndarray:
    """x: (M, K) or (B, M, K); w: (K, N). active_* are dynamic scalars or,
    for batched x, per-batch ``(B,)`` vectors. ``impl``: "pallas" | "ref" |
    "auto" (pallas on TPU, ref elsewhere)."""
    if impl == "auto":
        impl = default_impl()
    batched = x.ndim == 3
    if not batched:
        x = x[None]
    B, M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm, bk, bn = (min(block[0], M), min(block[1], K), min(block[2], N))
    an = _as_active(active_n, N, B)
    ak = _as_active(active_k, K, B)
    # Non-tile-divisible dims: zero-pad up to the next tile multiple *outside*
    # the jitted core (one trace per logical shape). The kernel's active_n /
    # active_k masking already zeroes everything beyond the true (K, N), so
    # padded columns/rows contribute nothing; padded M rows are sliced off.
    pad_m = -M % bm
    pad_k = -K % bk
    pad_n = -N % bn
    if pad_m or pad_k or pad_n:
        x = jnp.pad(x, ((0, 0), (0, pad_m), (0, pad_k)))
        w = jnp.pad(w, ((0, pad_k), (0, pad_n)))
    out = _morph_matmul_core(x, w, an, ak, block=(bm, bk, bn),
                             interpret=interpret, impl=impl)
    out = out[:, :M, :N]
    return out if batched else out[0]


def sharded_morph_matmul(mesh, x: jnp.ndarray, w: jnp.ndarray,
                         active_n: ActiveDim = None,
                         active_k: ActiveDim = None, *, axis: str = "model",
                         interpret: bool = False,
                         impl: str = "pallas") -> jnp.ndarray:
    """``morph_matmul`` on a device mesh: one kernel per shard.

    The compiler cannot partition a Mosaic kernel, so the kernel runs inside
    ``shard_map``. A row-gated projection (``active_k``) splits w's rows and
    x's last dim over ``axis`` and sums the partial products there; any
    other splits w's columns. Each shard clips the global active width to
    its own slice. A dim that ``axis`` does not divide stays replicated, and
    other mesh axes replicate everything. The row split sums partials in
    x.dtype: one extra rounding of the output at bf16.
    x: (B, M, K); w: (K, N); active_*: scalars or (B,) vectors.
    """
    B, _, K = x.shape
    N = w.shape[1]
    tp = dict(mesh.shape).get(axis, 1)
    an = _as_active(active_n, N, B)
    ak = _as_active(active_k, K, B)
    split = None
    if tp > 1 and active_k is not None and K % tp == 0:
        split = "k"
    elif tp > 1 and N % tp == 0:
        split = "n"

    def body(x, w, an, ak):
        if split == "n":
            n = w.shape[1]
            an = jnp.clip(an - jax.lax.axis_index(axis) * n, 0, n)
        elif split == "k":
            k = w.shape[0]
            ak = jnp.clip(ak - jax.lax.axis_index(axis) * k, 0, k)
        y = morph_matmul(x, w, an, ak, interpret=interpret, impl=impl)
        return jax.lax.psum(y, axis) if split == "k" else y

    P = jax.sharding.PartitionSpec
    x_spec = P(None, None, axis) if split == "k" else P()
    w_spec = {"k": P(axis, None), "n": P(None, axis)}.get(split, P())
    out_spec = P(None, None, axis) if split == "n" else P()
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(x_spec, w_spec, P(), P()),
                         out_specs=out_spec, check_vma=False)(x, w, an, ak)
