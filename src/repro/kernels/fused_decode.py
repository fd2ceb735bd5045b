"""fused_decode — persistent decode/verify superkernel (projection + attention).

The serving hot path used to lower each decode step to many small XLA ops:
three ``morph_matmul`` launches for QKV, a separate attention kernel, int8
dequant round-trips materialized in HBM, the output projection, and (for
token trees) a dense (B, n_nodes, S) ancestor-bias add. This module fuses
the whole attention layer step into ONE kernel per launch:

    active-width QKV projection -> RoPE -> (int8 quantize of the new K/V)
    -> paged/extended-KV attention with tile-level dequant
    -> active-width output projection

Per-batch active widths (``a_q``/``a_kv``), per-slot positions, and per-slot
page tables all arrive via **scalar prefetch**, so one executable per
depth x page-bucket serves every width mode with zero re-traces — the same
single-executable invariant ``morph_matmul`` (PR 2) and the paged compile
keys (PR 6) already enforce.

Two implementations share one contract (mirroring ``morph_matmul``):

* ``impl="pallas"`` — the fused Pallas kernel (TPU fast path;
  ``interpret=True`` runs it on CPU for tests).
* ``impl="ref"`` — a jnp fallback that mirrors the unfused
  ``models.layers`` decode/verify math **operation for operation** (same
  dots, same mask constants, same quantize round-trips, same ``constrain``
  pinning), so off-TPU the fused flag is bit-identical to the unfused path
  by construction.
* ``impl="auto"`` picks "pallas" on TPU backends and "ref" elsewhere.

Tree verify: the per-topology ancestor mask is **baked into the kernel at
compile time** (a static numpy (S, S) boolean, like Canopy/VTA baking
schedule constants into its conv2d kernel) instead of materializing the
dense (B, S, cache+S) additive bias the unfused path adds to the scores.
One executable per (depth, topology) — topologies are already compile keys.

Layout contract: the kernel always consumes the cache as a *page pool*
``(n_pages, page_size, KV, hd)`` plus a per-slot ``(B, P)`` int32 table.
Dense caches are normalized to this layout with an identity table (a free
reshape), so a single kernel body serves both the dense and the block-paged
cache. Garbage / unwritten / stale columns are excluded by absolute key
positions the kernel derives from each slot's scalar-prefetched position,
with the unfused path's rule (masked columns contribute exact zeros).

Mosaic layout rules shape the kernel bodies: per-head rows are built with
lane slices and sublane concatenation (a reshape that splits the lane dim
is refused), and every block is either (8, 128)-aligned or a whole array
dimension. ``tests/test_tpu_compile.py`` compiles both kernels for a
described TPU v5e.

``trace_count()`` counts wrapper traces under an enclosing ``jax.jit`` —
the zero-re-trace tests measure the single-executable claim with it. (The
wrappers are intentionally NOT jitted internally: the serving engine always
calls them inside its per-depth jitted step, and an inner jit would hide
retrace bugs from the counter.)
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.parallel.sharding import constrain
from repro.kernels.morph_matmul import morph_matmul as _morph_matmul

NEG_INF = -1e9          # mirrors layers.NEG_INF (additive-mask scale)
KERNEL_NEG_INF = -1e30  # in-kernel running-max init (flash_decode convention)

# Incremented in the wrapper bodies: under an enclosing jit this advances at
# trace time only, so it counts compiled executables exactly like
# morph_matmul's counter counts its jitted core.
_TRACES = {"n": 0}


def trace_count() -> int:
    return _TRACES["n"]


def reset_trace_count() -> None:
    _TRACES["n"] = 0


def default_impl() -> str:
    """"pallas" on TPU backends, mirrored "ref" everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# ---------------------------------------------------------------------------
# mirrored primitives (must stay operation-identical to models.layers)
# ---------------------------------------------------------------------------


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _rope(x, positions, theta: float):
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _matmul(x, w, dtype):
    return jax.lax.dot_general(
        x, w.astype(dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dtype)


def _morph_proj(x, w, active_n=None, active_k=None):
    if active_n is None and active_k is None:
        return _matmul(x, w, x.dtype)
    return _morph_matmul(x, w.astype(x.dtype), active_n, active_k, impl="auto")


def _attn_mask(q_pos, k_pos, causal: bool, window: int):
    m = jnp.zeros(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), jnp.float32)
    dq = q_pos[..., :, None]
    dk = k_pos[..., None, :]
    m = jnp.where(dk < 0, NEG_INF, m)
    if causal:
        m = jnp.where(dk > dq, NEG_INF, m)
    if window > 0:
        m = jnp.where(dk <= dq - window, NEG_INF, m)
    return m


def _gqa_scores(q, k, cfg):
    groups = cfg.n_heads // max(cfg.n_kv_heads, 1)
    B, Sq, H, hd = q.shape
    qg = q.reshape(B, Sq, cfg.n_kv_heads, groups, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k, preferred_element_type=jnp.float32)
    return s / math.sqrt(hd)


def _gqa_out(w, v, cfg):
    B = w.shape[0]
    o = jnp.einsum("bkgqs,bskh->bqkgh", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, o.shape[1], cfg.n_heads, cfg.head_dim)


def _attention_full(q, k, v, cfg, q_pos, k_pos, causal=True, bias=None):
    s = _gqa_scores(q, k, cfg)
    mask = _attn_mask(q_pos, k_pos, causal, cfg.sliding_window)
    s = s + mask[:, None, None] if mask.ndim == 3 else s + mask
    if bias is not None:
        s = s + bias
    w = jax.nn.softmax(s, axis=-1)
    return _gqa_out(w, v, cfg).astype(q.dtype)


def _quantize_kv(x):
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True).astype(jnp.float32) / 127.0
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-8)).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def _cache_kpos(pos, n_slots: int, window: int):
    idx = jnp.arange(n_slots)[None, :]
    if window:
        last = pos[:, None] - 1
        wraps = jnp.where(idx <= jnp.mod(last, n_slots), 0, 1)
        kpos = (jnp.floor_divide(last, n_slots) - wraps) * n_slots + idx
        return jnp.where(kpos < 0, -10**9, kpos)
    return jnp.where(idx < pos[:, None], idx, -10**9)


# ---------------------------------------------------------------------------
# reference implementation — operation-identical mirror of the unfused path
# ---------------------------------------------------------------------------


def _decode_ref(params, x, cache, pos, cfg, *, a_q, a_kv, pages, page_size):
    """Mirror of ``layers.mha_decode`` (self-attention branch), bit-exact."""
    dt = x.dtype
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    qpos = pos[:, None] if per_slot else jnp.full((1,), pos, jnp.int32)
    q = _split_heads(_morph_proj(x, params["wq"], active_n=a_q),
                     cfg.n_heads, cfg.head_dim)
    if cfg.use_rope:
        q = _rope(q, qpos, cfg.rope_theta)
    q = constrain(q, "decode_q")

    k_new = _split_heads(_morph_proj(x, params["wk"], active_n=a_kv),
                         cfg.n_kv_heads, cfg.head_dim)
    v_new = _split_heads(_morph_proj(x, params["wv"], active_n=a_kv),
                         cfg.n_kv_heads, cfg.head_dim)
    if cfg.use_rope:
        k_new = _rope(k_new, qpos, cfg.rope_theta)
    k_new = constrain(k_new, "decode_kv")
    v_new = constrain(v_new, "decode_kv")

    window = cfg.sliding_window
    if pages is not None:
        if not per_slot:
            raise ValueError("paged decode needs per-slot positions (pos (B,))")
        ps = page_size
        S = pages.shape[1] * ps
        slot = jnp.mod(pos, S) if window else jnp.minimum(pos, S - 1)
        page_ix = slot // ps
        off = slot - page_ix * ps
        phys = jnp.take_along_axis(pages, page_ix[:, None], axis=1)[:, 0]

        def write(buf, new):
            return buf.at[phys, off].set(new[:, 0].astype(buf.dtype))

        def view(buf):
            g = jnp.take(buf, pages, axis=0)
            return g.reshape((B, S) + buf.shape[2:])
    else:
        S = cache["k"].shape[1]
        slot = jnp.mod(pos, S) if window else jnp.minimum(pos, S - 1)

        def view(buf):
            return buf

        if per_slot:
            batch_ix = jnp.arange(B)

            def write(buf, new):
                return buf.at[batch_ix, slot].set(new[:, 0].astype(buf.dtype))
        else:
            def write(buf, new):
                return jax.lax.dynamic_update_slice_in_dim(
                    buf, new.astype(buf.dtype), slot, axis=1)

    new_cache = dict(cache)
    if cfg.kv_quant:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        new_cache["k"] = write(cache["k"], kq)
        new_cache["v"] = write(cache["v"], vq)
        new_cache["k_scale"] = write(cache["k_scale"], ks)
        new_cache["v_scale"] = write(cache["v_scale"], vs)
        k = _dequantize_kv(view(new_cache["k"]), view(new_cache["k_scale"]), dt)
        v = _dequantize_kv(view(new_cache["v"]), view(new_cache["v_scale"]), dt)
    else:
        new_cache["k"] = write(cache["k"], k_new)
        new_cache["v"] = write(cache["v"], v_new)
        k, v = view(new_cache["k"]).astype(dt), view(new_cache["v"]).astype(dt)
    if pages is not None:
        k = constrain(k, "decode_kv")
        v = constrain(v, "decode_kv")

    pos_b = pos[:, None] if per_slot else pos
    idx = jnp.arange(S)[None, :] if per_slot else jnp.arange(S)
    if window:
        wraps = jnp.where(idx <= jnp.mod(pos_b, S), 0, 1)
        kpos = (pos_b // S - wraps) * S + idx
        kpos = jnp.where(kpos < 0, -10**9, kpos)
    else:
        kpos = jnp.where(idx <= pos_b, idx, -10**9)
    out = _attention_full(q, k, v, cfg, qpos, kpos, causal=True)
    out = _morph_proj(out.reshape(B, 1, cfg.q_dim), params["wo"], active_k=a_q)
    return out, new_cache


def _verify_ref(params, x, cache, pos, cfg, *, a_q, a_kv, node_depth,
                tree_bias, pages, page_size):
    """Mirror of ``layers.mha_verify``, bit-exact."""
    dt = x.dtype
    B, S, _ = x.shape
    pos = jnp.asarray(pos, jnp.int32)
    offs = (jnp.arange(S, dtype=jnp.int32) if node_depth is None
            else jnp.asarray(node_depth, jnp.int32))
    qpos = pos[:, None] + offs[None, :]
    q = constrain(_split_heads(_morph_proj(x, params["wq"], active_n=a_q),
                               cfg.n_heads, cfg.head_dim), "decode_q")
    k_new = constrain(_split_heads(_morph_proj(x, params["wk"], active_n=a_kv),
                                   cfg.n_kv_heads, cfg.head_dim), "decode_kv")
    v_new = constrain(_split_heads(_morph_proj(x, params["wv"], active_n=a_kv),
                                   cfg.n_kv_heads, cfg.head_dim), "decode_kv")
    if cfg.use_rope:
        q = _rope(q, qpos, cfg.rope_theta)
        k_new = _rope(k_new, qpos, cfg.rope_theta)
    q = constrain(q, "decode_q")
    k_new = constrain(k_new, "decode_kv")
    v_new = constrain(v_new, "decode_kv")

    if pages is not None:
        Sv = pages.shape[1] * page_size

        def _view(buf):
            g = jnp.take(buf, pages, axis=0)
            return g.reshape((B, Sv) + buf.shape[2:])

        kc, vc = _view(cache["k"]), _view(cache["v"])
        if cfg.kv_quant and "k_scale" in cache:
            kc = _dequantize_kv(kc, _view(cache["k_scale"]), dt)
            vc = _dequantize_kv(vc, _view(cache["v_scale"]), dt)
    else:
        kc, vc = cache["k"], cache["v"]
    if cfg.kv_quant and "k_scale" in cache and pages is None:
        kc = _dequantize_kv(kc, cache["k_scale"], dt)
        vc = _dequantize_kv(vc, cache["v_scale"], dt)
    if cfg.kv_quant and "k_scale" in cache:
        kq, ks_ = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        k_att = _dequantize_kv(kq, ks_, dt)
        v_att = _dequantize_kv(vq, vs, dt)
    else:
        k_att, v_att = k_new, v_new
    kc = constrain(kc.astype(dt), "decode_kv")
    vc = constrain(vc.astype(dt), "decode_kv")
    kpos_c = _cache_kpos(pos, kc.shape[1], cfg.sliding_window)
    k_ext = jnp.concatenate([kc, k_att], axis=1)
    v_ext = jnp.concatenate([vc, v_att], axis=1)
    kpos = jnp.concatenate([kpos_c, qpos], axis=1)
    bias = None
    if tree_bias is not None:
        bias = jnp.concatenate(
            [jnp.zeros((S, kc.shape[1]), jnp.float32),
             jnp.asarray(tree_bias, jnp.float32)], axis=1)
    out = _attention_full(q, k_ext, v_ext, cfg, qpos, kpos, causal=True,
                          bias=bias)
    out = _morph_proj(out.reshape(B, S, cfg.q_dim), params["wo"], active_k=a_q)
    return out, {"k": k_new, "v": v_new}


# ---------------------------------------------------------------------------
# Pallas superkernels
# ---------------------------------------------------------------------------


def _pick_bk(S: int, cap: int = 128) -> int:
    """Largest divisor of S not exceeding ``cap`` (exact tiling, no pad)."""
    for bk in range(min(cap, S), 0, -1):
        if S % bk == 0:
            return bk
    return 1


def _as_pool(cache, pages, page_size, B):
    """Normalize the KV cache to (pool, table, bk, nk, S) layout.

    Paged caches pass through (pool pages ARE the tiles). Dense caches are
    reshaped — a free relayout — to a (B*nk, bk, KV, hd) pool with an
    identity table, so one kernel body serves both layouts.
    """
    if pages is not None:
        ps = page_size
        S = pages.shape[1] * ps
        return dict(cache), pages, ps, pages.shape[1], S
    S = cache["k"].shape[1]
    bk = _pick_bk(S)
    nk = S // bk
    pool = {kk: v.reshape((B * nk, bk) + v.shape[2:]) for kk, v in cache.items()}
    table = (jnp.arange(B, dtype=jnp.int32)[:, None] * nk
             + jnp.arange(nk, dtype=jnp.int32)[None, :])
    return pool, table, bk, nk, S


def _rope_rows(x, positions, theta: float):
    """In-kernel RoPE. x: (R, hd) f32; positions: (R, 1) or scalar f32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jax.lax.broadcasted_iota(jnp.int32, (1, half), 1)
                    .astype(jnp.float32) * (math.log(theta) / half))
    ang = positions * freqs  # broadcast
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _heads_to_rows(x, n: int, hd: int):
    """(R, n*hd) -> (n*R, hd), head-major rows (row h*R + r).

    Lane slices plus a sublane concat: Mosaic refuses a reshape that splits
    the lane dimension, and head_dim is often below the 128-lane width.
    """
    return jnp.concatenate([x[:, h * hd:(h + 1) * hd] for h in range(n)],
                           axis=0)


def _rows_to_heads(x, n: int, R: int):
    """Inverse of ``_heads_to_rows``: (n*R, hd) -> (R, n*hd)."""
    return jnp.concatenate([x[h * R:(h + 1) * R, :] for h in range(n)],
                           axis=1)


def _proj(x, w_ref, active):
    """Active-width projection (morph_matmul's column gate), f32 result."""
    y = jax.lax.dot_general(x, w_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    return jnp.where(cols < active, y, 0.0)


def _decode_valid(p, ik, bk: int, S: int, window: int):
    """(1, 1, bk) mask of live cache columns in KV tile ``ik`` for a slot at
    absolute position ``p`` (a scalar), derived in-kernel from ``p`` alone.

    Same rule as the unfused path: column ``c`` holds absolute position
    ``kpos(c)`` (rolling buffer under a sliding window), live when
    ``0 <= kpos <= p`` and inside the window; the slot being written this
    step is excluded (stale until the write-back; the kernel's in-register
    extension column stands in for it).
    """
    col = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
    if window:
        slot = jax.lax.rem(p, S)
        wraps = jnp.where(col <= slot, 0, 1)
        kp = (jax.lax.div(p, S) - wraps) * S + col
        valid = (kp >= 0) & (kp <= p) & (kp > p - window)
    else:
        slot = jnp.minimum(p, S - 1)
        valid = col <= p
    return valid & (col != slot)


def _verify_kpos(p, ik, bk: int, S: int, window: int):
    """(1, 1, bk) absolute positions of the committed cache columns in tile
    ``ik`` before a verify pass at ``p`` (``_cache_kpos`` in-kernel); a
    negative value marks a column that holds no position."""
    col = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
    if window:
        # p == 0 has no live tile (lens is 0), so clamping keeps the scalar
        # division non-negative without changing any live column
        last = jnp.maximum(p - 1, 0)
        wraps = jnp.where(col <= jax.lax.rem(last, S), 0, 1)
        return (jax.lax.div(last, S) - wraps) * S + col
    return jnp.where(col < p, col, -1)


def _online_softmax_update(s, valid, v, m_s, l_s, acc_s, KV: int, R: int,
                           hd: int):
    """Flash-attention accumulator update for one KV tile.

    s: (KV, R, bk) scores; valid broadcastable to s; v: (KV, bk, hd). The
    running max / sum / accumulator live in (KV*R, 1|hd) scratch rows.
    Explicit zeroing keeps fully-masked tiles exact (m_new can sit at
    KERNEL_NEG_INF, where exp(s - m_new) would be 1, not 0).
    """
    s = jnp.where(valid, s, KERNEL_NEG_INF)
    m_prev = m_s[...].reshape(KV, R, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_s[...] = (l_s[...].reshape(KV, R, 1) * alpha
                + jnp.sum(pexp, axis=-1, keepdims=True)).reshape(KV * R, 1)
    pv = jax.lax.dot_general(pexp, v, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_s[...] = (acc_s[...].reshape(KV, R, hd) * alpha
                  + pv).reshape(KV * R, hd)
    m_s[...] = m_new.reshape(KV * R, 1)


def _load_kv_tile(k_ref, ks_ref, v_ref, vs_ref, quant: bool):
    """One cache tile as head-major f32 (KV, bk, hd) K and V."""
    k = k_ref[0].astype(jnp.float32)  # (bk, KV, hd)
    v = v_ref[0].astype(jnp.float32)
    if quant:
        k = k * ks_ref[0].astype(jnp.float32)
        v = v * vs_ref[0].astype(jnp.float32)
    return k.transpose(1, 0, 2), v.transpose(1, 0, 2)


def _decode_kernel(lens_ref, pos_ref, aq_ref, akv_ref, tbl_ref,
                   x_ref, wq_ref, wk_ref, wv_ref, wo_ref,
                   k_ref, ks_ref, v_ref, vs_ref,
                   o_ref, kn_ref, vn_ref, kns_ref, vns_ref,
                   q_s, ke_s, ve_s, m_s, l_s, acc_s,
                   *, bk, nk, H, KV, hd, scale, window, quant, use_rope,
                   rope_theta):
    b = pl.program_id(0)
    ik = pl.program_id(1)
    G = H // KV
    p = pos_ref[b]
    aq = aq_ref[b]
    akv = akv_ref[b]

    @pl.when(ik == 0)
    def _project():
        x = x_ref[0]  # (1, dm), weights' dtype
        pf = p.astype(jnp.float32)
        q = _heads_to_rows(_proj(x, wq_ref, aq), H, hd)     # (H, hd)
        kn = _heads_to_rows(_proj(x, wk_ref, akv), KV, hd)  # (KV, hd)
        vn = _heads_to_rows(_proj(x, wv_ref, akv), KV, hd)
        if use_rope:
            q = _rope_rows(q, pf, rope_theta)
            kn = _rope_rows(kn, pf, rope_theta)
        if quant:
            ksc = jnp.max(jnp.abs(kn), axis=-1, keepdims=True) / 127.0
            vsc = jnp.max(jnp.abs(vn), axis=-1, keepdims=True) / 127.0
            kq = jnp.round(kn / jnp.maximum(ksc, 1e-8))
            vq = jnp.round(vn / jnp.maximum(vsc, 1e-8))
            kn_ref[0] = kq.astype(kn_ref.dtype)
            vn_ref[0] = vq.astype(vn_ref.dtype)
            kns_ref[0] = ksc.astype(kns_ref.dtype)
            vns_ref[0] = vsc.astype(vns_ref.dtype)
            # attend over the same quantize->dequantize round trip the
            # sequential decode reads back from the cache (scales via bf16)
            ke = kq * ksc.astype(jnp.bfloat16).astype(jnp.float32)
            ve = vq * vsc.astype(jnp.bfloat16).astype(jnp.float32)
        else:
            kn_ref[0] = kn.astype(kn_ref.dtype)
            vn_ref[0] = vn.astype(vn_ref.dtype)
            kns_ref[0] = jnp.zeros_like(kns_ref[0])
            vns_ref[0] = jnp.zeros_like(vns_ref[0])
            ke = kn.astype(kn_ref.dtype).astype(jnp.float32)
            ve = vn.astype(vn_ref.dtype).astype(jnp.float32)
        q_s[...] = q
        ke_s[...] = ke
        ve_s[...] = ve
        m_s[...] = jnp.full_like(m_s, KERNEL_NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    live = jnp.logical_and(ik < nk, ik * bk < lens_ref[b])

    @pl.when(live)
    def _tile():
        kt, vt = _load_kv_tile(k_ref, ks_ref, v_ref, vs_ref, quant)
        qg = q_s[...].reshape(KV, G, hd)
        s = jax.lax.dot_general(qg, kt, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        valid = _decode_valid(p, ik, bk, nk * bk, window)
        _online_softmax_update(s, valid, vt, m_s, l_s, acc_s, KV, G, hd)

    @pl.when(ik == nk)
    def _finish():
        # extension column: the new (round-tripped) K/V at absolute pos p —
        # always live (p <= p, inside any window)
        qg = q_s[...].reshape(KV, G, hd)
        ke = ke_s[...][:, None, :]  # (KV, 1, hd)
        ve = ve_s[...][:, None, :]
        s_e = jnp.sum(qg * ke, axis=-1, keepdims=True) * scale  # (KV, G, 1)
        m_prev = m_s[...].reshape(KV, G, 1)
        m_new = jnp.maximum(m_prev, s_e)
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s_e - m_new)
        l = l_s[...].reshape(KV, G, 1) * alpha + pexp
        acc = acc_s[...].reshape(KV, G, hd) * alpha + pexp * ve
        out = (acc / jnp.maximum(l, 1e-20)).reshape(H, hd)
        oh = _rows_to_heads(out, H, 1).astype(wo_ref.dtype)  # (1, H*hd)
        # wo's active_k contraction gate
        ocols = jax.lax.broadcasted_iota(jnp.int32, oh.shape, 1)
        oh = jnp.where(ocols < aq, oh, jnp.zeros_like(oh))
        o = jax.lax.dot_general(oh, wo_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[0] = o.astype(o_ref.dtype)


def _weight_specs(dm: int, q_dim: int, kv_dim: int, d_out: int):
    """Whole-matrix weight blocks: constant index maps, so each weight is
    DMA'd once per call; one buffer each, since it is never re-fetched."""
    def spec(shape):
        return pl.BlockSpec(shape, lambda b, ik, *s: (0, 0),
                            pipeline_mode=pl.Buffered(1))
    return [spec((dm, q_dim)), spec((dm, kv_dim)), spec((dm, kv_dim)),
            spec((q_dim, d_out))]


def _kv_specs(bk: int, KV: int, hd: int, nk: int, quant: bool):
    """Cache tile specs through the page table. Tiles past a slot's length
    map to its last live tile, so the pipeline issues no DMA for them."""
    def last_live(b, lens_):
        return jnp.maximum(lens_[b] - 1, 0) // bk

    def pool_map(b, ik, lens_, pos_, aq_, akv_, tbl_):
        return (tbl_[b, jnp.minimum(jnp.minimum(ik, nk - 1),
                                    last_live(b, lens_))], 0, 0, 0)

    def scale_map(b, ik, lens_, pos_, aq_, akv_, tbl_):
        if quant:
            return pool_map(b, ik, lens_, pos_, aq_, akv_, tbl_)
        return (0, 0, 0, 0)

    return [pl.BlockSpec((1, bk, KV, hd), pool_map),
            pl.BlockSpec((1, bk, KV, 1), scale_map),
            pl.BlockSpec((1, bk, KV, hd), pool_map),
            pl.BlockSpec((1, bk, KV, 1), scale_map)]


# Resident blocks a fused launch may hold: v5e has 128 MiB of VMEM per core,
# and the scoped limit below adds half again plus 8 MiB for Mosaic's own
# scratch, which stays under it.
_VMEM_BUDGET = 72 * 2**20


def _compiler_params(weights, x_block_bytes: int, kv_block_bytes: int,
                     scratch_bytes: int):
    """Scoped-VMEM limit sized from the blocks the kernel keeps resident:
    single-buffered weights plus double-buffered activation and KV tiles,
    with headroom for Mosaic's internal scratch. Refuses (at trace time)
    a layer whose attention weights cannot stay resident."""
    w_bytes = sum(int(np.prod(w.shape)) * w.dtype.itemsize for w in weights)
    need = w_bytes + 2 * (x_block_bytes + kv_block_bytes) + scratch_bytes
    if need > _VMEM_BUDGET:
        raise ValueError(
            f"fused decode keeps one layer's attention weights resident in "
            f"VMEM: {need / 2**20:.1f} MiB exceeds the "
            f"{_VMEM_BUDGET / 2**20:.0f} MiB budget; serve this width "
            f"unfused (fused=False)")
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(max(need * 1.5, 16 * 2**20)) + (8 << 20))


def _decode_pallas(params, x, cache, pos, cfg, *, a_q, a_kv, pages, page_size,
                   interpret):
    dt = x.dtype
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    window = cfg.sliding_window
    quant = bool(cfg.kv_quant)
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    if pages is not None and not per_slot:
        raise ValueError("paged decode needs per-slot positions (pos (B,))")
    pos_b = pos if per_slot else jnp.broadcast_to(pos, (B,))
    pool, table, bk, nk, S = _as_pool(cache, pages, page_size, B)
    slot = jnp.mod(pos_b, S) if window else jnp.minimum(pos_b, S - 1)
    lens = (jnp.where(pos_b > 0, S, 0) if window
            else jnp.minimum(pos_b + 1, S)).astype(jnp.int32)

    # weights in the activation dtype, as the unfused projections cast them
    weights = [params[n].astype(dt) for n in ("wq", "wk", "wv", "wo")]
    dm = x.shape[-1]
    d_out = weights[3].shape[1]
    cache_dt = pool["k"].dtype
    if quant:
        ksp, vsp = pool["k_scale"], pool["v_scale"]
    else:
        ksp = jnp.zeros((1, bk, KV, 1), jnp.float32)
        vsp = ksp

    kern = functools.partial(
        _decode_kernel, bk=bk, nk=nk, H=H, KV=KV, hd=hd,
        scale=1.0 / math.sqrt(hd), window=window, quant=quant,
        use_rope=bool(cfg.use_rope), rope_theta=cfg.rope_theta)
    scratch = [
        pltpu.VMEM((H, hd), jnp.float32),   # q
        pltpu.VMEM((KV, hd), jnp.float32),  # new k (round-tripped)
        pltpu.VMEM((KV, hd), jnp.float32),  # new v
        pltpu.VMEM((H, 1), jnp.float32),    # running max
        pltpu.VMEM((H, 1), jnp.float32),    # running sum
        pltpu.VMEM((H, hd), jnp.float32),   # running acc
    ]
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, nk + 1),
        in_specs=[pl.BlockSpec((1, 1, dm), lambda b, ik, *s: (b, 0, 0))]
        + _weight_specs(dm, H * hd, KV * hd, d_out)
        + _kv_specs(bk, KV, hd, nk, quant),
        out_specs=[
            pl.BlockSpec((1, 1, d_out), lambda b, ik, *s: (b, 0, 0)),
            pl.BlockSpec((1, KV, hd), lambda b, ik, *s: (b, 0, 0)),
            pl.BlockSpec((1, KV, hd), lambda b, ik, *s: (b, 0, 0)),
            pl.BlockSpec((1, KV, 1), lambda b, ik, *s: (b, 0, 0)),
            pl.BlockSpec((1, KV, 1), lambda b, ik, *s: (b, 0, 0)),
        ],
        scratch_shapes=scratch,
    )
    kv_bytes = 2 * bk * KV * (hd * cache_dt.itemsize + 128 * 4)
    out, k_new, v_new, k_sc, v_sc = pl.pallas_call(
        kern, grid_spec=gs,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, d_out), dt),
            jax.ShapeDtypeStruct((B, KV, hd), jnp.int8 if quant else cache_dt),
            jax.ShapeDtypeStruct((B, KV, hd), jnp.int8 if quant else cache_dt),
            jax.ShapeDtypeStruct((B, KV, 1), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, KV, 1), jnp.bfloat16),
        ],
        compiler_params=_compiler_params(
            weights, (dm + d_out) * dt.itemsize, kv_bytes,
            (3 * H + 2 * KV) * 128 * 4),
        interpret=interpret,
    )(lens, pos_b, jnp.asarray(a_q, jnp.int32) if a_q is not None
      else jnp.full((B,), H * hd, jnp.int32),
      jnp.asarray(a_kv, jnp.int32) if a_kv is not None
      else jnp.full((B,), KV * hd, jnp.int32),
      table.astype(jnp.int32),
      x, *weights,
      pool["k"], ksp, pool["v"], vsp)

    # cache write-back (same formulas as the unfused path)
    new_cache = dict(cache)
    if pages is not None:
        page_ix = slot // page_size
        off = slot - page_ix * page_size
        phys = jnp.take_along_axis(pages, page_ix[:, None], axis=1)[:, 0]

        def write(buf, new):
            return buf.at[phys, off].set(new.astype(buf.dtype))
    else:
        batch_ix = jnp.arange(B)

        def write(buf, new):
            return buf.at[batch_ix, slot].set(new.astype(buf.dtype))

    new_cache["k"] = write(cache["k"], k_new)
    new_cache["v"] = write(cache["v"], v_new)
    if quant:
        new_cache["k_scale"] = write(cache["k_scale"], k_sc)
        new_cache["v_scale"] = write(cache["v_scale"], v_sc)
    return out, new_cache


def _verify_kernel(lens_ref, pos_ref, aq_ref, akv_ref, tbl_ref,
                   x_ref, wq_ref, wk_ref, wv_ref, wo_ref,
                   k_ref, ks_ref, v_ref, vs_ref, qoff_ref, ext_ref,
                   o_ref, kn_ref, vn_ref,
                   q_s, ke_s, ve_s, m_s, l_s, acc_s,
                   *, bk, nkc, S, H, KV, hd, scale, window, quant, use_rope,
                   rope_theta):
    """Verify/tree-verify superkernel over S query positions.

    Rows of the per-head state are head-major (``h*S + s``). ``qoff_ref``
    (H*S, 1) holds each row's node depth and ``ext_ref`` (G*S, S) the
    row-to-new-column mask; both are built from STATIC numpy in the wrapper,
    so under the serving jit they are constants of the executable (one
    executable per topology), replacing the unfused path's dense
    (B, S, cache+S) additive bias."""
    b = pl.program_id(0)
    ik = pl.program_id(1)
    G = H // KV
    p = pos_ref[b]
    aq = aq_ref[b]
    akv = akv_ref[b]

    @pl.when(ik == 0)
    def _project():
        x = x_ref[0]  # (S, dm), weights' dtype
        qpos = (p + qoff_ref[...]).astype(jnp.float32)  # (H*S, 1)
        q = _heads_to_rows(_proj(x, wq_ref, aq), H, hd)     # (H*S, hd)
        kn = _heads_to_rows(_proj(x, wk_ref, akv), KV, hd)  # (KV*S, hd)
        vn = _heads_to_rows(_proj(x, wv_ref, akv), KV, hd)
        if use_rope:
            q = _rope_rows(q, qpos, rope_theta)
            kn = _rope_rows(kn, qpos[:KV * S], rope_theta)
        # candidates are returned RAW (commit re-quantizes); attention uses
        # the round trip when the cache is int8
        kn_ref[0] = kn.reshape(KV, S, hd).transpose(1, 0, 2).astype(
            kn_ref.dtype)
        vn_ref[0] = vn.reshape(KV, S, hd).transpose(1, 0, 2).astype(
            vn_ref.dtype)
        if quant:
            ksc = jnp.max(jnp.abs(kn), axis=-1, keepdims=True) / 127.0
            vsc = jnp.max(jnp.abs(vn), axis=-1, keepdims=True) / 127.0
            ke = (jnp.round(kn / jnp.maximum(ksc, 1e-8))
                  * ksc.astype(jnp.bfloat16).astype(jnp.float32))
            ve = (jnp.round(vn / jnp.maximum(vsc, 1e-8))
                  * vsc.astype(jnp.bfloat16).astype(jnp.float32))
        else:
            ke, ve = kn, vn
        q_s[...] = q
        ke_s[...] = ke
        ve_s[...] = ve
        m_s[...] = jnp.full_like(m_s, KERNEL_NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    live = jnp.logical_and(ik < nkc, ik * bk < lens_ref[b])

    @pl.when(live)
    def _tile():
        kt, vt = _load_kv_tile(k_ref, ks_ref, v_ref, vs_ref, quant)
        qg = q_s[...].reshape(KV, G * S, hd)
        s = jax.lax.dot_general(qg, kt, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        kp = _verify_kpos(p, ik, bk, nkc * bk, window)  # (1, 1, bk)
        row_qpos = (p + qoff_ref[...][:G * S]).reshape(1, G * S, 1)
        valid = (kp >= 0) & (kp <= row_qpos)
        if window:
            valid = valid & (kp > row_qpos - window)
        _online_softmax_update(s, valid, vt, m_s, l_s, acc_s, KV, G * S, hd)

    @pl.when(ik == nkc)
    def _finish():
        qg = q_s[...].reshape(KV, G * S, hd)
        ke = ke_s[...].reshape(KV, S, hd)
        ve = ve_s[...].reshape(KV, S, hd)
        s_e = jax.lax.dot_general(qg, ke, (((2,), (2,)), ((0,), (0,))),
                                  preferred_element_type=jnp.float32) * scale
        emask = (ext_ref[...] != 0).reshape(1, G * S, S)
        s_e = jnp.where(emask, s_e, KERNEL_NEG_INF)
        m_prev = m_s[...].reshape(KV, G * S, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s_e, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.where(emask, jnp.exp(s_e - m_new), 0.0)
        l = (l_s[...].reshape(KV, G * S, 1) * alpha
             + jnp.sum(pexp, axis=-1, keepdims=True))
        pv = jax.lax.dot_general(pexp, ve, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc = acc_s[...].reshape(KV, G * S, hd) * alpha + pv
        out = (acc / jnp.maximum(l, 1e-20)).reshape(H * S, hd)
        oh = _rows_to_heads(out, H, S).astype(wo_ref.dtype)  # (S, H*hd)
        ocols = jax.lax.broadcasted_iota(jnp.int32, oh.shape, 1)
        oh = jnp.where(ocols < aq, oh, jnp.zeros_like(oh))
        o = jax.lax.dot_general(oh, wo_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[0] = o.astype(o_ref.dtype)


def _ext_mask_np(offs: np.ndarray, window: int,
                 tree_bias: Optional[np.ndarray]) -> np.ndarray:
    """Static (S, S) boolean: may row attend to new-KV column? Linear verify
    is causal-in-offset; tree verify bakes the topology's ancestor mask
    (which subsumes depth causality). Both honor the sliding window."""
    if tree_bias is None:
        ok = offs[None, :] <= offs[:, None]
    else:
        ok = np.asarray(tree_bias) == 0.0
    if window:
        ok = ok & (offs[None, :] > offs[:, None] - window)
    return np.ascontiguousarray(ok)


def _verify_pallas(params, x, cache, pos, cfg, *, a_q, a_kv, node_depth,
                   tree_bias, pages, page_size, interpret):
    dt = x.dtype
    B, S, dm = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KV
    window = cfg.sliding_window
    quant = bool(cfg.kv_quant)
    pos = jnp.asarray(pos, jnp.int32)
    offs = (np.arange(S, dtype=np.int32) if node_depth is None
            else np.asarray(node_depth, np.int32))
    # per-row static operands in the kernel's head-major row order
    qoff = np.tile(offs, H)[:, None].astype(np.int32)             # (H*S, 1)
    ext = np.tile(_ext_mask_np(offs, window, tree_bias), (G, 1))  # (G*S, S)
    pool, table, bk, nkc, Sc = _as_pool(cache, pages, page_size, B)
    lens = (jnp.where(pos > 0, Sc, 0) if window
            else jnp.minimum(pos, Sc)).astype(jnp.int32)

    weights = [params[n].astype(dt) for n in ("wq", "wk", "wv", "wo")]
    d_out = weights[3].shape[1]
    cache_dt = pool["k"].dtype
    if quant:
        ksp, vsp = pool["k_scale"], pool["v_scale"]
    else:
        ksp = jnp.zeros((1, bk, KV, 1), jnp.float32)
        vsp = ksp

    kern = functools.partial(
        _verify_kernel, bk=bk, nkc=nkc, S=S, H=H, KV=KV, hd=hd,
        scale=1.0 / math.sqrt(hd), window=window, quant=quant,
        use_rope=bool(cfg.use_rope), rope_theta=cfg.rope_theta)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, nkc + 1),
        in_specs=[pl.BlockSpec((1, S, dm), lambda b, ik, *s: (b, 0, 0))]
        + _weight_specs(dm, H * hd, KV * hd, d_out)
        + _kv_specs(bk, KV, hd, nkc, quant)
        + [pl.BlockSpec((H * S, 1), lambda b, ik, *s: (0, 0)),
           pl.BlockSpec((G * S, S), lambda b, ik, *s: (0, 0))],
        out_specs=[
            pl.BlockSpec((1, S, d_out), lambda b, ik, *s: (b, 0, 0)),
            pl.BlockSpec((1, S, KV, hd), lambda b, ik, *s: (b, 0, 0, 0)),
            pl.BlockSpec((1, S, KV, hd), lambda b, ik, *s: (b, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((H * S, hd), jnp.float32),
            pltpu.VMEM((KV * S, hd), jnp.float32),
            pltpu.VMEM((KV * S, hd), jnp.float32),
            pltpu.VMEM((H * S, 1), jnp.float32),
            pltpu.VMEM((H * S, 1), jnp.float32),
            pltpu.VMEM((H * S, hd), jnp.float32),
        ],
    )
    kv_bytes = 2 * bk * KV * (hd * cache_dt.itemsize + 128 * 4)
    out, k_new, v_new = pl.pallas_call(
        kern, grid_spec=gs,
        out_shape=[
            jax.ShapeDtypeStruct((B, S, d_out), dt),
            jax.ShapeDtypeStruct((B, S, KV, hd), dt),
            jax.ShapeDtypeStruct((B, S, KV, hd), dt),
        ],
        compiler_params=_compiler_params(
            weights, S * (dm + d_out) * dt.itemsize, kv_bytes,
            (3 * H + 2 * KV) * S * 128 * 4),
        interpret=interpret,
    )(lens, pos, jnp.asarray(a_q, jnp.int32) if a_q is not None
      else jnp.full((B,), H * hd, jnp.int32),
      jnp.asarray(a_kv, jnp.int32) if a_kv is not None
      else jnp.full((B,), KV * hd, jnp.int32),
      table.astype(jnp.int32),
      x, *weights,
      pool["k"], ksp, pool["v"], vsp,
      jnp.asarray(qoff), jnp.asarray(ext, jnp.int32))
    return out, {"k": k_new, "v": v_new}


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _norm_active(a, B):
    """Broadcast an active-width operand to (B,) int32 (or keep None)."""
    if a is None:
        return None
    a = jnp.asarray(a, jnp.int32)
    return jnp.broadcast_to(a, (B,)) if a.ndim == 0 else a


def fused_decode_step(params, x, cache, pos, cfg, *, active=None, pages=None,
                      page_size=0, impl: str = "auto",
                      interpret: Optional[bool] = None):
    """Fused one-token decode: same contract as ``layers.mha_decode``
    (self-attention branch) — returns (out (B,1,d), new_cache).

    ``impl="ref"`` replays the unfused op sequence exactly (bit-identical);
    ``impl="pallas"`` runs the superkernel; ``"auto"`` picks per backend.
    """
    _TRACES["n"] += 1
    if impl == "auto":
        impl = default_impl()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    a_q = active.get("q_dim") if active else None
    a_kv = active.get("kv_dim") if active else None
    if impl == "ref":
        return _decode_ref(params, x, cache, pos, cfg, a_q=a_q, a_kv=a_kv,
                           pages=pages, page_size=page_size)
    B = x.shape[0]
    return _decode_pallas(params, x, cache, pos, cfg,
                          a_q=_norm_active(a_q, B), a_kv=_norm_active(a_kv, B),
                          pages=pages, page_size=page_size,
                          interpret=interpret)


def fused_verify(params, x, cache, pos, cfg, *, active=None, node_depth=None,
                 tree_bias=None, pages=None, page_size=0, impl: str = "auto",
                 interpret: Optional[bool] = None):
    """Fused verify / tree-verify: same contract as ``layers.mha_verify`` —
    returns (out (B,S,d), {"k","v"} raw candidates). ``node_depth`` /
    ``tree_bias`` must be static (numpy): the topology's ancestor mask is
    baked into the executable, not passed as a dense operand."""
    _TRACES["n"] += 1
    if impl == "auto":
        impl = default_impl()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    a_q = active.get("q_dim") if active else None
    a_kv = active.get("kv_dim") if active else None
    if impl == "ref":
        return _verify_ref(params, x, cache, pos, cfg, a_q=a_q, a_kv=a_kv,
                           node_depth=node_depth, tree_bias=tree_bias,
                           pages=pages, page_size=page_size)
    B = x.shape[0]
    return _verify_pallas(params, x, cache, pos, cfg,
                          a_q=_norm_active(a_q, B),
                          a_kv=_norm_active(a_kv, B),
                          node_depth=node_depth, tree_bias=tree_bias,
                          pages=pages, page_size=page_size,
                          interpret=interpret)
