"""Core transformer layers: norms, positions, MLPs, GQA attention.

Everything is functional: ``init_*`` builds a param subtree, ``apply`` style
functions consume (params, inputs). Activations run in ``cfg.dtype``; params
are stored in ``cfg.param_dtype``. All matmuls accumulate in f32
(``preferred_element_type``) — the TPU MXU native mode.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.parallel.sharding import constrain, current_mesh

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _dtype(name: str):
    return jnp.dtype(name)


def dense_init(key, shape, in_axis: int = 0, scale: float = 1.0, dtype=jnp.float32):
    """Truncated-normal fan-in init (maxtext-style)."""
    fan_in = shape[in_axis] if in_axis >= 0 else int(np.prod(shape[:-1]))
    std = scale / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def matmul(x, w, dtype):
    if _BF16_GRAD_MATMUL:
        return _matmul_bf16g(x.astype(dtype), w.astype(dtype)).astype(dtype)
    return jax.lax.dot_general(
        x, w.astype(dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dtype)


def morph_proj(x, w, active_n=None, active_k=None):
    """Width-gated projection on the decode hot path (NeuroMorph clock gate).

    Routes through ``kernels.morph_matmul`` (impl="auto": tile-skipping
    Pallas on TPU, fused masked dot elsewhere). Output columns >= active_n
    are exactly zero; contraction rows >= active_k contribute nothing.
    ``active_n`` / ``active_k`` may be per-batch ``(B,)`` vectors — batch
    slots running *different* width modes share this single projection.
    Under a multi-device serving mesh the Pallas kernel runs per shard
    (``sharded_morph_matmul``). x: (B, S, d); w: (d, N).
    """
    # local: keep layers import-light
    from repro.kernels import morph_matmul as _mm
    from repro.kernels.morph_matmul import default_impl, sharded_morph_matmul

    if active_n is None and active_k is None:
        return matmul(x, w, x.dtype)
    w = w.astype(x.dtype)
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1 and default_impl() == "pallas":
        return sharded_morph_matmul(mesh, x, w, active_n, active_k)
    return _mm(x, w, active_n, active_k, impl="auto")


# --- bf16-cotangent matmul (beyond-paper §Perf lever) -----------------------
#
# The default transpose rule leaves dW in f32 and GSPMD reduces it over the
# token axes *in f32* (2x wire). This custom VJP downcasts dW to the weight
# dtype immediately after the backward dot, so the cross-shard reduction
# happens at bf16. Enabled via ``use_bf16_grad_matmul`` (dry-run knob).

_BF16_GRAD_MATMUL = False


def set_bf16_grad_matmul(on: bool) -> None:
    global _BF16_GRAD_MATMUL
    _BF16_GRAD_MATMUL = on


@jax.custom_vjp
def _matmul_bf16g(x, w):
    return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _matmul_bf16g_fwd(x, w):
    return _matmul_bf16g(x, w), (x, w)


def _matmul_bf16g_bwd(res, dy):
    x, w = res
    dy = dy.astype(x.dtype)
    dx = jax.lax.dot_general(dy, w, (((dy.ndim - 1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32).astype(x.dtype)
    # contract all leading (token) dims of x against dy. The dot's result
    # type IS the cross-shard reduction dtype under GSPMD (a later convert
    # cannot be hoisted above the psum without changing semantics), so emit
    # bf16 directly — the industry-standard bf16 gradient reduction.
    lead = tuple(range(x.ndim - 1))
    dw = jax.lax.dot_general(x, dy, ((lead, lead), ((), ())),
                             preferred_element_type=w.dtype)
    return dx, dw.astype(w.dtype)


_matmul_bf16g.defvjp(_matmul_bf16g_fwd, _matmul_bf16g_bwd)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    pd = _dtype(cfg.param_dtype)
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), pd), "bias": jnp.zeros((d,), pd)}
    return {"scale": jnp.ones((d,), pd)}


def apply_norm(params, x, cfg: ModelConfig, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    if "bias" in params:  # layernorm
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    else:  # rmsnorm
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    return y.astype(dt)


def apply_norm_masked(params, x, cfg: ModelConfig, n_active, eps: float = 1e-6):
    """RMSNorm whose mean-square spans only the first ``n_active`` channels.

    The runtime-width morph path guarantees x is exactly zero beyond
    ``n_active``, so the full-width sum-of-squares equals the active-prefix
    sum; only the divisor changes. ``n_active``: scalar or per-batch (B,).
    """
    assert "bias" not in params, "masked norm is rmsnorm-only"
    dt = x.dtype
    xf = x.astype(jnp.float32)
    n = jnp.asarray(n_active, jnp.float32)
    if n.ndim:
        n = n.reshape(n.shape + (1,) * (x.ndim - n.ndim))
    var = jnp.sum(jnp.square(xf), axis=-1, keepdims=True) / jnp.maximum(n, 1.0)
    y = xf * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """Apply rotary embeddings. x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(positions, d: int):
    """Absolute sinusoidal positions (whisper-style). positions: (..., S)."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(10000.0) / max(half - 1, 1)))
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


# ---------------------------------------------------------------------------
# MLP (dense)
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    pd = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    p = {"wi": dense_init(ks[0], (d, f), dtype=pd), "wo": dense_init(ks[1], (f, d), dtype=pd)}
    if cfg.activation == "swiglu":
        p["wg"] = dense_init(ks[2], (d, f), dtype=pd)
    return p


def apply_mlp(params, x, cfg: ModelConfig, active_ff=None):
    """Dense MLP. ``active_ff`` (scalar or per-batch (B,)) runtime-gates the
    hidden columns: columns >= active_ff are exactly zero after the up
    projection (so every activation maps 0 -> 0 across them) and are skipped
    by the down projection's contraction."""
    dt = x.dtype
    h = morph_proj(x, params["wi"], active_n=active_ff)
    if cfg.activation == "swiglu":
        g = morph_proj(x, params["wg"], active_n=active_ff)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * h
    elif cfg.activation == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    else:  # gelu
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(dt)
    return morph_proj(h, params["wo"], active_k=active_ff)


# ---------------------------------------------------------------------------
# GQA attention (full / sliding window; train, prefill, decode)
# ---------------------------------------------------------------------------

NEG_INF = -1e9


def init_attention(key, cfg: ModelConfig, cross: bool = False):
    d = cfg.d_model
    pd = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, cfg.q_dim), dtype=pd),
        "wk": dense_init(ks[1], (d, cfg.kv_dim), dtype=pd),
        "wv": dense_init(ks[2], (d, cfg.kv_dim), dtype=pd),
        "wo": dense_init(ks[3], (cfg.q_dim, d), dtype=pd),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _attn_mask(q_pos, k_pos, causal: bool, window: int):
    """(..., Sq, Sk) additive mask."""
    m = jnp.zeros(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), jnp.float32)
    dq = q_pos[..., :, None]
    dk = k_pos[..., None, :]
    m = jnp.where(dk < 0, NEG_INF, m)  # unwritten / padded slots carry pos < 0
    if causal:
        m = jnp.where(dk > dq, NEG_INF, m)
    if window > 0:
        m = jnp.where(dk <= dq - window, NEG_INF, m)
    return m


def _gqa_scores(q, k, cfg: ModelConfig):
    """q: (B,Sq,H,hd), k: (B,Sk,KV,hd) -> (B,KV,H/KV,Sq,Sk) f32."""
    groups = cfg.n_heads // max(cfg.n_kv_heads, 1)
    B, Sq, H, hd = q.shape
    qg = q.reshape(B, Sq, cfg.n_kv_heads, groups, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k, preferred_element_type=jnp.float32)
    return s / math.sqrt(hd)


def _gqa_out(w, v, cfg: ModelConfig):
    """w: (B,KV,G,Sq,Sk) f32, v: (B,Sk,KV,hd) -> (B,Sq,H,hd).

    v stays in its storage dtype (bf16): upcasting the whole cache to f32
    would double the decode HBM stream; the MXU accumulates in f32 via
    preferred_element_type regardless.
    """
    B = w.shape[0]
    o = jnp.einsum("bkgqs,bskh->bqkgh", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, o.shape[1], cfg.n_heads, cfg.head_dim)


def attention_full(q, k, v, cfg: ModelConfig, q_pos, k_pos, causal=True,
                   bias=None):
    """Plain einsum attention (used for short sequences).

    ``bias`` is an optional additive (Sq, Sk) f32 term on top of the
    position mask — the token-tree verify path uses it to restrict each
    tree node's attention to its ancestors (position masking alone cannot
    separate siblings at equal depth).
    """
    s = _gqa_scores(q, k, cfg)
    mask = _attn_mask(q_pos, k_pos, causal, cfg.sliding_window)
    s = s + mask[:, None, None] if mask.ndim == 3 else s + mask
    if bias is not None:
        s = s + bias
    w = jax.nn.softmax(s, axis=-1)
    return _gqa_out(w, v, cfg).astype(q.dtype)


def attention_chunked(q, k, v, cfg: ModelConfig, q_pos, k_pos, causal=True):
    """Blockwise (flash-style) attention in pure JAX.

    Scans over KV chunks carrying running (max, sum, acc) so peak memory is
    O(Sq * chunk) instead of O(Sq * Sk). This is the default for long
    sequences in dry-run lowering (honest FLOPs, bounded memory); the Pallas
    kernel in ``repro.kernels.flash_attention`` is the TPU-native fast path.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    chunk = min(cfg.attn_chunk, Sk)
    n_chunks = (Sk + chunk - 1) // chunk
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, pad),), constant_values=-10**9)
    kc = k.reshape(B, n_chunks, chunk, cfg.n_kv_heads, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, cfg.n_kv_heads, hd).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n_chunks, chunk)
    groups = H // max(cfg.n_kv_heads, 1)
    qg = q.reshape(B, Sq, cfg.n_kv_heads, groups, hd)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        k_i, v_i, p_i = xs
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_i, preferred_element_type=jnp.float32)
        s = s / math.sqrt(hd)
        mask = _attn_mask(q_pos, p_i, causal, cfg.sliding_window)
        s = s + mask  # (B?,Sq,chunk) broadcast over (b,k,g,..)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(v_i.dtype), v_i,
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, cfg.n_kv_heads, groups, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, cfg.n_kv_heads, groups, Sq), jnp.float32)
    a0 = jnp.zeros((B, cfg.n_kv_heads, groups, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def mha(params, x, cfg: ModelConfig, positions, *, kv_x=None, kv_positions=None,
        causal=True):
    """Full-sequence attention (train/prefill). Returns (out, (k, v))."""
    dt = x.dtype
    q = _split_heads(matmul(x, params["wq"], dt), cfg.n_heads, cfg.head_dim)
    kv_in = x if kv_x is None else kv_x
    k = _split_heads(matmul(kv_in, params["wk"], dt), cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(matmul(kv_in, params["wv"], dt), cfg.n_kv_heads, cfg.head_dim)
    kpos = positions if kv_positions is None else kv_positions
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kpos, cfg.rope_theta)
    # serving prefill under a mesh: same by-head pinning as the decode path
    # (no-ops outside an activation_sharding context, e.g. in training)
    q = constrain(q, "decode_q")
    k = constrain(k, "decode_kv")
    v = constrain(v, "decode_kv")
    Sk = k.shape[1]
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "chunked" if Sk > 2048 else "einsum"
    fn = attention_chunked if impl == "chunked" else attention_full
    out = fn(q, k, v, cfg, positions, kpos, causal=causal)
    out = matmul(out.reshape(out.shape[0], out.shape[1], cfg.q_dim), params["wo"], dt)
    return out, (k, v)


# --- decode path with KV cache ---------------------------------------------


def quantize_kv(x):
    """int8 per-(batch,pos,head) absmax quantization."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True).astype(jnp.float32) / 127.0
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-8)).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def init_kv_cache(cfg: ModelConfig, batch: int, seq: int, dtype):
    """KV cache for one attention layer. SWA uses a rolling window buffer."""
    window = cfg.sliding_window
    s = min(seq, window) if window else seq
    shape = (batch, s, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_quant:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1] + (1,), jnp.bfloat16),
            "v_scale": jnp.zeros(shape[:-1] + (1,), jnp.bfloat16),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def mha_decode(params, x, cache, pos, cfg: ModelConfig, *, cross=False,
               active=None, pages=None, page_size=0, fused=False):
    """One-token decode. x: (B,1,d); cache dict; pos: scalar int32 or (B,)
    per-slot positions (continuous batching: each batch slot is an independent
    request at its own sequence offset).

    ``active`` (dict with "q_dim"/"kv_dim", scalars or per-batch (B,))
    runtime-gates the projections: q/k/v columns beyond each slot's active
    width are exactly zero, so inactive heads score uniformly over zero
    values and contribute nothing, and the output projection's contraction
    skips inactive head columns — one executable serves every width.

    ``pages`` switches the cache to the block-paged layout (see
    ``models.paged``): cache K/V leaves are physical page pools
    ``(n_pages, page_size, KV, hd)`` and ``pages`` is the traced
    ``(B, P)`` int32 page table. The new K/V is written to the physical
    (page, offset) the slot's position maps to, then attention runs over the
    gathered per-slot view — garbage columns (table entries past a slot's
    length) sit at kpos = -1e9 and contribute exact zeros, so the paged
    path is bit-identical to the dense one. Requires per-slot positions.

    Returns (out, new_cache). For cross-attention the cache holds precomputed
    encoder K/V and is returned unchanged.

    ``fused=True`` routes the self-attention branch through the
    ``kernels.fused_decode`` superkernel (projection + attention + dequant in
    one launch; ``impl="auto"``: Pallas on TPU, a bit-identical mirrored ref
    elsewhere). Cross-attention ignores the flag (no cache write, tiny S).
    """
    if fused and not cross:
        from repro.kernels import fused_decode_step  # local: keep layers import-light
        return fused_decode_step(params, x, cache, pos, cfg, active=active,
                                 pages=pages, page_size=page_size)
    dt = x.dtype
    B = x.shape[0]
    a_q = active.get("q_dim") if active else None
    a_kv = active.get("kv_dim") if active else None
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    qpos = pos[:, None] if per_slot else jnp.full((1,), pos, jnp.int32)
    q = _split_heads(morph_proj(x, params["wq"], active_n=a_q),
                     cfg.n_heads, cfg.head_dim)
    if cfg.use_rope and not cross:
        q = rope(q, qpos, cfg.rope_theta)
    # under a mesh, pin the post-projection layout to by-head sharding (or
    # replication): attention math must never be split through head_dim,
    # which is what the fused projection's column sharding would propagate
    q = constrain(q, "decode_q")

    if cross:
        k, v = cache["k"], cache["v"]
        if cfg.kv_quant and "k_scale" in cache:
            k = dequantize_kv(k, cache["k_scale"], dt)
            v = dequantize_kv(v, cache["v_scale"], dt)
        S = k.shape[1]
        kpos = jnp.arange(S)
        out = attention_full(q, k, v, cfg, qpos, kpos, causal=False)
        # cross K/V is full-width encoder output, so inactive q heads attend
        # to NON-zero values — the active_k contraction gate on wo is what
        # excludes them, not zero propagation.
        out = morph_proj(out.reshape(B, 1, cfg.q_dim), params["wo"], active_k=a_q)
        return out, cache

    k_new = _split_heads(morph_proj(x, params["wk"], active_n=a_kv),
                         cfg.n_kv_heads, cfg.head_dim)
    v_new = _split_heads(morph_proj(x, params["wv"], active_n=a_kv),
                         cfg.n_kv_heads, cfg.head_dim)
    if cfg.use_rope:
        k_new = rope(k_new, qpos, cfg.rope_theta)
    k_new = constrain(k_new, "decode_kv")
    v_new = constrain(v_new, "decode_kv")

    window = cfg.sliding_window
    if pages is not None:
        if not per_slot:
            raise ValueError("paged decode needs per-slot positions (pos (B,))")
        ps = page_size
        S = pages.shape[1] * ps  # positions visible through the table
        slot = jnp.mod(pos, S) if window else jnp.minimum(pos, S - 1)
        page_ix = slot // ps
        off = slot - page_ix * ps
        phys = jnp.take_along_axis(pages, page_ix[:, None], axis=1)[:, 0]

        def write(buf, new):  # buf: (n_pages, page_size, ...)
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
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new_cache["k"] = write(cache["k"], kq)
        new_cache["v"] = write(cache["v"], vq)
        new_cache["k_scale"] = write(cache["k_scale"], ks)
        new_cache["v_scale"] = write(cache["v_scale"], vs)
        k = dequantize_kv(view(new_cache["k"]), view(new_cache["k_scale"]), dt)
        v = dequantize_kv(view(new_cache["v"]), view(new_cache["v_scale"]), dt)
    else:
        new_cache["k"] = write(cache["k"], k_new)
        new_cache["v"] = write(cache["v"], v_new)
        k, v = view(new_cache["k"]).astype(dt), view(new_cache["v"]).astype(dt)
    if pages is not None:
        # mesh serving: the gather collapses the pool's page axis into a
        # per-slot seq axis — pin the result back to the by-head layout the
        # attention math below assumes (no-op outside a sharding context)
        k = constrain(k, "decode_kv")
        v = constrain(v, "decode_kv")

    # kpos: absolute position of each cache slot. With per-slot pos the mask
    # broadcasts to (B, S) — stale entries from a slot's previous request sit
    # at idx > pos and are masked out, which is what makes in-place slot
    # re-admission safe without zeroing the KV buffer.
    pos_b = pos[:, None] if per_slot else pos
    idx = jnp.arange(S)[None, :] if per_slot else jnp.arange(S)
    if window:
        # rolling buffer: absolute position of slot i given current pos
        wraps = jnp.where(idx <= jnp.mod(pos_b, S), 0, 1)
        kpos = (pos_b // S - wraps) * S + idx  # absolute positions, may be negative
        kpos = jnp.where(kpos < 0, -10**9, kpos)  # unwritten slots -> masked
    else:
        kpos = jnp.where(idx <= pos_b, idx, -10**9)
    out = attention_full(q, k, v, cfg, qpos, kpos, causal=True)
    out = morph_proj(out.reshape(B, 1, cfg.q_dim), params["wo"], active_k=a_q)
    return out, new_cache


def _cache_kpos(pos, n_slots: int, window: int):
    """Absolute position of every cache slot given ``pos`` committed tokens.

    ``pos`` is the (B,) per-slot committed-token count — the cache holds
    entries for absolute positions < pos only. Returns (B, n_slots) int32
    with unwritten / stale / rolled-over slots at -1e9 (masked).
    """
    idx = jnp.arange(n_slots)[None, :]
    if window:
        last = pos[:, None] - 1  # newest committed absolute position (-1: none)
        wraps = jnp.where(idx <= jnp.mod(last, n_slots), 0, 1)
        kpos = (jnp.floor_divide(last, n_slots) - wraps) * n_slots + idx
        return jnp.where(kpos < 0, -10**9, kpos)
    return jnp.where(idx < pos[:, None], idx, -10**9)


def mha_verify(params, x, cache, pos, cfg: ModelConfig, *, active=None,
               node_depth=None, tree_bias=None, pages=None, page_size=0,
               fused=False):
    """Speculative verify attention: score S positions in one pass.

    x: (B, S, d) — embeddings of the last committed token followed by S-1
    draft tokens, occupying absolute positions ``pos .. pos+S-1`` (``pos`` is
    the (B,) per-slot committed-token count). The cache is READ but never
    written: new K/V for the S positions are returned as candidates for
    ``models.model.commit_verify`` to scatter once the acceptance count is
    known. Attention runs over [cache entries, new K/V] with absolute-position
    masking, so each query sees exactly the keys a sequential ``mha_decode``
    stream would have seen — including the rolling sliding-window buffer,
    where attending BEFORE any write avoids clobbering entries that later
    (rejected) positions would have rolled over.

    Token-tree verify: ``node_depth`` (S,) static ints map each position to
    its tree depth (absolute position ``pos + depth``) and ``tree_bias``
    (S, S) is the static ancestor mask (0 ancestor-or-self / -inf) applied
    over the new-KV block — position masking alone cannot separate sibling
    branches sitting at the same depth. Default (both None) is the linear
    window ``pos .. pos+S-1``.

    With ``pages`` (traced (B, P) int32 table; see ``models.paged``) the
    cache operands are page pools and the committed K/V is read through the
    gathered per-slot view — same masking argument as ``mha_decode``, same
    bit-identity to the dense path.

    Returns (out (B, S, d), {"k": k_new, "v": v_new} with (B, S, KV, hd)).

    ``fused=True`` routes through the ``kernels.fused_decode`` verify
    superkernel; tree topologies bake their ancestor mask into the kernel
    instead of materializing this function's dense additive ``bias``.
    """
    if fused:
        from repro.kernels import fused_verify  # local: keep layers import-light
        return fused_verify(params, x, cache, pos, cfg, active=active,
                            node_depth=node_depth, tree_bias=tree_bias,
                            pages=pages, page_size=page_size)
    dt = x.dtype
    B, S, _ = x.shape
    a_q = active.get("q_dim") if active else None
    a_kv = active.get("kv_dim") if active else None
    pos = jnp.asarray(pos, jnp.int32)
    offs = (jnp.arange(S, dtype=jnp.int32) if node_depth is None
            else jnp.asarray(node_depth, jnp.int32))
    qpos = pos[:, None] + offs[None, :]  # (B, S)
    # pin BEFORE rope as well as after: at (B, S>1) decode shapes the XLA CPU
    # partitioner mis-lowers rope over projection-propagated column sharding
    # (wrong values, not just slow — same bug class decode_specs documents)
    q = constrain(_split_heads(morph_proj(x, params["wq"], active_n=a_q),
                               cfg.n_heads, cfg.head_dim), "decode_q")
    k_new = constrain(_split_heads(morph_proj(x, params["wk"], active_n=a_kv),
                                   cfg.n_kv_heads, cfg.head_dim), "decode_kv")
    v_new = constrain(_split_heads(morph_proj(x, params["wv"], active_n=a_kv),
                                   cfg.n_kv_heads, cfg.head_dim), "decode_kv")
    if cfg.use_rope:
        q = rope(q, qpos, cfg.rope_theta)
        k_new = rope(k_new, qpos, cfg.rope_theta)
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
            kc = dequantize_kv(kc, _view(cache["k_scale"]), dt)
            vc = dequantize_kv(vc, _view(cache["v_scale"]), dt)
    else:
        kc, vc = cache["k"], cache["v"]
    if cfg.kv_quant and "k_scale" in cache and pages is None:
        kc = dequantize_kv(kc, cache["k_scale"], dt)
        vc = dequantize_kv(vc, cache["v_scale"], dt)
    if cfg.kv_quant and "k_scale" in cache:
        # attend over the quantize->dequantize round trip of the NEW entries
        # too: that is what sequential mha_decode reads back from the cache,
        # and what commit_verify will store — raw values would break the
        # verify-equals-sequential-decode identity. Candidates stay raw
        # (commit re-quantizes them to the same stored values).
        kq, ks_ = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        k_att = dequantize_kv(kq, ks_, dt)
        v_att = dequantize_kv(vq, vs, dt)
    else:
        k_att, v_att = k_new, v_new
    # under a mesh the serving cache keeps KV seq sharded on the model axis;
    # concatenating it with the replicated new K/V along that axis is one of
    # the layouts the XLA CPU partitioner gets WRONG (verified: bad logits at
    # every position) — pin the cache operand to the verify layout first
    kc = constrain(kc.astype(dt), "decode_kv")
    vc = constrain(vc.astype(dt), "decode_kv")
    kpos_c = _cache_kpos(pos, kc.shape[1], cfg.sliding_window)
    k_ext = jnp.concatenate([kc, k_att], axis=1)
    v_ext = jnp.concatenate([vc, v_att], axis=1)
    kpos = jnp.concatenate([kpos_c, qpos], axis=1)
    bias = None
    if tree_bias is not None:
        # cache columns stay position-masked only; new-KV columns get the
        # ancestor mask (sibling/cousin nodes are invisible to each other)
        bias = jnp.concatenate(
            [jnp.zeros((S, kc.shape[1]), jnp.float32),
             jnp.asarray(tree_bias, jnp.float32)], axis=1)
    out = attention_full(q, k_ext, v_ext, cfg, qpos, kpos, causal=True,
                         bias=bias)
    out = morph_proj(out.reshape(B, S, cfg.q_dim), params["wo"], active_k=a_q)
    return out, {"k": k_new, "v": v_new}


def mha_tree_level(params, x, cache, pos, cfg: ModelConfig, carry_kv, *,
                   level, carry_depths, bias, active=None, pages=None,
                   page_size=0):
    """One tree-draft LEVEL of attention: frontier nodes vs cache + carry.

    The KV-carrying tree draft processes each node exactly once: level
    ``level``'s frontier embeddings ``x`` (B, nf, d) attend over the
    committed cache plus the K/V CARRIED from earlier levels instead of
    re-scoring the whole tree prefix per pass. ``carry_kv`` holds
    ``{"k", "v"}`` (B, Nc, KV, hd) round-tripped K/V of already-processed
    nodes in BFS order (rows past the readable prefix are unread zeros);
    ``carry_depths`` is the static (f1,) depth of each readable carry row
    and ``bias`` the static (nf, f1) ancestor-mask rows for the frontier —
    columns [f1-nf, f1) are the frontier's own in-flight K/V.

    Bit-identical to the frontier rows of ``mha_verify`` over the full
    prefix: carried rows equal the values that pass would recompute, and
    the extended key axis keeps the same BFS column order, so the softmax
    reduction is unchanged. Returns (out (B, nf, d), rows {"k", "v"}
    (B, nf, KV, hd)) with rows ROUND-TRIPPED through kv quantization (what
    a cache read-back would return) — ready to write into the carry.
    """
    dt = x.dtype
    B, nf, _ = x.shape
    a_q = active.get("q_dim") if active else None
    a_kv = active.get("kv_dim") if active else None
    pos = jnp.asarray(pos, jnp.int32)
    offs = jnp.full((nf,), level, jnp.int32)  # one level = one depth
    qpos = pos[:, None] + offs[None, :]  # (B, nf)
    q = constrain(_split_heads(morph_proj(x, params["wq"], active_n=a_q),
                               cfg.n_heads, cfg.head_dim), "decode_q")
    k_new = constrain(_split_heads(morph_proj(x, params["wk"], active_n=a_kv),
                                   cfg.n_kv_heads, cfg.head_dim), "decode_kv")
    v_new = constrain(_split_heads(morph_proj(x, params["wv"], active_n=a_kv),
                                   cfg.n_kv_heads, cfg.head_dim), "decode_kv")
    if cfg.use_rope:
        q = rope(q, qpos, cfg.rope_theta)
        k_new = rope(k_new, qpos, cfg.rope_theta)
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
            kc = dequantize_kv(kc, _view(cache["k_scale"]), dt)
            vc = dequantize_kv(vc, _view(cache["v_scale"]), dt)
    else:
        kc, vc = cache["k"], cache["v"]
        if cfg.kv_quant and "k_scale" in cache:
            kc = dequantize_kv(kc, cache["k_scale"], dt)
            vc = dequantize_kv(vc, cache["v_scale"], dt)
    if cfg.kv_quant and "k_scale" in cache:
        # same round trip the verify path attends over (see mha_verify)
        kq, ks_ = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        k_att = dequantize_kv(kq, ks_, dt)
        v_att = dequantize_kv(vq, vs, dt)
    else:
        k_att, v_att = k_new, v_new
    kc = constrain(kc.astype(dt), "decode_kv")
    vc = constrain(vc.astype(dt), "decode_kv")
    kpos_c = _cache_kpos(pos, kc.shape[1], cfg.sliding_window)
    f1 = bias.shape[1]  # readable carry prefix (ancestors + frontier)
    k_car = jnp.concatenate([carry_kv["k"][:, : f1 - nf].astype(dt), k_att], 1)
    v_car = jnp.concatenate([carry_kv["v"][:, : f1 - nf].astype(dt), v_att], 1)
    kpos_car = pos[:, None] + jnp.asarray(carry_depths, jnp.int32)[None, :]
    k_ext = jnp.concatenate([kc, k_car], axis=1)
    v_ext = jnp.concatenate([vc, v_car], axis=1)
    kpos = jnp.concatenate([kpos_c, kpos_car], axis=1)
    bias_full = jnp.concatenate(
        [jnp.zeros((nf, kc.shape[1]), jnp.float32),
         jnp.asarray(bias, jnp.float32)], axis=1)
    out = attention_full(q, k_ext, v_ext, cfg, qpos, kpos, causal=True,
                         bias=bias_full)
    out = morph_proj(out.reshape(B, nf, cfg.q_dim), params["wo"], active_k=a_q)
    return out, {"k": k_att, "v": v_att}
