"""Plain float32 reference of a decoder-only transformer: RMSNorm, rotary
grouped-query attention, and either a SwiGLU MLP or a dropless top-k
mixture of SwiGLU experts. It covers granite-moe (GraniteMoeForCausalLM)
and phi3 (Phi3ForCausalLM) as their configuration files state them.

It reads sizes from the configuration file alone and imports nothing of
the program: no kernels, no cache, no batching, ``highest`` matmul
precision. Weights are drawn from the seed by the same recipe the program's
initializer documents (key splits, truncated-normal fan-in scaling, the
parameter dtype's rounding), layer by layer, so the reference never holds
a whole model and takes nothing the program made.

NeuroMorph paths, as the program documents them (``core/elastic.py``):

* width ``w`` keeps the first ``round(H*w)`` query heads, the first
  ``max(1, round(KV*w))`` key/value heads, the first ``round(F*w)`` MLP
  columns, and for a mixture of experts the first ``max(1, round(k*w))``
  of the router's top-k choices, renormalised over those;
* depth ``d`` runs the first ``d`` layers and then the exit's own norm
  before the shared unembedding.

A served request takes its prompt through the whole-prompt prefill at full
width and the request's depth, and every later token at its admitted width
and depth; keys and values of the prompt stay as the full-width pass made
them. ``forward`` computes exactly that path: positions before
``n_prompt`` run at full width, positions from ``n_prompt`` on at width
``w``, and a narrow position attends to the prompt's keys and values of the
heads it keeps. Masking a head, column or choice here is the same
arithmetic as slicing it away.

``precision="fp8"`` is the control: float8 e4m3 (one absmax scale per
tensor, products accumulated in float32) wherever the served model holds
bfloat16: every operand of every product (the embedding, projections,
router, experts and unembedding, and the attention's queries, keys, values
and probabilities, as a float8 cache would hold them) and the residual
stream between layers.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def root_key(seed: int):
    """The weights' root key for a seed (32 bits of it)."""
    return jax.random.PRNGKey(seed % 2**32)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def _dense(key, shape, fan_in: int, dtype: str):
    std = 1.0 / math.sqrt(fan_in)
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) * std
    return w.astype(jnp.dtype(dtype)).astype(F32)


def _dims(c: Dict) -> Dict[str, int]:
    d = c["hidden_size"]
    hd = d // c["num_attention_heads"]
    return dict(d=d, hd=hd, H=c["num_attention_heads"],
                KV=c["num_key_value_heads"], F=c["intermediate_size"],
                E=c.get("num_local_experts", 0),
                k=c.get("num_experts_per_tok", 0),
                V=c["vocab_size"], Vp=c["serving"]["padded_vocab"],
                L=c["num_hidden_layers"])


def top_weights(c: Dict, seed: int) -> Dict[str, jnp.ndarray]:
    """Embedding (Vp, d) and, untied, the unembedding (d, Vp)."""
    n = _dims(c)
    ks = jax.random.split(root_key(seed), 8)
    pd = c["serving"]["param_dtype"]
    out = {"embed": jax.jit(partial(_dense, shape=(n["Vp"], n["d"]),
                                    fan_in=n["Vp"], dtype=pd))(ks[0])}
    if not c["tie_word_embeddings"]:
        out["unembed"] = jax.jit(partial(_dense, shape=(n["d"], n["Vp"]),
                                         fan_in=n["d"], dtype=pd))(ks[2])
    return out


def layer_keys(c: Dict, seed: int):
    """One key per layer, as the program's stacked initializer splits them."""
    ks = jax.random.split(root_key(seed), 8)
    groups = jax.random.split(ks[1], c["num_hidden_layers"])
    # one layer per group: the group key splits once more, into the layer's
    return [jax.random.split(g, 1)[0] for g in groups]


@partial(jax.jit, static_argnums=(1,))
def _layer_weights(key, spec: Tuple):
    d, hd, H, KV, F, E, pd = spec
    ks = jax.random.split(key, 6)
    a = jax.random.split(ks[0], 4)
    w = {"wq": _dense(a[0], (d, H * hd), d, pd),
         "wk": _dense(a[1], (d, KV * hd), d, pd),
         "wv": _dense(a[2], (d, KV * hd), d, pd),
         "wo": _dense(a[3], (H * hd, d), H * hd, pd)}
    if E:
        m = jax.random.split(ks[2], 4)
        w["router"] = _dense(m[0], (d, E), d, "float32")
        w["wi"] = _dense(m[1], (E, d, F), d, pd)
        w["wo_e"] = _dense(m[2], (E, F, d), F, pd)
        w["wg"] = _dense(m[3], (E, d, F), d, pd)
    else:
        m = jax.random.split(ks[2], 3)
        w["wi"] = _dense(m[0], (d, F), d, pd)
        w["wo_m"] = _dense(m[1], (F, d), F, pd)
        w["wg"] = _dense(m[2], (d, F), d, pd)
    return w


def layer_weights(c: Dict, key) -> Dict[str, jnp.ndarray]:
    """One layer's weights (norm scales are ones, as initialized)."""
    n = _dims(c)
    return _layer_weights(key, (n["d"], n["hd"], n["H"], n["KV"], n["F"],
                                n["E"], c["serving"]["param_dtype"]))


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _q8(x):
    """float8 e4m3 rounding with one absmax scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x), _q8(w)
    return jnp.matmul(x, w, precision="highest")


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, theta):
    """Rotate-half rotary embedding over positions 0..T-1. x: (T, h, hd)."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnums=(3, 4))
def _layer(w, h, act, spec: Tuple, fp8: bool):
    """One layer over a whole sequence. ``act`` holds per-position active
    sizes: query heads (T,), MLP columns (T,), expert choices (T,)."""
    (H, KV, hd, E, k, eps, theta, att_mult, res_mult) = spec
    T = h.shape[0]
    G = H // KV
    x = _rms(h, eps)
    q = _rope(_mm(x, w["wq"], fp8).reshape(T, H, hd), theta)
    kk = _rope(_mm(x, w["wk"], fp8).reshape(T, KV, hd), theta)
    v = _mm(x, w["wv"], fp8).reshape(T, KV, hd)
    kk = jnp.repeat(kk, G, axis=1)  # head h reads key/value head h // G
    v = jnp.repeat(v, G, axis=1)
    if fp8:  # the attention's operands too, as a float8 cache would hold them
        q, kk, v = _q8(q), _q8(kk), _q8(v)
    s = jnp.einsum("qhd,khd->hqk", q, kk, precision="highest") * att_mult
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("hqk,khd->qhd", _q8(p) if fp8 else p, v,
                   precision="highest")
    o = jnp.where(jnp.arange(H)[None, :, None] < act["heads"][:, None, None],
                  o, 0.0)
    h = h + res_mult * _mm(o.reshape(T, H * hd), w["wo"], fp8)
    if fp8:
        h = _q8(h)
    x = _rms(h, eps)
    if E:
        probs = jax.nn.softmax(_mm(x, w["router"], fp8), -1)
        top, idx = jax.lax.top_k(probs, k)
        top = jnp.where(jnp.arange(k)[None, :] < act["choices"][:, None], top, 0.0)
        top = top / jnp.sum(top, -1, keepdims=True)
        gates = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], idx].add(top)
        xe = _q8(x) if fp8 else x
        wg, wi, wo = ((_q8(w["wg"]), _q8(w["wi"]), _q8(w["wo_e"])) if fp8
                      else (w["wg"], w["wi"], w["wo_e"]))
        g = jnp.einsum("td,edf->tef", xe, wg, precision="highest")
        u = jnp.einsum("td,edf->tef", xe, wi, precision="highest")
        a = jax.nn.silu(g) * u
        if fp8:
            a = _q8(a)
        y = jnp.einsum("tef,efd,te->td", a, wo, gates, precision="highest")
    else:
        a = jax.nn.silu(_mm(x, w["wg"], fp8)) * _mm(x, w["wi"], fp8)
        a = jnp.where(jnp.arange(a.shape[1])[None, :] < act["cols"][:, None], a, 0.0)
        y = _mm(a, w["wo_m"], fp8)
    h = h + res_mult * y
    return _q8(h) if fp8 else h


@partial(jax.jit, static_argnums=(3, 4, 5))
def _head(top, h, eps, V: int, scale: float, fp8: bool):
    x = _rms(h, eps)  # the final or exit norm: its scale is ones
    w = top["unembed"] if "unembed" in top else top["embed"].T
    return _mm(x, w[:, :V], fp8) / scale


def active_sizes(c: Dict, width: float) -> Dict[str, int]:
    """Sizes a width keeps (the program's documented width semantics)."""
    n = _dims(c)
    out = {"heads": int(round(n["H"] * width)),
           "cols": int(round(n["F"] * width)),
           "choices": max(1, int(round(n["k"] * width))) if n["E"] else 0}
    return out


def _act(c: Dict, n_prompt: int, width: float, T: int):
    full, narrow = active_sizes(c, 1.0), active_sizes(c, width)
    pos = np.arange(T)
    return {key: jnp.asarray(np.where(pos < n_prompt, full[key], narrow[key]),
                             jnp.int32) for key in full}


def _bucket(n: int, step: int) -> int:
    return -(-n // step) * step


def forward(c: Dict, seed: int, seqs: Sequence[Dict], *,
            precision: str = "f32", timings: Dict = None) -> List[np.ndarray]:
    """Logits (real vocabulary) at the positions that predicted each
    sequence's served tokens: rows ``n_prompt - 1`` to the end.

    ``seqs``: dicts with ``tokens`` (prompt then fed tokens), ``n_prompt``,
    ``width`` and ``depth``. Layers are made one at a time and every
    sequence passes each layer before the next is made. All sequences are
    padded to one length, a multiple of 512 (one compiled layer per run),
    which causal attention never lets an earlier position see.
    ``timings`` (a dict) receives the seconds spent making weights, in the
    layers and in the unembedding.
    """
    import time

    n = _dims(c)
    fp8 = precision == "fp8"
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    eps = float(c["rms_norm_eps"])
    spec = (n["H"], n["KV"], n["hd"], n["E"], n["k"], eps,
            float(c["rope_theta"]),
            float(c.get("attention_multiplier", 1 / math.sqrt(n["hd"]))),
            float(c.get("residual_multiplier", 1.0)))
    T = _bucket(max(len(s["tokens"]) for s in seqs), 512)
    R = _bucket(max(len(s["tokens"]) - s["n_prompt"] + 1 for s in seqs), 64)
    tm = {"weights_s": 0.0, "layers_s": 0.0, "head_s": 0.0}
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        top = jax.block_until_ready(top_weights(c, seed))
        tm["weights_s"] += time.perf_counter() - t0
        emb_w = _q8(top["embed"]) if fp8 else top["embed"]
        emb_mult = float(c.get("embedding_multiplier", 1.0))
        hs, acts = [], []
        for s in seqs:
            toks = np.zeros(T, np.int32)
            toks[: len(s["tokens"])] = s["tokens"]
            h = emb_w[jnp.asarray(toks)] * emb_mult
            hs.append(_q8(h) if fp8 else h)
            acts.append(_act(c, s["n_prompt"], s["width"], T))
        keys = layer_keys(c, seed)
        for layer in range(max(s["depth"] for s in seqs)):
            t0 = time.perf_counter()
            w = jax.block_until_ready(layer_weights(c, keys[layer]))
            t1 = time.perf_counter()
            for i, s in enumerate(seqs):
                if layer < s["depth"]:
                    hs[i] = _layer(w, hs[i], acts[i], spec, fp8)
            jax.block_until_ready(hs)
            tm["weights_s"] += t1 - t0
            tm["layers_s"] += time.perf_counter() - t1
            del w
        t0 = time.perf_counter()
        out = []
        for s, h in zip(seqs, hs):
            rows = len(s["tokens"]) - s["n_prompt"] + 1
            idx = np.minimum(np.arange(R) + s["n_prompt"] - 1, T - 1)
            lg = _head(top, h[jnp.asarray(idx)], eps, n["V"],
                       float(c.get("logits_scaling", 1.0)), fp8)
            out.append(np.asarray(lg)[:rows])
        tm["head_s"] += time.perf_counter() - t0
    if timings is not None:
        timings.update(tm)
    return out
