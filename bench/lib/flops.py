"""Operations the work needs, from shapes and active sizes.

These count what the model and the kernel *need*, not what the program
happens to compute: a decode token's FLOPs at its width, depth and context
(top-k experts only). Work on free slots, padding or experts a token did
not choose is waste and is not counted, so it shows as a lower share.
Each layer is counted by its kind (``spec.layer_types``): attention or a
Mamba2 mixer, then the layer's MLP or experts.
"""
from __future__ import annotations

from typing import Dict

from bench.lib import spec as S


def _dims(c: Dict) -> Dict[str, int]:
    d = c["hidden_size"]
    E = c.get("num_local_experts", 0)
    n = dict(d=d, hd=S.head_dim(c) or 0,
             H=c.get("num_attention_heads", 0),
             KV=c.get("num_key_value_heads", 0),
             F=c["intermediate_size"], E=E,
             R=c.get("num_routed_experts", E),
             k=c.get("num_experts_per_tok", 0),
             Fs=c.get("shared_intermediate_size", 0), V=c["vocab_size"],
             mats=3 if c["hidden_act"] == "silu" else 2)
    if "mamba_d_state" in c:
        n.update(nh=c["mamba_n_heads"], hp=c["mamba_d_head"],
                 N=c["mamba_d_state"], g=c["mamba_n_groups"],
                 K=c["mamba_d_conv"])
    return n


def active(c: Dict, width: float) -> Dict[str, int]:
    """Inner sizes a width keeps: query and key/value widths, MLP (and
    shared expert) columns, expert choices, SSD heads (the program's
    documented width semantics: prefixes of each, B/C groups never
    gated)."""
    n = _dims(c)
    out = {"q": round(n["H"] * width) * n["hd"],
           "kv": max(1, round(n["KV"] * width)) * n["hd"],
           "ff": round(n["F"] * width),
           "shared": round(n["Fs"] * width),
           "topk": max(1, round(n["k"] * width)) if n["E"] else 0}
    if "nh" in n:
        out["ssm_heads"] = round(n["nh"] * width)
    return out


def attention_flops(c: Dict, width: float, ctx: int) -> int:
    """One token through one attention layer: the four projections and
    ``ctx`` keys' scores and values."""
    n, a = _dims(c), active(c, width)
    return 2 * n["d"] * (2 * a["q"] + 2 * a["kv"]) + 4 * ctx * a["q"]


def mamba_flops(c: Dict, width: float) -> int:
    """One token through one Mamba2 mixer at its active SSD heads: the
    in-projection to x, z, B, C and dt, the depthwise conv over x, B and C,
    the state's update (decay, outer product, sum: 3 per element) and
    read-out (2 per element), and the out-projection. The skip, gate and
    norm are elementwise over the inner width and are left out."""
    n, a = _dims(c), active(c, width)
    heads = a["ssm_heads"]
    inner, bc = heads * n["hp"], 2 * n["g"] * n["N"]
    return (2 * n["d"] * (2 * inner + bc + heads) + 2 * n["K"] * (inner + bc)
            + 5 * heads * n["hp"] * n["N"] + 2 * inner * n["d"])


def ffn_flops(c: Dict, width: float) -> float:
    """One token through one layer's MLP, or its experts: the router over
    every routed expert, the chosen experts at the share of them held here
    (``top_k * held / routed``: ``num_local_experts`` counts the experts
    held, ``num_routed_experts``, where the file states it, those the
    router spans), and a shared expert of ``shared_intermediate_size``."""
    n, a = _dims(c), active(c, width)
    per_col = n["mats"] * 2 * n["d"]
    if not n["E"]:
        return per_col * a["ff"]
    return (2 * n["d"] * n["R"] + a["topk"] * n["E"] / n["R"] * per_col * n["F"]
            + per_col * a["shared"])


def decode_token_flops(c: Dict, width: float, depth: int, ctx: int) -> float:
    """Model FLOPs of one decode token: the first ``depth`` layers at
    ``width``, each by its kind, with ``ctx`` keys in attention (the
    token's own included), then the unembedding over the real vocabulary.
    A multiply-add is 2 FLOPs."""
    n_ssm = S.layer_types(c)[:depth].count("ssm")
    mix = (depth - n_ssm) * attention_flops(c, width, ctx)
    if n_ssm:
        mix += n_ssm * mamba_flops(c, width)
    return float(mix + depth * ffn_flops(c, width)
                 + 2 * c["hidden_size"] * c["vocab_size"])
