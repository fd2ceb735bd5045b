"""Operations the work needs, from shapes and active sizes.

These count what the model and the kernel *need*, not what the program
happens to compute: a decode token's FLOPs at its width, depth and context
(top-k experts only). Work on free slots, padding or experts a token did
not choose is waste and is not counted, so it shows as a lower share.
"""
from __future__ import annotations

from typing import Dict


def _dims(c: Dict) -> Dict[str, int]:
    d = c["hidden_size"]
    return dict(d=d, hd=d // c["num_attention_heads"],
                H=c["num_attention_heads"], KV=c["num_key_value_heads"],
                F=c["intermediate_size"], E=c.get("num_local_experts", 0),
                k=c.get("num_experts_per_tok", 0), V=c["vocab_size"])


def active(c: Dict, width: float) -> Dict[str, int]:
    """Inner sizes a width keeps: query and key/value widths, MLP columns,
    expert choices (the program's documented width semantics)."""
    n = _dims(c)
    return {"q": round(n["H"] * width) * n["hd"],
            "kv": max(1, round(n["KV"] * width)) * n["hd"],
            "ff": round(n["F"] * width),
            "topk": max(1, round(n["k"] * width)) if n["E"] else 0}


def decode_token_flops(c: Dict, width: float, depth: int, ctx: int) -> float:
    """Model FLOPs of one decode token: ``depth`` layers at ``width`` with
    ``ctx`` keys in attention (the token's own included), then the
    unembedding over the real vocabulary. A multiply-add is 2 FLOPs."""
    n, a = _dims(c), active(c, width)
    d = n["d"]
    attn = 2 * d * (2 * a["q"] + 2 * a["kv"]) + 4 * ctx * a["q"]
    if n["E"]:
        mlp = 2 * d * n["E"] + a["topk"] * 3 * 2 * d * n["F"]
    else:
        mlp = 3 * 2 * d * a["ff"]
    return float(depth * (attn + mlp) + 2 * d * n["V"])
