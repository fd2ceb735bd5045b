"""Decide ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of the requests that were served,
drawn from the seed, is run through the float32 reference over each
prompt and the tokens the engine served, on the path each request took
(its admitted width and depth). For every served token the gap by which
the reference's logit of that token lies below the reference's best logit
at that position is read; the widest gap over the sample is compared with
the configuration's limit. Under greedy decoding a sound program serves
the reference's best token or one within rounding of it; a wrong token
lies far below.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

# the sample: at least this many served tokens, from at most this many
# requests (the reference's time grows with their summed length)
MAX_REQUESTS = 32


def draw_sample(recs: Sequence, seed: int, min_tokens: int) -> List:
    """The longest served request, then one request of each (depth, width)
    path not yet in the sample, then random requests until ``min_tokens``
    served tokens are in (all drawn from ``seed``)."""
    cand = [r for r in recs if r.req.generated]
    if not cand:
        return []
    rng = np.random.default_rng([seed % 2**64, 7])
    order = [cand[i] for i in rng.permutation(len(cand))]
    longest = max(cand, key=lambda r: (len(r.req.generated), -r.planned.idx))
    out = [longest]
    paths = {(longest.depth, longest.width)}
    for r in order:
        if (r.depth, r.width) not in paths and len(out) < MAX_REQUESTS:
            out.append(r)
            paths.add((r.depth, r.width))
    for r in order:
        if sum(len(x.req.generated) for x in out) >= min_tokens:
            break
        if len(out) >= MAX_REQUESTS:
            break
        if r not in out:
            out.append(r)
    return out


def sequences(sample: Sequence) -> List[Dict]:
    """Reference inputs: each prompt followed by the served tokens that
    were fed back (all but the last)."""
    return [{"tokens": list(r.req.prompt) + list(r.req.generated[:-1]),
             "n_prompt": len(r.req.prompt), "served": list(r.req.generated),
             "width": r.width, "depth": r.depth} for r in sample]


def seq_gaps(logits: Sequence[np.ndarray],
             chosen: Sequence[Sequence[int]]) -> List[np.ndarray]:
    """Per sequence, the gap below the best logit of each chosen token;
    ``logits`` holds, per sequence, the rows that predicted its served
    tokens."""
    return [lg.max(-1) - lg[np.arange(len(ch)), np.asarray(ch)]
            for lg, ch in zip(logits, chosen)]


def gap_stats(per_seq: Sequence[np.ndarray], seqs: Sequence[Dict]) -> Dict:
    """How the gaps spread: their number, widest, mean, the share of tokens
    that are not the reference's first choice, and the widest on each
    (depth, width) path."""
    g = np.concatenate(per_seq) if len(per_seq) else np.zeros(0)
    by_path: Dict[str, float] = {}
    for gs, s in zip(per_seq, seqs):
        key = f"d{s['depth']}w{s['width']}"
        by_path[key] = max(by_path.get(key, 0.0), float(gs.max(initial=0.0)))
    if not g.size:
        return {"n": 0}
    return {"n": int(g.size), "max": float(g.max()), "mean": float(g.mean()),
            "mismatch_share": float((g > 0).mean()), "by_path": by_path}


# the numbers a configuration's ``check.limits`` may name, and the entry of
# ``gap_stats`` each is
NUMBERS = {"max_logit_gap": "max", "mismatch_share": "mismatch_share"}


def compare(stats: Dict, limits: Dict) -> Dict:
    """Each number the limits name, beside its limit."""
    return {name: {"value": stats.get(NUMBERS[name]), "limit": limit}
            for name, limit in limits.items()}


def within(compared: Dict) -> bool:
    """``correct``: every compared number is there and within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in compared.values())
