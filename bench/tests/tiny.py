"""Tiny cells for CPU tests: the configurations' recipe at test sizes."""
from __future__ import annotations

import copy

from bench.lib import spec as S


def tiny_config(name: str, *, f32: bool = True,
                embedding_multiplier: bool = False) -> dict:
    """A configuration file shrunk to CPU size (same arch, same keys), in
    f32 or, with ``f32=False``, in the dtypes the configuration states.

    The embedding multiplier is set to 1 unless ``embedding_multiplier``:
    at a width of 64 Granite's 12 makes the embedded token outweigh every
    layer's output, so the tiny model repeats its input whatever its cache
    holds, and a fault in the cache could not show."""
    c = copy.deepcopy(S.load_config(name))
    if not embedding_multiplier and "embedding_multiplier" in c:
        c["embedding_multiplier"] = 1.0
    dtypes = {k: c["serving"][k] for k in ("param_dtype", "dtype")}
    moe = "num_local_experts" in c
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=32 if moe else 96, num_hidden_layers=4,
             vocab_size=500)
    if moe:
        c.update(num_local_experts=4, num_experts_per_tok=2)
    c["serving"].update(param_dtype="float32", dtype="float32",
                        padded_vocab=2048, exit_groups=[2, 3])
    if not f32:
        c["serving"].update(dtypes)
    kw = dict(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
              vocab_size=500)
    if moe:
        kw.update(n_experts=4, top_k=2, d_ff=32, moe_d_ff=32)
    else:
        kw.update(d_ff=96)
    c["program"]["set"] = {**c["program"].get("set", {}), **kw}
    # f32 program against the f32 reference: they differ by accumulation
    # order alone, far below a logit gap a wrong token opens (> 1e-2 here)
    c["check"]["limits"] = {"max_logit_gap": 1e-3}
    return c


def tiny_traffic(name: str) -> dict:
    t = copy.deepcopy(S.load_traffic(name))
    t["engine"] = {"batch_size": 4, "cache_capacity": 160}
    t["prompt"].update(median=24, min=16, max=64, ladder=[16, 32, 64])
    t["output"].update(median=12, min=4, max=32)
    if t["arrivals"]["kind"] == "poisson":
        t["arrivals"]["rate_per_s"] = 8.0
    else:
        t["arrivals"]["backlog_cycle"] = 16
    return t


def tiny_cell(config: str, traffic: str, *, f32: bool = True) -> S.Cell:
    """A configuration under a mix at test size, with the metrics of the
    benchmark's cell of that pair or, for a pair not (yet) a cell, every
    metric whose reader the mix's arrivals can feed."""
    bm = S.load_benchmark()
    cell = next((w for w in bm["workloads"]
                 if w["config"] == config and w["traffic"] == traffic), None)
    mix = tiny_traffic(traffic)
    if cell is not None:
        full = S.load_cell(cell["name"])
        name, e2e, per_layer = full.name, full.end_to_end, full.per_layer
    else:
        name = f"{config}.{traffic}"
        e2e = [m for m in bm["end_to_end"] if m["name"] == "setup_s"] + [
            {"name": n, "unit": u} for n, u in (
                (("ttft_p90_ms", "ms"), ("itl_p99_ms", "ms"))
                if mix["arrivals"]["kind"] == "poisson"
                else (("output_tokens_per_s", "tokens/s"),))]
        per_layer = []
    return S.Cell(name=name, config=tiny_config(config, f32=f32),
                  traffic=mix, chips=1, end_to_end=e2e, per_layer=per_layer)
