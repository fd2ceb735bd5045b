"""Tiny cells for CPU tests: the configurations' recipe at test sizes."""
from __future__ import annotations

import copy

from bench.lib import spec as S


def tiny_config(name: str, *, f32: bool = True,
                embedding_multiplier: bool = False) -> dict:
    """The configuration file ``name`` shrunk to CPU size (``shrink``)."""
    return shrink(S.load_config(name), f32=f32,
                  embedding_multiplier=embedding_multiplier)


def shrink(conf: dict, *, f32: bool = True,
           embedding_multiplier: bool = False) -> dict:
    """A configuration file shrunk to CPU size (same arch, same keys), in
    f32 or, with ``f32=False``, in the dtypes the configuration states.
    Every size the harness checks (``spec.CHECKS``) shrinks, and the
    program's config with it: widths, experts and their widths, Mamba2
    sizes, and ``layer_types`` to one short period that keeps each kind,
    four groups deep. A ``program.checks`` attribute whose file key
    shrank follows it.

    The embedding multiplier is set to 1 unless ``embedding_multiplier``:
    at a width of 64 Granite's 12 makes the embedded token outweigh every
    layer's output, so the tiny model repeats its input whatever its cache
    holds, and a fault in the cache could not show."""
    c = copy.deepcopy(conf)
    if not embedding_multiplier and "embedding_multiplier" in c:
        c["embedding_multiplier"] = 1.0
    dtypes = {k: c["serving"][k] for k in ("param_dtype", "dtype")}
    moe = "num_local_experts" in c
    pattern = list(dict.fromkeys(c.get("layer_types") or ["attention"]))
    checks = c["program"].get("checks", {})
    stated = {key: c.get(key) for key in checks.values()}
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=32 if moe else 96,
             num_hidden_layers=4 * len(pattern), vocab_size=500)
    kw = dict(n_layers=4 * len(pattern), d_model=64, n_heads=4, n_kv_heads=2,
              head_dim=16, vocab_size=500)
    if "head_dim" in c:  # one that is not hidden_size / heads
        c["head_dim"] = kw["head_dim"] = 8
    if moe:
        c.update(num_local_experts=4, num_experts_per_tok=2)
        kw.update(n_experts=4, top_k=2, d_ff=32, moe_d_ff=32)
    else:
        kw.update(d_ff=96)
    for key, size in (("num_routed_experts", 8),
                      ("shared_intermediate_size", 48)):
        if key in c:
            c[key] = size
    if "layer_types" in c:
        c["layer_types"] = pattern
        kw["layer_pattern"] = tuple(S.LAYER_KINDS[k] for k in pattern)
    if "mamba_d_state" in c:  # heads of 8; chunks a test prompt spans
        c.update(mamba_d_state=16, mamba_d_head=8, mamba_n_groups=1,
                 mamba_chunk_size=8, mamba_n_heads=64 * c["mamba_expand"] // 8)
        kw.update(ssm_state=16, ssm_head_dim=8, ssm_ngroups=1, ssm_chunk=8,
                  ssm_conv=c["mamba_d_conv"], ssm_expand=c["mamba_expand"])
    c["serving"].update(param_dtype="float32", dtype="float32",
                        padded_vocab=2048, exit_groups=[2, 3])
    if not f32:
        c["serving"].update(dtypes)
    kw.update({attr: c[key] for attr, key in checks.items()
               if c.get(key) != stated[key]})
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


def tiny_cell(config: str, traffic: str, *, f32: bool = True,
              chips: int = 0) -> S.Cell:
    """A configuration under a mix at test size, with the chips and metrics
    of the benchmark's cell of that pair or, for a pair not (yet) a cell,
    one chip and every metric whose reader the mix's arrivals can feed;
    ``chips`` overrides the count."""
    bm = S.load_benchmark()
    cell = next((w for w in bm["workloads"]
                 if w["config"] == config and w["traffic"] == traffic), None)
    mix = tiny_traffic(traffic)
    if cell is not None:
        full = S.load_cell(cell["name"])
        name, e2e, per_layer = full.name, full.end_to_end, full.per_layer
        cell_chips = full.chips
    else:
        name, cell_chips = f"{config}.{traffic}", 1
        e2e = [m for m in bm["end_to_end"] if m["name"] == "setup_s"] + [
            {"name": n, "unit": u} for n, u in (
                (("ttft_p90_ms", "ms"), ("itl_p99_ms", "ms"))
                if mix["arrivals"]["kind"] == "poisson"
                else (("output_tokens_per_s", "tokens/s"),))]
        per_layer = []
    return S.Cell(name=name, config=tiny_config(config, f32=f32),
                  traffic=mix, chips=chips or cell_chips,
                  end_to_end=e2e, per_layer=per_layer)
