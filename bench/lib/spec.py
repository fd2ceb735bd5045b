"""Find a cell's files by name: ``BENCHMARK.json`` at the checkout root,
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py``. Adding a cell or a metric adds files; no
code here names one."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(kind: str, name: str) -> Dict:
    path = os.path.join(BENCH_DIR, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> Dict:
    return _load_json("configs", name)


def load_traffic(name: str) -> Dict:
    return _load_json("traffic", name)


def load_reader(metric: str) -> Callable:
    """The ``read(window)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Cell:
    name: str
    config: Dict
    traffic: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bm = load_benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    return Cell(name=name, config=load_config(w["config"]),
                traffic=load_traffic(w["traffic"]), chips=int(w["chips"]),
                end_to_end=[m for m in bm["end_to_end"] if _reports(m, name)],
                per_layer=[m for m in bm["per_layer"] if _reports(m, name)])


# ---------------------------------------------------------------------------
# configuration file -> the program's config, checked against the file
# ---------------------------------------------------------------------------


def depth_of(config: Dict, label) -> int:
    """A mix names depths by role: ``"full"`` or ``"exitN"`` (the N-th exit
    group of the configuration); a number is taken as it is."""
    if isinstance(label, int):
        return label
    if label == "full":
        return config["num_hidden_layers"]
    if label.startswith("exit"):
        return config["serving"]["exit_groups"][int(label[4:])]
    raise ValueError(f"unknown depth label {label!r}")


def program_config(config: Dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry ``program.arch`` with ``program.set`` applied. Every size the file
    states is checked against it, so the file stays the configuration as
    run."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import ElasticConfig

    prog = config["program"]
    kw = dict(prog.get("set", {}))
    sv = config["serving"]
    kw["elastic"] = ElasticConfig(
        width_fractions=tuple(sv["width_fractions"]),
        exit_layers=tuple(sv["exit_groups"]))
    kw["param_dtype"] = sv["param_dtype"]
    kw["dtype"] = sv["dtype"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **kw)
    moe = config.get("num_local_experts", 0)
    want = {
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "vocab_size": config["vocab_size"],
        "tie_embeddings": config["tie_word_embeddings"],
        "rope_theta": config["rope_theta"],
        "n_experts": moe,
        "top_k": config.get("num_experts_per_tok", 0),
        ("moe_d_ff" if moe else "d_ff"): config["intermediate_size"],
        "activation": {"silu": "swiglu"}[config["hidden_act"]],
        "norm": "rmsnorm",
        "sliding_window": config.get("sliding_window") or 0,
        "period": 1,
    }
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if cfg.padded_vocab() != sv["padded_vocab"]:  # the weights' drawn shape
        bad["padded_vocab"] = (cfg.padded_vocab(), sv["padded_vocab"])
    if bad:
        raise ValueError(f"program config {cfg.name} differs from "
                         f"{config['name']}.json (program, file): {bad}")
    return cfg


def fold_multipliers(params, config: Dict):
    """The program's weights made to compute the configuration's scalar
    multipliers, which the program itself does not apply.

    Granite's ``embedding_multiplier`` scales the embedded input,
    ``attention_multiplier`` replaces the ``1/sqrt(head size)`` of the
    attention scores, ``residual_multiplier`` scales every block's output
    before it joins the residual stream, and ``logits_scaling`` divides
    the logits. Each is linear, so it goes exactly into a weight: the
    embedding, the query projection, the attention and MLP/expert output
    projections, and the final and exit norms' scales (the unembedding
    when it is not tied). With every multiplier at 1 nothing changes."""
    import math

    hd = config["hidden_size"] // config["num_attention_heads"]
    emb = float(config.get("embedding_multiplier", 1.0))
    att = float(config.get("attention_multiplier", 1 / math.sqrt(hd))) * math.sqrt(hd)
    res = float(config.get("residual_multiplier", 1.0))
    logit = float(config.get("logits_scaling", 1.0))
    if (emb, att, res, logit) == (1.0, 1.0, 1.0, 1.0):
        return params

    def scaled(w, f):
        return (w.astype("float32") * f).astype(w.dtype)

    p = dict(params)
    p["embed"] = scaled(p["embed"], emb)
    tied = "unembed" not in p
    head = 1.0 / (logit * emb) if tied else 1.0
    if not tied:
        p["unembed"] = scaled(p["unembed"], 1.0 / logit)
    p["final_norm"] = {"scale": scaled(p["final_norm"]["scale"], head)}
    if "exit_norms" in p:
        p["exit_norms"] = {g: {"scale": scaled(n["scale"], head)}
                           for g, n in p["exit_norms"].items()}
    stack = {}
    for pos, lp in p["stack"].items():
        lp = dict(lp)
        lp["attn"] = {**lp["attn"], "wq": scaled(lp["attn"]["wq"], att),
                      "wo": scaled(lp["attn"]["wo"], res)}
        for ff in ("moe", "mlp"):
            if ff in lp:
                lp[ff] = {**lp[ff], "wo": scaled(lp[ff]["wo"], res)}
        stack[pos] = lp
    p["stack"] = stack
    return p


def make_weights(config: Dict, cfg, key):
    """The program's weights for a configuration, on the device in one
    jitted call: its initializer from ``key``, then the multipliers
    folded in."""
    import jax

    from repro.models.model import init_params

    return jax.jit(lambda k: fold_multipliers(init_params(k, cfg), config))(key)


def mode_table(config: Dict, traffic: Dict):
    """(depth, width) of every entry of the mix's mode schedule."""
    return [(depth_of(config, m["depth"]), float(m["width"]))
            for m in traffic["modes"]["mix"]]


def reference_module(config: Dict):
    """The plain reference named by the configuration file
    (``bench/references/<reference>.py``)."""
    path = os.path.join(BENCH_DIR, "references", config["reference"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + config["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

