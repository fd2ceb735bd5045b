"""Find a cell's files by name: ``BENCHMARK.json`` at the checkout root,
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py``. Adding a cell or a metric adds files; no
code here names one."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

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


# a layer kind as a configuration file names it (``layer_types``) -> as the
# program's ``layer_pattern`` does
LAYER_KINDS = {"attention": "attn", "mamba": "ssm"}
# the file's ``hidden_act`` -> the program's ``activation``: HF's ``silu``
# MLPs are gated (SwiGLU), its ``gelu`` and ``relu2`` ones are not
ACTIVATIONS = {"silu": "swiglu", "gelu": "gelu", "relu2": "squared_relu"}
POSITIONS = {"rope": True, "nope": False}


def layer_types(config: Dict) -> List[str]:
    """The program's kind of every layer the file runs: ``layer_types``
    (which may list the published depth, longer than the file's) repeated
    over ``num_hidden_layers``; a file without it is all attention."""
    types = config.get("layer_types") or ["attention"]
    bad = sorted(set(types) - set(LAYER_KINDS))
    if bad:
        raise ValueError(f"{config['name']}.json: layer_types names {bad}; "
                         f"the harness knows {sorted(LAYER_KINDS)}")
    return [LAYER_KINDS[types[i % len(types)]]
            for i in range(config["num_hidden_layers"])]


def period(config: Dict) -> int:
    """Layers in one period of the file's ``layer_types``: the shortest
    prefix that the whole list repeats (1 without the key). The program
    scans one period as a group, so its depths count periods."""
    types = config.get("layer_types") or ["attention"]
    return next(p for p in range(1, len(types) + 1)
                if types == types[:p] * (len(types) // p))


def depth_of(config: Dict, label) -> int:
    """A depth in layers. A mix names depths by role: ``"full"`` (every
    layer of the file) or ``"exitN"`` (the N-th exit group of the
    configuration, counted in periods); a number is taken as layers."""
    if isinstance(label, int):
        return label
    if label == "full":
        return config["num_hidden_layers"]
    if label.startswith("exit"):
        return config["serving"]["exit_groups"][int(label[4:])] * period(config)
    raise ValueError(f"unknown depth label {label!r}")


def engine_depth(config: Dict, layers: int) -> int:
    """The engine's depth for ``layers`` layers: whole groups (periods)."""
    p = period(config)
    if layers % p:
        raise ValueError(f"{config['name']}.json: a depth of {layers} layers "
                         f"is not a whole number of {p}-layer groups")
    return layers // p


def _pattern(config: Dict):
    """``layer_types`` -> the program's ``layer_pattern``: one period."""
    p = period(config)
    if config["num_hidden_layers"] % p:
        raise ValueError(f"{config['name']}.json: num_hidden_layers "
                         f"{config['num_hidden_layers']} is not a whole number "
                         f"of layer_types' {p}-layer periods")
    return tuple(layer_types(config)[:p])


def _has_rope(config: Dict) -> bool:
    kind = config.get("position_embedding_type", "rope")
    if kind not in POSITIONS:
        raise ValueError(f"{config['name']}.json: position_embedding_type "
                         f"{kind!r}; the harness knows {sorted(POSITIONS)}")
    return POSITIONS[kind]


def _activation(v, config: Dict):
    if v not in ACTIVATIONS:
        raise ValueError(f"{config['name']}.json: hidden_act {v!r}; the "
                         f"harness knows {sorted(ACTIVATIONS)}")
    return ACTIVATIONS[v]


def _same(v, config: Dict):
    return v


# file key -> (the program's ``ModelConfig`` attribute, the file's value as
# the program states it). Each key is checked where the file states it or,
# where ``DEFAULTS`` gives one, at the value that its absence means.
CHECKS: Dict[str, Tuple[str, Callable]] = {
    "hidden_size": ("d_model", _same),
    "num_hidden_layers": ("n_layers", _same),
    "num_attention_heads": ("n_heads", _same),
    "num_key_value_heads": ("n_kv_heads", _same),
    "head_dim": ("head_dim", _same),
    "vocab_size": ("vocab_size", _same),
    "tie_word_embeddings": ("tie_embeddings", _same),
    "position_embedding_type": ("use_rope", lambda v, c: _has_rope(c)),
    "rope_theta": ("rope_theta", _same),
    "num_local_experts": ("n_experts", _same),
    "num_experts_per_tok": ("top_k", _same),
    "hidden_act": ("activation", _activation),
    "normalization_function": ("norm", _same),
    "sliding_window": ("sliding_window", lambda v, c: v or 0),
    "layer_types": ("layer_pattern", lambda v, c: _pattern(c)),
    "mamba_d_state": ("ssm_state", _same),
    "mamba_d_head": ("ssm_head_dim", _same),
    "mamba_n_heads": ("ssm_nheads", _same),
    "mamba_n_groups": ("ssm_ngroups", _same),
    "mamba_d_conv": ("ssm_conv", _same),
    "mamba_expand": ("ssm_expand", _same),
    "mamba_chunk_size": ("ssm_chunk", _same),
}
# what a file that leaves a key out states by that (Hugging Face's
# defaults); ``None``: nothing, as the key it follows from is left out too
DEFAULTS: Dict[str, Callable] = {
    "head_dim": lambda c: (c["hidden_size"] // c["num_attention_heads"]
                           if "num_attention_heads" in c else None),
    "position_embedding_type": lambda c: "rope",
    "num_local_experts": lambda c: 0,
    "num_experts_per_tok": lambda c: 0,
    "normalization_function": lambda c: "rmsnorm",
    "sliding_window": lambda c: 0,
    "layer_types": lambda c: ["attention"],
}
# a file with Mamba2 layers states every size of them
MAMBA_KEYS = tuple(k for k in CHECKS if k.startswith("mamba_"))


def wanted(config: Dict) -> Dict[str, Tuple[str, object]]:
    """Every program attribute the file fixes: attribute -> (file key, the
    value the program has to hold). ``intermediate_size`` is the width of
    one expert where the file has experts, else the MLP's; the file's
    ``program.checks`` (``{attribute: file key}``) adds attributes that a
    configuration brings with it, each taken as the file states it."""
    if "ssm" in layer_types(config):
        missing = [k for k in MAMBA_KEYS if k not in config]
        if missing:
            raise ValueError(f"{config['name']}.json has Mamba2 layers and "
                             f"does not state {missing}")
    out = {}
    for key, (attr, how) in CHECKS.items():
        v = config[key] if key in config else DEFAULTS.get(
            key, lambda c: None)(config)
        if key in config or v is not None:
            out[attr] = (key, how(v, config))
    if "intermediate_size" in config:
        ff = "moe_d_ff" if config.get("num_local_experts") else "d_ff"
        out[ff] = ("intermediate_size", config["intermediate_size"])
    if not _has_rope(config):  # NoPE: the theta the file states is unused
        out.pop("rope_theta", None)
    for attr, key in config["program"].get("checks", {}).items():
        if key not in config:
            raise ValueError(f"{config['name']}.json: program.checks names "
                             f"{key!r} for {attr!r}, and the file has no {key!r}")
        out[attr] = (key, config[key])
    return out


def program_config(config: Dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry ``program.arch`` with ``program.set`` applied. Every size the file
    states is checked against it (``wanted``), so the file stays the
    configuration as run; a checked attribute that the program's config
    does not have is refused by name."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import ElasticConfig

    prog = config["program"]
    # JSON has lists where the program's (hashable) config holds tuples
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in prog.get("set", {}).items()}
    sv = config["serving"]
    kw["elastic"] = ElasticConfig(
        width_fractions=tuple(sv["width_fractions"]),
        exit_layers=tuple(sv["exit_groups"]))
    kw["param_dtype"] = sv["param_dtype"]
    kw["dtype"] = sv["dtype"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **kw)
    want = wanted(config)
    missing = sorted(a for a in want if not hasattr(cfg, a))
    if missing:
        raise ValueError(f"{config['name']}.json checks {missing}, which the "
                         f"program's config {cfg.name} does not have")
    bad = {f"{key} -> {attr}": (getattr(cfg, attr), v)
           for attr, (key, v) in want.items() if getattr(cfg, attr) != v}
    if cfg.padded_vocab() != sv["padded_vocab"]:  # the weights' drawn shape
        bad["padded_vocab"] = (cfg.padded_vocab(), sv["padded_vocab"])
    if bad:
        raise ValueError(f"program config {cfg.name} differs from "
                         f"{config['name']}.json (program, file): {bad}")
    return cfg


# the leaves that project a block's output into the residual stream:
# attention's and every MLP's or expert's ``wo`` (a shared expert's too,
# wherever the program nests it) and a Mamba2 mixer's ``out_proj``
OUTPUT_PROJECTIONS = ("wo", "out_proj")


def head_dim(config: Dict):
    """The attention head size: the file's ``head_dim`` where it states
    one, else ``hidden_size / num_attention_heads`` (``None`` without
    attention heads)."""
    if "head_dim" in config:
        return config["head_dim"]
    heads = config.get("num_attention_heads")
    return config["hidden_size"] // heads if heads else None


def fold_multipliers(params, config: Dict):
    """The program's weights made to compute the configuration's scalar
    multipliers, which the program itself does not apply.

    Granite's ``embedding_multiplier`` scales the embedded input,
    ``attention_multiplier`` replaces the ``1/sqrt(head size)`` of the
    attention scores, ``residual_multiplier`` scales every block's output
    before it joins the residual stream, and ``logits_scaling`` divides
    the logits. Each is linear, so it goes exactly into a weight: the
    embedding, each attention layer's query projection, every block's
    output projection (``OUTPUT_PROJECTIONS``: attention, Mamba2 mixer,
    MLP, experts, shared expert), each scaled once, and the final and exit
    norms' scales (the unembedding when it is not tied). With every
    multiplier at 1 nothing changes."""
    import math

    hd = head_dim(config)
    att = (float(config.get("attention_multiplier", 1 / math.sqrt(hd)))
           * math.sqrt(hd) if hd else 1.0)
    emb = float(config.get("embedding_multiplier", 1.0))
    res = float(config.get("residual_multiplier", 1.0))
    logit = float(config.get("logits_scaling", 1.0))
    if (emb, att, res, logit) == (1.0, 1.0, 1.0, 1.0):
        return params

    def scaled(w, f):
        return (w.astype("float32") * f).astype(w.dtype)

    def outputs_scaled(tree):
        return {k: scaled(v, res) if k in OUTPUT_PROJECTIONS
                else outputs_scaled(v) if isinstance(v, dict) else v
                for k, v in tree.items()}

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
        lp = outputs_scaled(lp)
        if "attn" in lp:
            lp["attn"] = {**lp["attn"], "wq": scaled(lp["attn"]["wq"], att)}
        stack[pos] = lp
    p["stack"] = stack
    return p


def make_weights(config: Dict, cfg, key, mesh=None):
    """The program's weights for a configuration, on the device in one
    jitted call: its initializer from ``key``, then the multipliers
    folded in. With a serving ``mesh`` the call makes every array sharded
    as the mesh's executor places it (``param_specs`` under
    ``serve_policy``); jit's values do not depend on the output sharding,
    so they are the one-device weights array for array."""
    import jax

    from repro.models.model import init_params

    make = lambda k: fold_multipliers(init_params(k, cfg), config)  # noqa: E731
    if mesh is None:
        return jax.jit(make)(key)
    from repro.parallel import sharding as SH

    specs = SH.param_specs(jax.eval_shape(make, key), cfg, mesh,
                           SH.serve_policy(cfg, mesh.shape["model"]))
    return jax.jit(make, out_shardings=SH.shardings_for(specs, mesh))(key)


def mode_table(config: Dict, traffic: Dict):
    """(depth in layers, width) of every entry of the mix's mode schedule."""
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

