"""The benchmark's configurations read what they read before the harness
took layers of several kinds: the FLOPs behind ``decode_mfu``, the
program's config, the mode tables and the weights. Every number here was
computed by the harness as it stood before, for period-1 files alone."""
from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
import pytest

from bench.lib import flops as F
from bench.lib import spec as S
from bench.tests.tiny import tiny_config

# (config, width, depth in layers, keys in attention) -> FLOPs of one token
FLOPS = [
    ("granite-moe-1b-a400m", 1.0, 24, 512, 907548672),
    ("granite-moe-1b-a400m", 0.5, 24, 2048, 580392960),
    ("granite-moe-1b-a400m", 1.0, 12, 100, 483858432),
    ("phi3-medium-14b", 1.0, 40, 512, 28010741760),
    ("phi3-medium-14b", 0.5, 40, 2048, 14798684160),
    ("phi3-medium-14b", 1.0, 20, 100, 14000783360),
]


@pytest.mark.parametrize("name,width,depth,ctx,want", FLOPS)
def test_decode_token_flops_as_before(name, width, depth, ctx, want):
    assert F.decode_token_flops(S.load_config(name), width, depth, ctx) == want


MODES = [
    ("granite-moe-1b-a400m", "chat", [(24, 1.0), (24, 0.5), (12, 1.0)]),
    ("granite-moe-1b-a400m", "longprompt", [(24, 1.0)]),
    ("phi3-medium-14b", "chat-tp4", [(40, 1.0), (40, 0.5), (20, 1.0)]),
    ("phi3-medium-14b.8L", "batch", [(8, 1.0), (8, 0.5), (4, 1.0)]),
]


@pytest.mark.parametrize("name,mix,want", MODES)
def test_mode_tables_as_before(name, mix, want):
    conf = S.load_config(name)
    table = S.mode_table(conf, S.load_traffic(mix))
    assert table == want
    # period 1: the engine's groups are the layers
    assert [S.engine_depth(conf, d) for d, _ in table] == [d for d, _ in want]


def _built_as_before(conf):
    """The config the harness built before: the registry entry with
    ``program.set``, the exits, widths and dtypes applied."""
    from repro.configs import get_config
    from repro.configs.base import ElasticConfig

    sv = conf["serving"]
    return dataclasses.replace(
        get_config(conf["program"]["arch"]), **conf["program"].get("set", {}),
        elastic=ElasticConfig(width_fractions=tuple(sv["width_fractions"]),
                              exit_layers=tuple(sv["exit_groups"])),
        param_dtype=sv["param_dtype"], dtype=sv["dtype"])


CONFIGS = ["granite-moe-1b-a400m", "phi3-medium-14b", "phi3-medium-14b.8L"]


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_as_before(name):
    for conf in (S.load_config(name), tiny_config(name)):
        assert S.program_config(conf) == _built_as_before(conf)


def _fold_as_before(params, config):
    """The fold as it stood, for layers that all hold attention."""
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


@pytest.mark.parametrize("f32", [True, False], ids=["f32", "served_dtypes"])
@pytest.mark.parametrize("name", ["granite-moe-1b-a400m", "phi3-medium-14b"])
def test_tiny_weights_as_before(name, f32):
    from repro.models.model import init_params

    conf = tiny_config(name, f32=f32, embedding_multiplier=True)
    cfg = S.program_config(conf)
    key = jax.random.PRNGKey(29)
    new = S.make_weights(conf, cfg, key)
    old = jax.jit(lambda k: _fold_as_before(init_params(k, cfg), conf))(key)
    assert jax.tree_util.tree_structure(new) == jax.tree_util.tree_structure(old)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(new),
                            jax.tree_util.tree_leaves(old)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))
