"""A configuration whose layers differ by kind, through the harness on the
CPU: a Granite-4.0-H-shaped file (Mamba2 mixers with NoPE attention in a
period of ten, sparse experts with a shared one, Granite's multipliers),
written here, is checked against the program's config, named in layers,
folded, counted, shrunk, and served through the driver's warm-up. No
reference check runs: the benchmark has no hybrid reference yet."""
from __future__ import annotations

import copy
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.lib import driver as D
from bench.lib import flops as F
from bench.lib import spec as S
from bench.tests.tiny import shrink, tiny_traffic

SEED = 2**31 + 23
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def granite_h() -> dict:
    """granite-4.0-h-small's published config, cut to 20 of its 40 layers
    (two whole periods), with ``head_dim`` stated; the program's hybrid
    entry set to match, and one ``program.checks`` entry."""
    return {
        "name": "granite-4.0-h-small",
        "source": "https://huggingface.co/ibm-granite/granite-4.0-h-small",
        # gives the weights' root key only: no reference check runs here
        "reference": "gqa_decoder",
        "model_type": "granitemoehybrid",
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768, "head_dim": 128,
        "layer_types": PERIOD * 4, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_hidden_layers": 20,
        "num_key_value_heads": 8, "num_local_experts": 72,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
        "vocab_size": 100352, "expert_layer_period": 1,
        "serving": {"param_dtype": "bfloat16", "dtype": "bfloat16",
                    "padded_vocab": 100352, "exit_groups": [1],
                    "width_fractions": [0.5, 1.0]},
        "program": {
            "arch": "jamba-v0.1-52b",
            "checks": {"moe_period": "expert_layer_period"},
            "set": {"n_layers": 20, "d_model": 4096, "n_heads": 32,
                    "n_kv_heads": 8, "head_dim": 128, "d_ff": 768,
                    "moe_d_ff": 768, "vocab_size": 100352,
                    "tie_embeddings": True, "n_experts": 72, "top_k": 10,
                    "moe_period": 1,
                    "layer_pattern": [S.LAYER_KINDS[k] for k in PERIOD],
                    "ssm_state": 128, "ssm_head_dim": 64, "ssm_conv": 4,
                    "ssm_expand": 2, "ssm_ngroups": 1, "ssm_chunk": 256,
                    "moe_impl": "dense"}},
        "check": {"sample_tokens": 600, "limits": {"max_logit_gap": 0.1}},
    }


def tiny() -> dict:
    return shrink(granite_h())


# -- program_config ----------------------------------------------------------


def test_published_and_tiny_files_are_accepted():
    cfg = S.program_config(granite_h())
    assert (cfg.period, cfg.n_groups, cfg.use_rope) == (10, 2, False)
    assert cfg.ssm_nheads == 128 and cfg.head_dim == 128
    t = tiny()
    assert t["layer_types"] == ["mamba", "attention"]
    assert t["head_dim"] != t["hidden_size"] // t["num_attention_heads"]
    cfg = S.program_config(t)
    assert cfg.layer_pattern == ("ssm", "attn") and cfg.n_groups == 4
    assert (cfg.ssm_nheads, cfg.ssm_head_dim, cfg.head_dim) == (16, 8, 8)


def _changed(v):
    if isinstance(v, bool):
        return not v
    if isinstance(v, (int, float)):
        return 2 * v
    if isinstance(v, list):
        return v[::-1]
    return {"silu": "gelu", "nope": "rope", "rmsnorm": "layernorm"}[v]


CHECKED = sorted({key for key, _ in S.wanted(tiny()).values()
                  if key in tiny()})


@pytest.mark.parametrize("key", CHECKED)
def test_any_checked_size_changed_is_refused_by_name(key):
    c = tiny()
    c[key] = _changed(c[key])
    with pytest.raises(ValueError, match=key):
        S.program_config(c)


def test_table_covers_the_hybrid_keys():
    assert {"layer_types", "head_dim", "position_embedding_type",
            "expert_layer_period"} <= set(CHECKED)
    assert set(S.MAMBA_KEYS) <= set(CHECKED)
    # without positions the theta is never used, so it is not compared
    assert "rope_theta" not in CHECKED
    c = tiny()
    c["rope_theta"] = 1.0
    S.program_config(c)


@pytest.mark.parametrize("key", ["layer_types", "mamba_d_state",
                                 "mamba_d_head", "expert_layer_period"])
def test_missing_key_is_refused_by_name(key):
    c = tiny()
    del c[key]
    with pytest.raises(ValueError, match=key):
        S.program_config(c)


def test_layer_types_not_repeating_the_program_pattern_is_refused():
    c = tiny()
    c["layer_types"] = ["mamba", "attention", "mamba", "mamba"]
    with pytest.raises(ValueError, match="layer_types"):
        S.program_config(c)
    c = tiny()
    c["layer_types"] = ["mamba", "cross_attention"]
    with pytest.raises(ValueError, match="cross_attention"):
        S.program_config(c)


def test_checks_attribute_the_program_lacks_is_refused_by_name():
    c = tiny()
    c["program"]["checks"]["shared_d_ff"] = "shared_intermediate_size"
    with pytest.raises(ValueError, match="shared_d_ff"):
        S.program_config(c)
    c = tiny()
    c["program"]["checks"]["moe_period"] = "moe_layer_period"
    with pytest.raises(ValueError, match="moe_layer_period"):
        S.program_config(c)


# -- one depth unit ----------------------------------------------------------


def test_depths_are_layers_and_the_engine_gets_groups():
    c = granite_h()
    assert S.period(c) == 10
    assert S.depth_of(c, "full") == 20 and S.depth_of(c, "exit0") == 10
    assert S.mode_table(c, S.load_traffic("chat")) == [
        (20, 1.0), (20, 0.5), (10, 1.0)]
    assert [S.engine_depth(c, d) for d in (20, 10)] == [2, 1]
    with pytest.raises(ValueError, match="whole number"):
        S.engine_depth(c, 15)
    assert D.parse_mode("d2w50", 10) == (20, 0.5)
    t = tiny()
    assert S.mode_table(t, tiny_traffic("chat")) == [
        (8, 1.0), (8, 0.5), (4, 1.0)]


# -- the fold, per kind ------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_weights():
    from repro.models.model import init_params

    conf = tiny()
    cfg = S.program_config(conf)
    raw = jax.jit(init_params, static_argnums=1)(jax.random.PRNGKey(3), cfg)
    folded = S.fold_multipliers(raw, conf)
    first = lambda p: jax.tree_util.tree_map(lambda a: a[0], p["stack"])  # noqa: E731
    return conf, cfg, first(raw), first(folded)


def _close(a, b):
    """Equal to f32 rounding: one rounding of a weight, then sums."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6 * np.abs(b).max())


def _x(cfg, seq=12):
    return jax.random.normal(jax.random.PRNGKey(5), (2, seq, cfg.d_model),
                             jnp.float32)


def test_fold_scales_a_mamba2_mixer_by_the_residual_multiplier(tiny_weights):
    from repro.models import ssm as SSM

    conf, cfg, raw, folded = tiny_weights
    assert cfg.layer_kind(0) == "ssm"
    x = _x(cfg)
    y0, _ = SSM.ssm_forward(raw["pos0"]["ssm"], x, cfg)
    y1, _ = SSM.ssm_forward(folded["pos0"]["ssm"], x, cfg)
    _close(y1, 0.22 * y0)


def test_fold_scales_attention_by_both_multipliers(tiny_weights):
    from repro.models import layers as L

    conf, cfg, raw, folded = tiny_weights
    assert cfg.layer_kind(1) == "attn" and not cfg.use_rope
    x = _x(cfg)
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    att = conf["attention_multiplier"] * math.sqrt(conf["head_dim"])
    a = raw["pos1"]["attn"]
    y0, _ = L.mha({**a, "wq": a["wq"] * att}, x, cfg, pos)
    y1, _ = L.mha(folded["pos1"]["attn"], x, cfg, pos)
    _close(y1, 0.22 * y0)


@pytest.mark.parametrize("pos", ["pos0", "pos1"])
def test_fold_scales_an_expert_block_by_the_residual_multiplier(
        tiny_weights, pos):
    from repro.models import moe as MOE

    conf, cfg, raw, folded = tiny_weights
    x = _x(cfg)
    y0, _ = MOE.apply_moe_dense(raw[pos]["moe"], x, cfg)
    y1, _ = MOE.apply_moe_dense(folded[pos]["moe"], x, cfg)
    _close(y1, 0.22 * y0)


def test_fold_scales_each_output_projection_once_wherever_it_sits():
    """A shared expert nested in the expert block: its output projection
    is scaled once, its other weights and the router not at all."""
    w = lambda v: jnp.full((2, 2), v, jnp.float32)  # noqa: E731
    layer = {"norm1": {"scale": w(1.0)},
             "ssm": {"w_x": w(1.0), "out_proj": w(1.0)},
             "moe": {"router": w(1.0), "wi": w(1.0), "wo": w(1.0),
                     "shared": {"wi": w(1.0), "wg": w(1.0), "wo": w(1.0)}}}
    params = {"embed": w(1.0), "final_norm": {"scale": w(1.0)},
              "stack": {"pos0": layer}}
    out = S.fold_multipliers(params, {"hidden_size": 4,
                                      "residual_multiplier": 0.5})
    flat = {jax.tree_util.keystr(k): float(v[0, 0]) for k, v in
            jax.tree_util.tree_leaves_with_path(out["stack"])}
    assert {k for k, v in flat.items() if v != 1.0} == {
        "['pos0']['ssm']['out_proj']", "['pos0']['moe']['wo']",
        "['pos0']['moe']['shared']['wo']"}
    assert {v for v in flat.values() if v != 1.0} == {0.5}


# -- FLOPs by kind -----------------------------------------------------------


def test_attention_and_mamba2_flops_by_hand():
    c = granite_h()
    # q 32 x 128 = 4096 and kv 8 x 128 = 1024 wide; 512 keys
    assert F.attention_flops(c, 1.0, 512) == \
        2 * 4096 * (2 * 4096 + 2 * 1024) + 4 * 512 * 4096 == 92274688
    # 128 heads of 64 (inner 8192), B and C 2 x 1 x 128, conv 4
    in_proj = 2 * 4096 * (2 * 8192 + 256 + 128)
    conv = 2 * 4 * (8192 + 256)
    state = 5 * 128 * 64 * 128
    out_proj = 2 * 8192 * 4096
    assert F.mamba_flops(c, 1.0) == in_proj + conv + state + out_proj \
        == 209782784
    # width 0.5: 64 heads (inner 4096); B and C are never gated
    assert F.mamba_flops(c, 0.5) == (2 * 4096 * (2 * 4096 + 256 + 64)
                                     + 2 * 4 * (4096 + 256)
                                     + 5 * 64 * 64 * 128
                                     + 2 * 4096 * 4096) == 105940992


def test_experts_count_the_router_over_all_and_the_held_share():
    c = dict(granite_h(), num_local_experts=9, num_routed_experts=72)
    expert = 3 * 2 * 4096 * 768
    ffn = 2 * 4096 * 72 + 10 * 9 / 72 * expert + 3 * 2 * 4096 * 1536
    assert F.ffn_flops(c, 1.0) == ffn == 61931520
    # 18 Mamba2 and 2 attention layers, every one with its experts
    assert F.decode_token_flops(c, 1.0, 20, 512) == (
        18 * 209782784 + 2 * 92274688 + 20 * ffn + 2 * 4096 * 100352)
    # the exit after one period: 9 Mamba2 layers and 1 attention layer
    assert F.decode_token_flops(c, 1.0, 10, 512) == (
        9 * 209782784 + 92274688 + 10 * ffn + 2 * 4096 * 100352)


# -- the harness's set-up on the tiny hybrid ---------------------------------


def test_weights_warm_up_and_launches_in_layers_on_the_tiny_hybrid():
    from repro.models.model import init_params

    conf = tiny()
    cell = S.Cell(name="granite-4.0-h.tiny", config=conf,
                  traffic=tiny_traffic("chat"), chips=1, end_to_end=[],
                  per_layer=[])
    sv = run.Served(cell, seed=SEED, seconds=1.0, trace=False,
                    require_accelerator=False)
    assert sorted({(m.depth, m.width) for m in sv.modes}) == [
        (2, 1.0), (4, 0.5), (4, 1.0)]
    assert sv.n_warm == 2 * len(sv.traffic.prompt_shapes())
    cfg = S.program_config(conf)
    raw = jax.jit(init_params, static_argnums=1)(sv.ref.root_key(SEED), cfg)
    want = S.fold_multipliers(raw, conf)
    assert jax.tree_util.tree_structure(sv.params) == \
        jax.tree_util.tree_structure(want)
    # one jitted call fuses the fold into the draw: equal to f32 rounding
    for a, b in zip(jax.tree_util.tree_leaves(sv.params),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-7, atol=0)
    win = sv.driver.run(1.0, sv.request_cls, D.CompileCounter())
    assert win.compiles == 0
    launches = [l for t in win.ticks for l in t.launches]
    assert launches and {l.depth for l in launches} <= {4, 8}
    assert {r.depth for r in win.recs if r.admit_t is not None} <= {4, 8}
    full = F.decode_token_flops(conf, 1.0, 8, 64)
    assert F.decode_token_flops(conf, 1.0, 4, 64) < full
    sv.free()


def test_shrink_follows_a_checked_key_it_shrinks():
    c = granite_h()
    c["program"]["checks"] = {"moe_d_ff": "intermediate_size"}
    t = shrink(c)
    assert t["program"]["set"]["moe_d_ff"] == t["intermediate_size"] == 32
    c = copy.deepcopy(granite_h())
    c["num_routed_experts"] = 72
    t = shrink(c)
    assert (t["num_local_experts"], t["num_routed_experts"],
            t["shared_intermediate_size"]) == (4, 8, 48)
