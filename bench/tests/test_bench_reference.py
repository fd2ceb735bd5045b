"""The plain reference against the program at a small size on the CPU.

The reference takes nothing from the program, so these tests tie the two
together: the same weights from the same seed, and the same logits along
each path a served request takes (prefill at full width and the request's
depth, then decode at its width through the per-slot cache).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import spec as S
from bench.tests.tiny import tiny_config

# f32 program against the f32 reference: the two differ by accumulation
# order and by the program's bf16-free but differently fused arithmetic;
# a misplaced head, expert or position moves logits by O(1) relative
F32_REL = 2e-4
SEED = 2**31 + 17


def _setup(name):
    conf = tiny_config(name, embedding_multiplier=True)
    cfg = S.program_config(conf)
    ref = S.reference_module(conf)
    params = S.make_weights(conf, cfg, ref.root_key(SEED))
    return conf, cfg, ref, params


@pytest.mark.parametrize("name", ["granite-moe-1b-a400m", "phi3-medium-14b.8L"])
def test_reference_weights_are_the_programs(name):
    """The reference draws the weights of the program's initializer (the
    multipliers are not folded in: the reference applies them itself)."""
    from repro.models.model import init_params
    conf, cfg, ref, _ = _setup(name)
    params = jax.jit(init_params, static_argnums=1)(ref.root_key(SEED), cfg)
    top = ref.top_weights(conf, SEED)
    np.testing.assert_array_equal(top["embed"], params["embed"])
    if "unembed" in params:
        np.testing.assert_array_equal(top["unembed"], params["unembed"])
    keys = ref.layer_keys(conf, SEED)
    for layer in (0, conf["num_hidden_layers"] - 1):
        w = ref.layer_weights(conf, keys[layer])
        lp = jax.tree_util.tree_map(lambda a: a[layer], params["stack"])["pos0"]
        np.testing.assert_array_equal(w["wq"], lp["attn"]["wq"])
        np.testing.assert_array_equal(w["wo"], lp["attn"]["wo"])
        if "moe" in lp:
            np.testing.assert_array_equal(w["router"], lp["moe"]["router"])
            np.testing.assert_array_equal(w["wo_e"], lp["moe"]["wo"])
            np.testing.assert_array_equal(w["wg"], lp["moe"]["wg"])
        else:
            np.testing.assert_array_equal(w["wo_m"], lp["mlp"]["wo"])
            np.testing.assert_array_equal(w["wg"], lp["mlp"]["wg"])


@pytest.mark.parametrize("name", ["granite-moe-1b-a400m", "phi3-medium-14b.8L"])
@pytest.mark.parametrize("depth,width", [(4, 1.0), (4, 0.5), (2, 1.0)])
def test_prefill_then_decode_matches_reference(name, depth, width):
    """The engine's two steps on the path the harness drives: the per-slot
    prefill (full width, ``depth``) adopted into slot 1 of a 3-slot cache,
    then decode steps at ``width`` with per-slot active sizes."""
    from repro.core import elastic
    from repro.models.model import (adopt_cache_slot, decode_step,
                                    init_decode_cache, prefill)

    conf, cfg, ref, params = _setup(name)
    rng = np.random.default_rng(3)
    plen, n_dec, slot, B, cap = 13, 6, 1, 3, 32
    prompt = rng.integers(1, conf["vocab_size"], plen).astype(np.int32)
    fed = rng.integers(1, conf["vocab_size"], n_dec).astype(np.int32)
    lg, pre = prefill(params, {"tokens": jnp.asarray(prompt[None])}, cfg,
                      cache_extra=cap - plen, per_slot=True, slot=slot,
                      n_slots=B, depth=depth)
    cache = adopt_cache_slot(init_decode_cache(cfg, B, cap, per_slot=True),
                             pre, slot)
    got = [np.asarray(lg[0, 0])]
    active = elastic.active_widths_batch(cfg, [1.0, width, 0.5])
    for t in fed:
        toks = jnp.zeros((B, 1), jnp.int32).at[slot, 0].set(int(t))
        lg, cache = decode_step(params, cache, toks, cfg, depth=depth,
                                active=active)
        got.append(np.asarray(lg[slot, 0]))
    got = np.stack(got)[:, : conf["vocab_size"]]
    seq = {"tokens": list(prompt) + list(fed), "n_prompt": plen,
           "width": width, "depth": depth}
    want = ref.forward(conf, SEED, [seq])[0]
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert rel.max() < F32_REL, rel
