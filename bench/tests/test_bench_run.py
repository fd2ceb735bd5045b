"""A whole run of each cell at a small size on the CPU: the harness path
(weights, engine, warm-up, window, reference check) with the chip check
skipped. A sound program comes out ``correct``; with the timed path broken
underneath, every fault a served cell can have turns ``correct`` false."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.tiny import tiny_cell

CELLS = [("granite-moe-1b-a400m", "chat"), ("phi3-medium-14b.8L", "batch"),
         ("granite-moe-1b-a400m", "longprompt")]
SEED = 2**31 + 5


def _run(cell, seconds=1.5):
    return run.run_cell(tiny_cell(*cell), seed=SEED, seconds=seconds,
                        trace=False, require_accelerator=False)


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c[1])
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    names = set(res["metrics"])
    assert "setup_s" in names and len(names) >= 2


def _state_unchanged(real):
    """The decode step hands back the cache it was given."""
    def step(params, cache, tokens, cfg, **kw):
        logits, _ = real(params, cache, tokens, cfg, **kw)
        return logits, cache
    return step


def _token_altered(real):
    """The decode step's logits favour one token: the token produced is
    altered where it is produced."""
    def step(params, cache, tokens, cfg, **kw):
        logits, new = real(params, cache, tokens, cfg, **kw)
        return logits.at[..., 7].add(jnp.asarray(1e3, logits.dtype)), new
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
@pytest.mark.parametrize("cell", CELLS[:2], ids=lambda c: c[1])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    from repro.core import morph
    monkeypatch.setattr(morph, "decode_step", fault(morph.decode_step))
    res = _run(cell)
    assert res["correct"] is False, res["compared"]
