"""The control at a test size: the plain reference in float8 (e4m3, one
scale per tensor), put in the program's place, must not come out correct,
while the program itself does. On the chip the same comparison at each
cell's own size set the limits (``bench/control.py``, readings in PERF.md);
here the program runs in f32, whose limit the tiny configuration states."""
from __future__ import annotations

import pytest

from bench import run
from bench.tests.tiny import tiny_cell


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
@pytest.mark.parametrize("cell", [("granite-moe-1b-a400m", "chat"),
                                  ("phi3-medium-14b.8L", "batch")],
                         ids=["granite-moe", "phi3-medium"])
def test_control_fails_where_the_program_passes(cell, seed):
    res = run.run_cell(tiny_cell(*cell), seed=seed, seconds=1.5, trace=False,
                       require_accelerator=False, control=True)
    gap = res["compared"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"] and res["correct"]
    # judged by the same comparison and limit, the control is not correct
    ctl = res["control"]
    assert ctl["correct"] is False, ctl
    assert ctl["compared"]["max_logit_gap"]["limit"] == gap["limit"]
