"""BENCHMARK.json and the files it names: found by name, consistent, and
the traffic generator deterministic for a seed. No accelerator needed."""
from __future__ import annotations

import os
import re
from collections import Counter

import numpy as np
import pytest

from bench.lib import spec as S
from bench.lib.traffic import Traffic, _deal, _strata_sizes

BM = S.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_names_and_keys():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BM[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m["unit"]
    setup = [m for m in BM["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    c = S.load_cell(cell)
    conf = next(x for x in BM["configs"] if x["name"] == c.config["name"])
    assert os.path.isfile(os.path.join(S.ROOT, conf["file"]))
    assert conf["source"] == c.config["source"]
    for m in c.end_to_end + c.per_layer:
        assert callable(S.load_reader(m["name"]))
    assert callable(S.reference_module(c.config).forward)
    # every depth the mix names exists in the configuration
    for depth, width in S.mode_table(c.config, c.traffic):
        assert 0 < depth <= c.config["num_hidden_layers"]
        assert width in c.config["serving"]["width_fractions"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_its_metrics_move(cell):
    c = S.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


CONFIG_FILES = sorted(os.path.splitext(f)[0] for f in
                      os.listdir(os.path.join(S.BENCH_DIR, "configs")))


@pytest.mark.parametrize("config", CONFIG_FILES)
def test_program_config_matches_file(config):
    cfg = S.program_config(S.load_config(config))
    assert cfg.padded_vocab() == S.load_config(config)["serving"]["padded_vocab"]


def _take(tr, n):
    it = tr.requests()
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("mix", ["chat", "chat-tp4", "batch", "longprompt"])
def test_traffic_deterministic_and_same_work_for_every_seed(mix):
    t = S.load_traffic(mix)
    a = Traffic(t, seed=2**31 + 11, seconds=32, vocab=1000)
    b = Traffic(t, seed=2**31 + 11, seconds=32, vocab=1000)
    c = Traffic(t, seed=5, seconds=32, vocab=1000)
    n = a.cycle_n
    ra, rb, rc = _take(a, n), _take(b, n), _take(c, n)
    assert ra == rb
    assert [a.mode_at(s) for s in range(64)] == [b.mode_at(s) for s in range(64)]
    # another seed: the same sizes, gaps and mode slots, in another order
    lens = lambda rs: (sorted(len(r.prompt) for r in rs),
                       sorted(r.max_new_tokens for r in rs))
    assert lens(ra) == lens(rc)
    assert ra != rc
    if a.kind == "poisson":
        assert all(r.due_s < 32 for r in ra)
        ends = lambda rs: [r.due_s for r in rs] + [32.0]
        gaps = lambda rs: sorted(y - x for x, y in zip(ends(rs), ends(rs)[1:]))
        assert gaps(ra) == pytest.approx(gaps(rc), abs=1e-9)
    assert Counter(a.mode_at(2 * k) for k in range(16)) == \
        Counter(c.mode_at(2 * k) for k in range(16))
    # every prompt length is on the ladder: the shapes warm-up compiles
    ladder = set(t["prompt"]["ladder"])
    assert {len(r.prompt) for r in ra} <= ladder
    assert set(a.prompt_shapes()) <= ladder


@pytest.mark.parametrize("n,per", [(102, 10), (100, 10), (7, 10), (256, 10)])
def test_every_stratum_takes_one_value_of_every_band(n, per):
    rng = np.random.default_rng(2**31 + 3)
    sizes = _strata_sizes(n, per, rng)
    assert sum(sizes) == n and max(sizes) - min(sizes) <= 1
    k = len(sizes)
    out = _deal(rng.permutation(n), sizes, rng)  # (n-1-value)//k: its band
    assert sorted(out) == list(range(n))
    at, sums = 0, []
    for size in sizes:
        assert sorted((n - 1 - out[at:at + size]) // k) == list(range(size))
        sums.append(out[at:at + size].sum())
        at += size
    assert max(sums) - min(sums) <= 2 * k  # about the same sum each


def test_chat_work_is_spread_evenly_over_the_window():
    """Each third of the window is due about a third of the prompt and of
    the output tokens, whatever the seed (without strata some seeds put
    under a fifth of either into one third); each 8 s of the schedule holds
    its shares exactly."""
    t = S.load_traffic("chat")
    for seed in list(range(40)) + [2**31 + 11, 2**40 + 1]:
        tr = Traffic(t, seed=seed, seconds=51, vocab=1000)
        reqs = _take(tr, tr.cycle_n)
        for size in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
            third = sum(size(r) for r in reqs) / 3
            for f in range(3):
                part = sum(size(r) for r in reqs
                           if f * 17 <= r.due_s < (f + 1) * 17)
                assert abs(part / third - 1) < 0.4, (seed, f)
        for b in range(6):
            assert Counter(tr.mode_at(8 * b + 2 * k) for k in range(4)) == \
                Counter({0: 2, 1: 1, 2: 1})
