"""The readers of the engine's phase spans: on synthetic spans and device
modules, on the older recorded trace that holds no engine span, and on a
small trace recorded on a TPU v5e with the spans in place.

``engine_data/engine_trace.xplane.pb`` (``record_engine_trace.py``): five
ticks of the tiny granite-moe chat cell (``bench.tick`` spans, ticks 0-4);
ticks 0 and 2 admit requests (two prefills, then one), every tick runs one
decode launch of the full-depth group. It lies apart from ``data/``, whose
reader takes the newest trace there.
"""
from __future__ import annotations

import os

import pytest

from bench.lib import engine_spans as E
from bench.lib import spec as S
from bench.lib.driver import Tick, Window
from bench.lib.trace import TraceSummary

HERE = os.path.dirname(__file__)
OLD = os.path.join(HERE, "data")  # driver spans only: what an older program leaves
NEW = os.path.join(HERE, "engine_data")
MS = 1e6  # ns


def _sp(name, a, b, **args):
    return E.Span(name, a * MS, b * MS, args)


def _trace(spans, modules=(), ops=(), lo=0.0, hi=300.0):
    ticks = [s for s in spans if s.name == "bench.tick"]
    return E.EngineTrace(lo=lo * MS, hi=hi * MS,
                         spans=sorted(spans, key=lambda s: (s.t0, -s.t1)),
                         ticks=ticks,
                         modules=sorted((a * MS, b * MS, n) for a, b, n in modules),
                         ops=[(a * MS, b * MS) for a, b in ops])


def _two_ticks():
    spans = [
        _sp("bench.tick", 0, 100, tick=0),
        _sp("engine.step", 1, 99, tick=0, rows=2),
        _sp("engine.admit", 2, 9, admitted=1),
        _sp("engine.prefill", 3, 8, plen=64, depth=24),
        _sp("engine.decode", 10, 60, depth=24, rows=2, w_max=1.0),
        _sp("engine.decode", 62, 98, depth=12, rows=1, w_max=1.0),
        _sp("bench.tick", 110, 200, tick=1),
        _sp("engine.step", 111, 199, tick=1, rows=2),
        _sp("engine.decode", 120, 170, depth=24, rows=2, w_max=0.5),
        # outside every tick of the window: not read
        _sp("engine.decode", 250, 290, depth=24, rows=2, w_max=1.0),
    ]
    modules = [(4, 7, "jit_pf(abc)"), (20, 50, "jit_step(abc)"),
               (12, 15, "jit_argmax(abc)"), (70, 90, "jit_step(def)"),
               # starts inside the span, ends after it: counted whole
               (150, 190, "jit_step(abc)"), (260, 280, "jit_step(abc)")]
    return _trace(spans, modules)


def test_decode_device_and_host_from_modules_starting_in_the_span():
    et = _two_ticks()
    got = [(s.t0 / MS, dev / MS, n) for s, dev, n in E.decode_launches(et)]
    assert got == [(10, 30, 1), (62, 20, 1), (120, 40, 1)]
    assert E.clock_lag_ns(et) == 0.0  # no device span to bound it
    assert E.decode_device_ms(et) == pytest.approx(30.0)
    # span minus device: 20, 16, 10
    assert E.decode_host_ms(et) == pytest.approx(46.0 / 3)


def test_device_clock_lag_is_the_tightest_bound_of_the_launches():
    """The device plane runs 2 ms behind: a module that seems to start
    before its decode span is paired once shifted, and the idle time moves
    with it."""
    spans = [_sp("bench.tick", 0, 100, tick=0),
             _sp("engine.step", 1, 99, tick=0, rows=1),
             _sp("engine.decode", 10, 40, depth=24, rows=1, w_max=1.0),
             _sp("engine.device", 12, 30),
             _sp("engine.decode", 50, 90, depth=12, rows=1, w_max=1.0),
             _sp("engine.device", 55, 80)]
    # device clock: the first module ends 2 ms before its block returns
    # (the lag alone), the second 3 ms before (lag and 1 ms to notice)
    modules = [(9, 28, "jit_step(a)"), (60, 77, "jit_step(b)")]
    et = _trace(spans, modules, ops=[(9, 28), (60, 77)], hi=100)
    assert E.clock_lag_ns(et) == pytest.approx(2 * MS)
    got = [(s.t0 / MS, dev / MS, n) for s, dev, n in E.decode_launches(et)]
    assert got == [(10, 19, 1), (50, 17, 1)]
    split = E.idle_split(et)
    # on the host's clock the device runs 11-30 and 62-79
    assert split["engine.device"] == pytest.approx((7 + 1) * 1e-3)


def test_prefill_stall_is_a_mean_over_ticks_that_ran_a_prefill():
    et = _two_ticks()
    assert E.prefill_stall_ms(et) == pytest.approx(5.0)
    et.spans.append(_sp("engine.prefill", 112, 115, plen=128, depth=24))
    et.spans.append(_sp("engine.prefill", 115, 119, plen=128, depth=24))
    # tick 0: 5 ms; tick 1: 3 + 4 ms; a tick without one does not count
    assert E.prefill_stall_ms(et) == pytest.approx(6.0)


def test_no_engine_spans_reads_none():
    et = _trace([_sp("bench.tick", 0, 100, tick=0)])
    assert E.decode_device_ms(et) is None and E.decode_host_ms(et) is None
    assert E.prefill_stall_ms(et) is None
    for f in (E.decode_device_ms, E.decode_host_ms, E.prefill_stall_ms):
        assert f(None) is None


def test_idle_goes_to_the_innermost_open_span():
    spans = [_sp("bench.tick", 0, 100, tick=0),
             _sp("engine.step", 1, 99, tick=0, rows=1),
             _sp("engine.decode", 10, 90, depth=24, rows=1, w_max=1.0),
             _sp("engine.inputs", 10, 20),
             _sp("engine.device", 20, 60),
             _sp("engine.readback", 60, 70),
             _sp("engine.commit", 70, 85)]
    ops = [(22, 58), (62, 64)]
    split = E.idle_split(_trace(spans, ops=ops, lo=0, hi=120))
    want = {"bench.tick": 1 + 1, "engine.step": 9 + 9, "engine.inputs": 10,
            "engine.device": 2 + 2, "engine.readback": 2 + 6,
            "engine.commit": 15, "engine.decode": 5, "no host span": 20}
    assert split.keys() == want.keys()
    for k, v in want.items():
        assert split[k] == pytest.approx(v * 1e-3), k
    assert sum(split.values()) == pytest.approx(0.120 - 0.036 - 0.002)


def _window(trace_dir):
    """A window over every ``bench.tick`` of the trace in ``trace_dir``, on
    the trace's own clock."""
    from jax.profiler import ProfileData

    from bench.lib.trace import find_xplane
    ticks = []
    for plane in ProfileData.from_file(find_xplane(trace_dir)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench.tick":
                    t = int(dict(e.stats)["tick"])
                    ticks.append(Tick(t, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9, []))
    ticks.sort(key=lambda t: t.idx)
    t0, t1 = ticks[0].t0 - 1e-3, ticks[-1].t1 + 1e-3
    win = Window(t_open=t0, seconds=t1 - t0, recs=[], ticks=ticks,
                 counters={}, decode_step_ms=[], compiles=0)
    win.trace = TraceSummary(t0=t0, t1=t1, window_s=t1 - t0, busy_s=0.0,
                             decode_s=0.0)
    return win


def test_older_program_trace_reads_none(monkeypatch):
    """The recorded trace of the driver's spans alone: every reader gives
    ``None``, as on a program without engine spans."""
    from bench import run

    assert E.load(_window(OLD), OLD) is None
    monkeypatch.setattr(run, "TRACE_DIR", OLD)  # where the readers look
    for name in ("decode_device_ms.itl", "decode_host_ms.itl",
                 "prefill_stall_ms.itl"):
        assert S.load_reader(name)(_window(OLD)) is None


@pytest.fixture(scope="module")
def recorded():
    win = _window(NEW)
    et = E.load(win, NEW)
    assert et is not None
    return win, et


def test_recorded_trace_pairs_each_decode_with_one_step(recorded):
    win, et = recorded
    assert [t.idx for t in win.ticks] == [0, 1, 2, 3, 4]
    launches = E.decode_launches(et)
    assert len(launches) == 5
    for s, dev, n in launches:
        assert n == 1 and 0 < dev < s.t1 - s.t0
        assert {"depth", "rows", "w_max"} <= set(s.args)
    dev, host = E.decode_device_ms(et), E.decode_host_ms(et)
    assert dev > 0 and host > 0
    # the device phase holds the launch's dispatch and its block: it ends
    # after the module, and a decode span holds one of each phase
    for s, _, _ in launches:
        kids = [k.name for k in et.spans
                if s.t0 <= k.t0 and k.t1 <= s.t1 and k is not s]
        assert sorted(kids) == sorted(E.PHASES[2:6]), kids


def test_recorded_trace_prefill_stall_and_idle_split(recorded):
    win, et = recorded
    prefills = et.engine("engine.prefill")
    assert [int(p.args["plen"]) for p in prefills] == [16, 32, 16]
    ticks_with = {0: prefills[0].t1 - prefills[0].t0 + prefills[1].t1
                  - prefills[1].t0, 2: prefills[2].t1 - prefills[2].t0}
    assert E.prefill_stall_ms(et) == pytest.approx(
        sum(ticks_with.values()) / 2 * 1e-6)
    split = E.idle_split(et)
    in_step = {k: v for k, v in split.items() if k.startswith("engine.")}
    assert in_step and sum(split.values()) < (et.hi - et.lo) * 1e-9
    # the engine's phases hold most of the idle time inside its ticks
    phases = sum(v for k, v in in_step.items() if k in E.PHASES)
    assert phases >= 0.9 * sum(in_step.values()), split
