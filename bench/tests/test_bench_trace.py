"""The trace reduction on a small trace recorded on a TPU v5e.

``data/decode_trace.xplane.pb``: three driver ticks (``bench.tick``
spans, ticks 0-2), each running one jitted ``step`` that calls the Pallas
``morph_matmul`` once (x (4, 1, 256) bf16 @ w (256, 384) bf16) and one
other program (a 512x512 f32 matmul and tanh), 10 ms apart.
"""
from __future__ import annotations

import os

import pytest

from bench.lib import readings as R
from bench.lib.driver import Launch, Tick, Window
from bench.lib.trace import find_xplane, reduce_trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _tick_spans():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(DATA))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench.tick":
                    out.append((dict(e.stats)["tick"], e.start_ns, e.duration_ns))
    return sorted(out)


@pytest.fixture(scope="module")
def window():
    spans = _tick_spans()
    assert [t for t, _, _ in spans] == [0, 1, 2]
    ticks = [Tick(int(t), s * 1e-9, (s + d) * 1e-9,
                  [Launch(int(t), 4, [(1.0, 9), (0.5, 17)])])
             for t, s, d in spans]
    t_open = ticks[0].t0 - 1e-3
    seconds = ticks[-1].t1 + 1e-3 - t_open
    return Window(t_open=t_open, seconds=seconds, recs=[], ticks=ticks,
                  counters={}, decode_step_ms=[], compiles=0)


def test_busy_idle_and_decode_time(window):
    tr = reduce_trace(DATA, window)
    assert tr.window_s == pytest.approx(window.seconds, rel=1e-9)
    assert 0 < tr.decode_s <= tr.busy_s < tr.window_s
    window.trace = tr
    idle = R.device_idle(window)
    assert 0 < idle < 100
    # most idle time is the 10 ms pause between ticks, outside any tick span
    names = [n for n, _ in tr.breakdown["idle_gaps"]]
    assert names[0] == "no host span"
    assert tr.breakdown["device_ops"] and len(tr.breakdown["device_ops"]) <= 10
