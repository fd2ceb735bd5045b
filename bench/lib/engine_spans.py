"""Read the serving engine's own phase spans from a ``--trace 1`` run.

The engine marks its phases with ``jax.profiler.TraceAnnotation`` spans
(``TraceRecorder.span`` in ``repro/runtime/observability.py``):
``engine.step`` per tick and, inside it, ``engine.admit``,
``engine.prefill`` (launch and first-token readback), ``engine.decode``
per depth group's launch with its phases ``engine.inputs``,
``engine.device`` (dispatch and ``block_until_ready``),
``engine.readback`` and ``engine.commit``, and ``engine.build`` on a cache
miss; ``host.gc`` marks a garbage collection. The spans lie on the host
plane of the trace that ``bench/run.py`` writes. The device plane's clock
runs behind the host plane's by about a millisecond on a TPU v5e; the
launches bound the lag (a decode module ends before the host's block on it
returns), and the readers shift the device's timeline by it. A decode
span's device time is then that of the decode programs (``jit_step``) that
start inside it.

Only engine spans inside a ``bench.tick`` span of a tick that began in the
traced window count. A program without these spans gives ``None``.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench.lib.trace import (DECODE_MODULE, _events, _gaps, _stats,
                              device_planes, find_xplane, is_collective)

# the spans a launch's time splits into, and the phases of an engine tick
# that hold no other span
PHASES = ("engine.admit", "engine.prefill", "engine.inputs", "engine.device",
          "engine.readback", "engine.commit", "engine.build")


@dataclass
class Span:
    name: str
    t0: float  # ns, the trace's clock
    t1: float
    args: Dict[str, object] = field(default_factory=dict)


@dataclass
class EngineTrace:
    """The traced window ``[lo, hi)`` (ns): its host spans (the driver's
    ``bench.*``, the engine's ``engine.*``, ``host.gc``) sorted by start,
    the ``bench.tick`` spans of its ticks, and chip 0's module executions,
    operation intervals and collective operations' intervals."""

    lo: float
    hi: float
    spans: List[Span]
    ticks: List[Span]
    modules: List[Tuple[float, float, str]] = field(default_factory=list)
    ops: List[Tuple[float, float]] = field(default_factory=list)
    collectives: List[Tuple[float, float]] = field(default_factory=list)

    def engine(self, name: str) -> List[Span]:
        """The ``name`` spans inside a tick of the window."""
        return _within([s for s in self.spans if s.name == name], self.ticks)


def load(win, trace_dir: Optional[str] = None) -> Optional[EngineTrace]:
    """The engine spans of ``win``'s traced window, read once per window
    from the profile in ``trace_dir`` (the one ``bench/run.py`` writes);
    ``None`` if the run was not traced or the trace holds no engine span."""
    if win.trace is None:
        return None
    if trace_dir is None:
        from bench.run import TRACE_DIR
        trace_dir = TRACE_DIR
    cached = getattr(win, "_engine_trace", None)
    if cached is None or cached[0] != trace_dir:
        cached = (trace_dir, read_xplane(find_xplane(trace_dir), win))
        win._engine_trace = cached
    return cached[1]


def read_xplane(path: str, win) -> Optional[EngineTrace]:
    from jax.profiler import ProfileData

    tr = win.trace
    ticks = {t.idx: t for t in win.ticks if tr.t0 <= t.t0 < tr.t1}
    pd = ProfileData.from_file(path)
    spans, kept, offsets = [], [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, d, ev in _events(line):
                if not name.startswith(("engine.", "host.", "bench.")):
                    continue
                sp = Span(name, s, s + d, _stats(ev))
                spans.append(sp)
                if name == "bench.tick" and int(sp.args.get("tick", -1)) in ticks:
                    kept.append(sp)
                    offsets.append(s * 1e-9 - ticks[int(sp.args["tick"])].t0)
    if not kept or not any(s.name == "engine.step" for s in spans):
        return None
    offsets.sort()
    off = offsets[len(offsets) // 2]  # the driver's clock to the trace's
    lo, hi = (tr.t0 + off) * 1e9, (tr.t1 + off) * 1e9
    modules, ops, collectives = [], [], []
    for plane in device_planes(pd):
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules = [(s, s + d, n) for n, s, d, _ in _events(line)]
            elif line.name == "XLA Ops":
                ops = [(s, s + d) for _, s, d, _ in _events(line)]
                collectives = [(s, s + d) for n, s, d, _ in _events(line)
                               if is_collective(n)]
    return EngineTrace(
        lo=lo, hi=hi,
        spans=sorted((s for s in spans if s.t1 > lo and s.t0 < hi),
                     key=lambda s: (s.t0, -s.t1)),
        ticks=sorted(kept, key=lambda s: s.t0), modules=sorted(modules),
        ops=ops, collectives=sorted(collectives))


def _decode_modules(et: EngineTrace) -> List[Tuple[float, float, str]]:
    return [m for m in et.modules if DECODE_MODULE.match(m[2])]


def _within(spans: List[Span], outer: List[Span]) -> List[Span]:
    starts = [o.t0 for o in outer]
    out = []
    for s in spans:
        i = bisect.bisect_right(starts, s.t0) - 1
        if i >= 0 and s.t1 <= outer[i].t1:
            out.append(s)
    return out


def clock_lag_ns(et: EngineTrace) -> float:
    """How far the device plane's clock runs behind the host plane's (ns).

    A decode launch's ``engine.device`` span ends when the host's block on
    the module returns, after the module ends: each launch bounds the lag
    from above by its span's end minus the end of its module, the last
    decode module to end before the span does on the device's clock (so
    for a lag of at least 0 and under the time from one launch's return to
    the next module's end). The lag is the tightest of these bounds (0
    without a launch to bound it)."""
    ends = sorted(m[1] for m in _decode_modules(et))
    bounds = []
    for s in _within(et.engine("engine.device"), et.engine("engine.decode")):
        i = bisect.bisect_right(ends, s.t1) - 1
        if i >= 0:
            bounds.append(s.t1 - ends[i])
    return min(bounds) if bounds else 0.0


def _paired(et: EngineTrace, lag: float):
    """Each ``engine.decode`` span with the decode module executions that
    start inside it, shifted by ``lag`` onto the host's clock."""
    mods = [(a + lag, b + lag, n) for a, b, n in _decode_modules(et)]
    starts = [m[0] for m in mods]
    return [(s, mods[bisect.bisect_left(starts, s.t0):
                     bisect.bisect_left(starts, s.t1)])
            for s in et.engine("engine.decode")]


def decode_launches(et: EngineTrace) -> List[Tuple[Span, float, int]]:
    """Each ``engine.decode`` span with the device time (ns) and number of
    the decode module executions that start inside it, on the host's
    clock."""
    return [(s, sum(b - a for a, b, _ in hit), len(hit))
            for s, hit in _paired(et, clock_lag_ns(et))]


def decode_device_ms(et: Optional[EngineTrace]) -> Optional[float]:
    """Mean over decode launches of their decode modules' device time. A
    mean, as the depth groups' launches take two sizes of time in turn,
    and a median of such a mix falls between them or flips from one to the
    other."""
    if et is None:
        return None
    launches = decode_launches(et)
    return (sum(dev for _, dev, _ in launches) / len(launches) * 1e-6
            if launches else None)


def decode_host_ms(et: Optional[EngineTrace]) -> Optional[float]:
    """Mean over decode launches of the span's time the device spent on no
    decode module: the host's part of a launch (with the device's part, the
    mean span)."""
    if et is None:
        return None
    launches = decode_launches(et)
    return (sum(s.t1 - s.t0 - dev for s, dev, _ in launches) / len(launches)
            * 1e-6 if launches else None)


def decode_collective_ms(et: Optional[EngineTrace]) -> Optional[float]:
    """Mean over decode launches of the device time of the collective
    operations that start inside their decode modules, on chip 0 (every
    chip runs the same SPMD module). ``None`` where chip 0 ran no
    collective: a cell on one chip has none to read."""
    if et is None or not et.collectives:
        return None
    lag = clock_lag_ns(et)
    coll = [(a + lag, b + lag) for a, b in et.collectives]
    starts = [c[0] for c in coll]
    per_launch = [
        sum(d - c for a, b, _ in hit
            for c, d in coll[bisect.bisect_left(starts, a):
                             bisect.bisect_left(starts, b)])
        for _, hit in _paired(et, lag)]
    return (sum(per_launch) / len(per_launch) * 1e-6 if per_launch
            else None)


def prefill_stall_ms(et: Optional[EngineTrace]) -> Optional[float]:
    """Mean over the ticks that ran a prefill of their time in prefills."""
    if et is None:
        return None
    starts = [t.t0 for t in et.ticks]
    per_tick = defaultdict(float)
    for s in et.engine("engine.prefill"):
        per_tick[bisect.bisect_right(starts, s.t0) - 1] += (s.t1 - s.t0) * 1e-6
    return sum(per_tick.values()) / len(per_tick) if per_tick else None


def idle_split(et: EngineTrace) -> Dict[str, float]:
    """Device-idle time (s) in the traced window, on the host's clock, by
    the innermost host span around it (the latest-starting open span);
    ``no host span`` where none is open."""
    lag = clock_lag_ns(et)
    clip = [(max(a + lag, et.lo), min(b + lag, et.hi)) for a, b in et.ops
            if b + lag > et.lo and a + lag < et.hi]
    gaps = _gaps(clip, et.lo, et.hi)
    events = sorted([(s.t0, 1, i) for i, s in enumerate(et.spans)] +
                    [(s.t1, 0, i) for i, s in enumerate(et.spans)])
    out: Dict[str, float] = defaultdict(float)
    open_: Dict[int, Span] = {}
    gi, prev = 0, et.lo
    for t, opens, i in events + [(et.hi, 0, -1)]:
        t = min(max(t, et.lo), et.hi)
        if t > prev:
            while gi < len(gaps) and gaps[gi][1] <= prev:
                gi += 1
            idle, j = 0.0, gi
            while j < len(gaps) and gaps[j][0] < t:
                idle += min(t, gaps[j][1]) - max(prev, gaps[j][0])
                j += 1
            if idle:
                inner = max(open_.values(), key=lambda s: (s.t0, -s.t1),
                            default=None)
                out[inner.name if inner else "no host span"] += idle * 1e-9
            prev = t
        if i < 0:
            continue
        if opens:
            open_[i] = et.spans[i]
        else:
            open_.pop(i, None)
    return dict(out)
