"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

The device plane (``/device:TPU:0``) holds one line of XLA module
executions and one of XLA operations, each event with a start and a
duration in nanoseconds. The host plane holds the driver's
``TraceAnnotation`` spans: ``bench.tick`` (carrying the tick number),
``bench.submit``, ``bench.wait`` and ``bench.record``. The two share one
clock, and the ticks' numbers tie it to the driver's own clock.

From these it reads: the union of the device's operation intervals inside
the measured window (busy time), the device time of the decode programs
(the engine's per-depth ``step`` executables), the operations that took
most time, and the idle gaps by what the host was doing.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

# the engine's per-depth decode executables (``core/morph.py``: jit of
# ``step``), as the trace names them
DECODE_MODULE = re.compile(r"^jit_step\(")


@dataclass
class TraceSummary:
    t0: float  # measured window in the driver's clock
    t1: float
    window_s: float
    busy_s: float
    decode_s: float
    breakdown: Dict[str, list] = field(default_factory=dict)


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.duration_ns, e


def _stats(ev) -> Dict[str, object]:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def _union(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def _gaps(intervals, lo, hi):
    """Idle intervals between merged busy intervals within [lo, hi]."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def reduce_trace(trace_dir: str, win) -> TraceSummary:
    """Reduce the trace of ``win``'s run (see the module docstring)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    devices = [p for p in pd.planes if p.name.startswith("/device:")
               and "TPU" in p.name]
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    # tie the trace clock to the driver's clock through the tick spans
    ticks = {t.idx: t for t in win.ticks}
    offsets, spans = [], []
    for plane in host:
        for line in plane.lines:
            for name, s, d, ev in _events(line):
                if not name.startswith("bench."):
                    continue
                spans.append((s, s + d, name))
                if name == "bench.tick":
                    idx = _stats(ev).get("tick")
                    if idx is not None and int(idx) in ticks:
                        offsets.append(s * 1e-9 - ticks[int(idx)].t0)
    if not offsets or not devices:
        raise ValueError("trace holds no device plane or no tick spans")
    offsets.sort()
    off = offsets[len(offsets) // 2]
    t_lo = max(win.t_open, win.t_trace if win.t_trace is not None else win.t_open)
    lo, hi = (t_lo + off) * 1e9, (win.t_close + off) * 1e9

    ops, mods = [], []
    for plane in devices[:1]:  # the cell's chip
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(s, s + d, name, ev) for name, s, d, ev in _events(line)]
            elif line.name == "XLA Modules":
                mods = [(s, s + d, name) for name, s, d, _ in _events(line)]
    clip = [(max(a, lo), min(b, hi)) for a, b, *_ in ops if b > lo and a < hi]
    busy = _union(clip)
    dec = sorted((a, b) for a, b, n in mods if DECODE_MODULE.match(n))
    decode_s = sum(min(b, hi) - max(a, lo) for a, b in dec if b > lo and a < hi)

    per_op = defaultdict(float)
    for a, b, name, _ in ops:
        if b > lo and a < hi:
            per_op[_op_label(name)] += (min(b, hi) - max(a, lo)) * 1e-9
    spans.sort()
    span_start = [sp[0] for sp in spans]
    idle = defaultdict(float)
    for a, b in _gaps(clip, lo, hi):
        sp = _at(span_start, spans, (a + b) / 2)
        name = sp[2] if sp is not None and (a + b) / 2 <= sp[1] else "no host span"
        idle[name] += (b - a) * 1e-9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return TraceSummary(
        t0=t_lo, t1=win.t_close, window_s=(hi - lo) * 1e-9,
        busy_s=busy * 1e-9, decode_s=decode_s * 1e-9,
        breakdown={"device_ops": top(per_op), "idle_gaps": top(idle)})


def _at(starts, items, t):
    """The last item starting at or before ``t`` (``starts`` sorted)."""
    i = bisect.bisect_right(starts, t) - 1
    return items[i] if i >= 0 else None


def _op_label(hlo: str) -> str:
    """An operation's name in the trace is its HLO text
    (``%fusion.12 = bf16[...] fusion(...)``); the label is the instruction
    name without its instance number (``fusion``)."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name) or name
