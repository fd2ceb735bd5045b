"""Drive ``ServingEngine`` with a traffic mix on the host's wall clock.

The window calls the engine's public entry points only: ``submit``,
``set_admission_mode`` and ``step`` (dense cache, unfused, greedy, no
speculation), on the default ``LocalExecutor`` for a cell on one chip and
on a ``MeshExecutor`` for one on several. Arrivals are due on the
wall clock whether or not the engine keeps up (open loop), or an offline
backlog is kept topped up. After every tick the driver reads the requests
it submitted to see which tokens appeared, so every token gets the time
the engine handed it out, and every decode launch of the tick its rows:
each occupied slot of a depth group yields one token per launch.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from bench.lib.traffic import Planned, Traffic

# wait for every request due in the window to get its first token for at
# most this long after the window closes; a request still without one fails
DRAIN_CAP_S = 60.0
# a backlog keeps this many requests queued per batch slot
BACKLOG_PER_SLOT = 2
# samples the engine's decode-step histogram keeps: every launch of a run
HIST_WINDOW = 1_000_000


@dataclass
class Rec:
    """What the driver saw of one request."""

    planned: Planned
    req: object  # the engine's Request
    due_t: float  # absolute perf_counter time it was due
    submit_t: float
    in_window: bool
    admit_t: Optional[float] = None  # start of the tick that admitted it
    depth: int = 0  # in layers
    width: float = 1.0
    token_t: List[float] = field(default_factory=list)


@dataclass
class Launch:
    """One decode launch: its depth group and occupied rows."""

    tick: int
    depth: int  # in layers
    rows: List[Tuple[float, int]]  # (width, keys in attention) per request


@dataclass
class Tick:
    idx: int
    t0: float
    t1: float
    launches: List[Launch]


@dataclass
class Window:
    """Everything one measured window produced, for the metric readers."""

    t_open: float
    seconds: float
    recs: List[Rec]
    ticks: List[Tick]
    counters: Dict[str, float]  # engine counter deltas over the window
    decode_step_ms: List[float]  # engine histogram samples in the window
    compiles: int  # compilations (traces, compiles, cache loads) in window
    config: Dict = field(default_factory=dict)
    device: Dict = field(default_factory=dict)
    trace: Optional[object] = None  # bench.lib.trace.TraceSummary
    peaks: Dict = field(default_factory=dict)
    setup_s: float = 0.0
    chips: int = 1  # the cell's chips: one SPMD program runs on each
    t_end: float = 0.0  # when the run stopped serving
    t_trace: Optional[float] = None  # when the profiler started, if it did

    @property
    def t_close(self) -> float:
        return self.t_open + self.seconds

    def in_window(self, t: float) -> bool:
        return self.t_open <= t < self.t_close

    def window_ticks(self) -> List[Tick]:
        return [t for t in self.ticks if self.in_window(t.t0)]


COUNTERS = ("prefills", "prefill_s", "prefill_prompt_tokens",
            "decode_launches")


def parse_mode(name: str, period: int) -> Tuple[int, float]:
    """(depth in layers, width) of a mode name ``d<depth>w<percent>``, whose
    depth counts the engine's groups of ``period`` layers."""
    d, w = name[1:].split("w")
    return int(d) * period, int(w) / 100.0


class CompileCounter:
    """Counts JAX compile events (tracing, lowering, backend compile or
    persistent-cache load) while armed."""

    def __init__(self):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.armed and event.startswith("/jax/core/compile/"):
            self.count += 1


class Driver:
    """Serves one traffic mix through one engine."""

    def __init__(self, engine, traffic: Traffic,
                 modes: Sequence, *, clock: Callable[[], float] = time.perf_counter,
                 annotate: Optional[Callable] = None):
        self.engine = engine
        self.traffic = traffic
        self.modes = list(modes)  # MorphMode per entry of the mix's schedule
        self.clock = clock
        self.annotate = annotate or (lambda name, **kw: contextlib.nullcontext())
        self._rid = 0

    # -- set-up ---------------------------------------------------------------

    def warm_up(self, request_cls) -> int:
        """Serve one two-token request per (prompt length, depth) the mix
        can send, so every prefill, decode, reset and adoption program it
        will run is compiled or loaded now. Returns requests served."""
        eng = self.engine
        rng = np.random.default_rng(0)
        by_depth = {}
        for i in self.traffic.mode_indices():
            by_depth.setdefault(self.modes[i].depth, self.modes[i])
        n = 0
        for mode in by_depth.values():
            eng.set_admission_mode(mode)
            for plen in self.traffic.prompt_shapes():
                eng.submit(request_cls(
                    rid=-1 - self._rid, prompt=tuple(int(t) for t in rng.integers(
                        1, self.traffic.vocab, plen)), max_new_tokens=2))
                self._rid += 1
                n += 1
                while eng.queue or eng.n_active:
                    eng.step()
        return n

    # -- the window -----------------------------------------------------------

    def run(self, seconds: float, request_cls, compiles: CompileCounter,
            tracer=None) -> Window:
        """Serve the mix for ``seconds``. ``tracer`` (with ``seconds``,
        ``start()`` and ``stop()``) profiles the last ``tracer.seconds`` of
        the window: started between two ticks, stopped once serving ends."""
        eng, tr = self.engine, self.traffic
        gen = tr.requests()
        nxt = next(gen)
        recs: List[Rec] = []
        live: List[Rec] = []
        ticks: List[Tick] = []
        pending_first = 0  # window requests still without a first token
        mode = None
        backlog = tr.kind == "backlog"
        refill = BACKLOG_PER_SLOT * eng.batch_size
        hist = eng.metrics.histogram("engine_decode_step_ms")
        c0 = {k: getattr(eng, k) for k in COUNTERS}
        h0 = hist.count
        compiles.count = 0
        compiles.armed = True
        t_open = self.clock()
        t_close = t_open + seconds
        c_close = h_close = None
        t_trace = None
        while True:
            now = self.clock()
            if tracer is not None and t_trace is None and \
                    now >= t_close - tracer.seconds:
                tracer.start()
                now = t_trace = self.clock()
            rel = now - t_open
            m = tr.mode_at(rel)
            if m != mode:
                eng.set_admission_mode(self.modes[m])
                mode = m
            with self.annotate("bench.submit"):
                while (len(eng.queue) < refill) if backlog else (
                        t_open + nxt.due_s <= now):
                    due = now if backlog else t_open + nxt.due_s
                    req = request_cls(rid=nxt.idx, prompt=nxt.prompt,
                                      max_new_tokens=nxt.max_new_tokens)
                    eng.submit(req)
                    rec = Rec(nxt, req, due, self.clock(), due < t_close)
                    recs.append(rec)
                    live.append(rec)
                    pending_first += rec.in_window
                    nxt = next(gen)
            if now >= t_close:
                if c_close is None:  # counters as the window closed
                    compiles.armed = False
                    c_close = {k: getattr(eng, k) for k in COUNTERS}
                    h_close = hist.count
                if backlog or pending_first == 0 or now >= t_close + DRAIN_CAP_S:
                    break
            if not (eng.queue or eng.n_active):
                wake = min(t_open + nxt.due_s,
                           t_open + (int(rel // tr.every_s) + 1) * tr.every_s)
                with self.annotate("bench.wait"):
                    time.sleep(max(0.0, min(wake, t_close) - self.clock())
                               if now < t_close else 0.0005)
                continue
            t0 = self.clock()
            with self.annotate("bench.tick", tick=len(ticks)):
                eng.step()
            t1 = self.clock()
            with self.annotate("bench.record"):
                launches: Dict[int, Launch] = {}
                still = []
                for rec in live:
                    r = rec.req
                    if rec.admit_t is None and r.status != "queued":
                        rec.admit_t = t0
                        rec.depth, rec.width = parse_mode(r.mode_name,
                                                          eng.cfg.period)
                    new = len(r.generated) - len(rec.token_t)
                    if new:
                        first = not rec.token_t
                        rec.token_t.extend([t1] * new)
                        if first and rec.in_window:
                            pending_first -= 1
                        n_decode = new - 1 if first else new
                        if n_decode:
                            # the launch fed token j-1 at position plen+j-1
                            j = len(r.generated) - 1
                            lau = launches.setdefault(
                                rec.depth, Launch(len(ticks), rec.depth, []))
                            lau.rows.append((rec.width, len(r.prompt) + j))
                    if r.status not in ("done", "expired"):
                        still.append(rec)
                live = still
                ticks.append(Tick(len(ticks), t0, t1, list(launches.values())))
        t_end = self.clock()
        if t_trace is not None:
            tracer.stop()
        c1 = c_close or {k: getattr(eng, k) for k in COUNTERS}
        # the histogram keeps every sample (see HIST_WINDOW), in order
        samples = hist.state_dict()["window"][h0:h_close]
        return Window(t_open=t_open, seconds=seconds, recs=recs, ticks=ticks,
                      counters={k: c1[k] - c0[k] for k in COUNTERS},
                      decode_step_ms=list(samples), compiles=compiles.count,
                      t_end=t_end, t_trace=t_trace)
