"""Unified observability layer: registry primitives (exact percentiles vs a
numpy reference, Prometheus/JSON export shapes, bounded event streams with
legacy tuple views), trace-recorder schema (matched B/E duration pairs,
request async spans nesting launch spans), disabled-mode no-op on the tick
path, snapshot/restore carrying the full metrics state, SLO catch-up after
failover, and the chaos-scenario acceptance check: a failover run under
tracing exports Chrome trace JSON whose launch spans account for every
committed token."""
import gc
import glob
import json
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import smoke_config
from repro.models.model import init_params
from repro.models.paged import PagedLayout
from repro.runtime.fault_tolerance import ExecutorSupervisor, FailurePlan
from repro.runtime.observability import (DEFAULT_LATENCY_BUCKETS_MS,
                                         EventStream, Histogram,
                                         MetricsRegistry, Observability,
                                         TraceRecorder, _TupleView,
                                         gc_pauses)
from repro.runtime.serving import (Request, ServingEngine, SLOPolicy,
                                   poisson_trace)
from repro.runtime.speculative import SpecConfig

CFG = smoke_config("tinyllama-1.1b")
PARAMS = init_params(jax.random.PRNGKey(0), CFG)


def _factory(obs=None, paged=None, speculative=None):
    def make():
        eng = ServingEngine(PARAMS, CFG, batch_size=3, cache_capacity=32,
                            prefill_threshold=4, speculative=speculative,
                            paged=paged, observability=obs)
        eng.warmup()
        return eng
    return make


def _trace(n=10, seed=5):
    # rate 1e6 collapses all arrivals to t~0 so the tick schedule is
    # latency-independent (same trick as the chaos suite)
    return poisson_trace(n, rate_per_s=1e6, seed=seed, vocab=CFG.vocab_size,
                         prompt_len=(1, 9), interactive_frac=0.3)


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------


def test_histogram_percentiles_match_numpy():
    """Window percentiles are the exact inverted-CDF order statistics."""
    rng = np.random.default_rng(0)
    h = Histogram("t", window=512)
    vals = rng.lognormal(0.0, 1.5, size=1000) * 10.0
    for v in vals:
        h.observe(float(v))
    ref = vals[-512:]  # FIFO eviction keeps the most recent `window` samples
    for q in (0.5, 0.9, 0.95, 0.99):
        want = float(np.quantile(ref, q, method="inverted_cdf"))
        assert h.quantile(q) == pytest.approx(want), q
    assert h.p50 == h.quantile(0.5)
    assert h.count == 1000
    assert h.sum == pytest.approx(vals.sum())


def test_histogram_buckets_and_prometheus_export():
    reg = MetricsRegistry()
    h = reg.histogram("step_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 0.5, 5.0, 50.0, 5000.0):
        h.observe(v)
    # raw per-bucket counts: <=1, <=10, <=100, +Inf
    assert h.bucket_counts == [2, 1, 1, 1]
    reg.counter("launches").add(3)
    reg.gauge("occ").set(0.75)
    text = reg.prometheus_text()
    assert "# TYPE launches counter\nlaunches 3" in text
    assert "# TYPE occ gauge\nocc 0.75" in text
    # exposition buckets are CUMULATIVE and end at +Inf == count
    assert 'step_ms_bucket{le="1.0"} 2' in text
    assert 'step_ms_bucket{le="10.0"} 3' in text
    assert 'step_ms_bucket{le="100.0"} 4' in text
    assert 'step_ms_bucket{le="+Inf"} 5' in text
    assert "step_ms_count 5" in text


def test_counter_stays_int_and_get_or_create_identity():
    reg = MetricsRegistry()
    c = reg.counter("n")
    c.add()
    c.add(2)
    assert c.value == 3 and isinstance(c.value, int)
    assert reg.counter("n") is c
    assert reg.histogram("h") is reg.histogram("h")
    assert reg.events("e", ("a",)) is reg.events("e", ("a",))


def test_event_stream_bounded_with_tuple_view():
    s = EventStream("log", ("step", "mode"), maxlen=4)
    for i in range(6):
        s.emit(step=i, mode=f"m{i}")
    assert len(s) == 4  # bounded like the old deque(maxlen=...) logs
    assert s[0] == {"step": 2, "mode": "m2"}
    view = _TupleView(s)
    step, mode = view[-1]  # legacy positional unpack keeps working
    assert (step, mode) == (5, "m5")
    assert view[1:3] == [(3, "m3"), (4, "m4")]
    assert list(view)[0] == (2, "m2")
    # append stores by reference: late in-place patches stay visible
    row = s.emit(step=9, mode="x")
    row["mode"] = "patched"
    assert s[-1]["mode"] == "patched"
    # state_dict rows are copies, immune to later mutation
    st = s.state_dict()
    row["mode"] = "mutated-after-snapshot"
    assert st["rows"][-1]["mode"] == "patched"


def test_registry_json_export_and_callback_replacement():
    reg = MetricsRegistry()
    reg.counter("c").add(2)
    reg.histogram("h").observe(3.0)
    reg.events("e", ("x",)).emit(x=1)
    reg.register_callback(lambda: {"lazy": 1.0}, key="k")
    out = reg.to_json()
    assert out["counters"]["c"] == 2
    assert out["gauges"]["lazy"] == 1.0
    assert out["histograms"]["h"]["count"] == 1
    assert out["events"]["e"] == 1  # lengths only by default
    full = reg.to_json(events=True)
    assert full["events"]["e"] == [{"x": 1}]
    # same key replaces the producer (restored engines re-bind; a retired
    # standby's closure must stop exporting)
    reg.register_callback(lambda: {"lazy": 2.0}, key="k")
    assert reg.to_json()["gauges"]["lazy"] == 2.0
    # a dead producer is skipped, not fatal
    def boom():
        raise RuntimeError("torn down")
    reg.register_callback(boom, key="dead")
    assert reg.to_json()["gauges"]["lazy"] == 2.0
    json.dumps(reg.to_json(events=True))  # JSON-serializable end to end


def test_registry_state_roundtrip():
    reg = MetricsRegistry()
    reg.counter("c").add(5)
    reg.gauge("g").set(1.5)
    for v in (1.0, 2.0, 30.0):
        reg.histogram("h").observe(v)
    reg.events("e", ("a", "b")).emit(a=1, b="x")
    reg2 = MetricsRegistry()
    reg2.load_state(reg.state_dict())
    assert reg2.counter("c").value == 5
    assert reg2.gauge("g").value == 1.5
    assert reg2.histogram("h").p50 == 2.0
    assert reg2.histogram("h").bucket_counts == reg.histogram("h").bucket_counts
    assert list(reg2.events("e", ("a", "b"))) == [{"a": 1, "b": "x"}]


# ---------------------------------------------------------------------------
# trace recorder
# ---------------------------------------------------------------------------


def test_recorder_disabled_is_noop():
    rec = TraceRecorder(enabled=False)
    rec.launch("decode", 0.0, 1.0, tokens=3)
    rec.request_begin(1)
    rec.request_event(1, "first_token")
    rec.request_end(1, "done")
    assert rec.events == [] and rec.dropped == 0


def test_recorder_schema_and_cap():
    clk = [0.0]
    rec = TraceRecorder(enabled=True, clock=lambda: clk[0], max_events=4)
    rec.request_begin(7, slo_class="interactive")
    rec.launch("decode", 1.0, 2.0, tokens=1)
    clk[0] = 3.0
    rec.request_end(7, "done", tokens=1)
    b, e = rec.events[1], rec.events[2]
    assert (b["ph"], b["name"], b["ts"], b["args"]["tokens"]) == \
        ("B", "decode", 1e6, 1)
    assert (e["ph"], e["name"], e["ts"]) == ("E", "decode", 2e6)
    assert rec.events[0]["ph"] == "b" and rec.events[0]["id"] == 7
    assert rec.events[3]["args"]["status"] == "done"
    assert rec.events[3]["ts"] == 3e6  # clock-injected timestamp
    rec.launch("decode", 4.0, 5.0)  # over cap: dropped, counted
    assert len(rec.events) == 4 and rec.dropped == 2
    trace = rec.export_chrome_trace()
    assert set(trace) == {"traceEvents", "displayTimeUnit"}
    json.dumps(trace)


def test_span_disabled_is_shared_noop():
    """Recorder disabled and no profiler session: ``span`` hands back one
    shared no-op whatever its name or args, and records nothing."""
    assert not TraceAnnotation.is_enabled()
    rec = TraceRecorder(enabled=False)
    sp = rec.span("engine.step", tick=3, rows=2)
    assert sp is rec.span("engine.decode") is TraceRecorder().span("x")
    with sp as inner:
        inner.set(admitted=1)
    assert rec.events == [] and rec.dropped == 0


def test_span_chrome_lane_nests_beside_launch_lane():
    """Enabled: phase spans are complete events on tid 1 on the recorder's
    clock, args set inside the span included; launches keep tid 0."""
    clk = [1.0]
    rec = TraceRecorder(enabled=True, clock=lambda: clk[0])
    with rec.span("engine.step", tick=0, rows=1):
        with rec.span("engine.admit") as sp:
            clk[0] = 1.5
            sp.set(admitted=2)
        rec.launch("decode", 1.5, 2.0, tokens=1)
        clk[0] = 2.5
    admit, launch_b, _, step = rec.events
    assert (admit["ph"], admit["tid"], admit["ts"], admit["dur"]) == \
        ("X", 1, 1e6, 0.5e6)
    assert admit["args"] == {"admitted": 2}
    assert (step["name"], step["ts"], step["dur"]) == ("engine.step", 1e6, 1.5e6)
    assert step["args"] == {"tick": 0, "rows": 1}
    assert launch_b["ph"] == "B" and launch_b["tid"] == 0
    json.dumps(rec.export_chrome_trace())


def test_gc_hook_installed_once_and_observes_pauses():
    hist = gc_pauses()
    Observability()
    Observability()
    assert gc_pauses() is hist
    assert sum(cb.__class__.__name__ == "_GcPauses" for cb in gc.callbacks) == 1
    n = hist.count
    gc.collect()
    assert hist.count == n + 1 and hist.sum > 0
    g = Observability().registry.to_json()["gauges"]
    assert g["host_gc_pauses"] == hist.count
    assert g["host_gc_pause_ms_sum"] == hist.sum


# ---------------------------------------------------------------------------
# engine phase spans in the JAX profiler's trace
# ---------------------------------------------------------------------------

DECODE_PARENTS = {"dense": "engine.decode", "paged": "engine.paged_decode",
                  "spec": "engine.verify"}
PHASES = ("engine.inputs", "engine.device", "engine.readback",
          "engine.commit")


def _profiled(tmp_path, fn):
    """Run ``fn`` under the JAX profiler; the trace's events as
    ``(name, start_ns, end_ns, stats)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "host.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _inside(span, parents):
    _, a, b, _ = span
    return [p for p in parents if p[1] <= a and b <= p[2]]


def _serve(eng, lengths, rid0):
    for k, plen in enumerate(lengths):
        eng.submit(Request(rid=rid0 + k, prompt=tuple(range(1, plen + 1)),
                           max_new_tokens=3))
    n = 0
    while (eng.queue or eng.n_active) and n < 100:
        eng.step()
        n += 1
    assert not (eng.queue or eng.n_active)


@pytest.mark.parametrize("kind", ["dense", "paged", "spec"])
def test_engine_spans_nest_in_profiler_trace(kind, tmp_path):
    """A few CPU ticks under the profiler: every decode and prefill lies in
    an ``engine.step``, the four launch phases in their decode span, args
    present; ``engine_program_builds`` counts warm-up's misses, stays flat
    while serving warmed shapes, and names the one new prompt length."""
    eng = _factory(paged=PagedLayout(page_size=4) if kind == "paged" else None,
                   speculative=SpecConfig(ks=(2,)) if kind == "spec"
                   else None)()
    builds = eng.metrics.counters["engine_program_builds"]
    lengths = (5, 7, 5)
    _serve(eng, lengths, 0)  # warms the prefills of these lengths
    warm = builds.value
    assert warm >= len(eng.groups) + len(set(lengths))
    spans = _profiled(tmp_path, lambda: _serve(eng, lengths, 10))
    assert builds.value == warm
    assert not [s for s in spans if s[0] == "engine.build"]

    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    steps = by["engine.step"]
    parent = DECODE_PARENTS[kind]
    decodes = by[parent]
    assert decodes and by["engine.prefill"] and by["engine.admit"]
    for s in decodes + by["engine.prefill"] + by["engine.admit"]:
        assert len(_inside(s, steps)) == 1, s
    for _, _, _, st in steps:
        assert {"tick", "rows"} <= set(st)
    assert any(st["admitted"] >= 1 for *_, st in by["engine.admit"])
    for _, _, _, st in by["engine.prefill"]:
        assert st["plen"] in lengths and st["depth"] in eng.groups
    for _, _, _, st in decodes:
        assert st["depth"] in eng.groups and st["rows"] >= 1
    if kind == "spec":
        assert len(by["engine.draft"]) == len(decodes)
        for s in by["engine.draft"]:
            assert len(_inside(s, steps)) == 1
    else:
        for _, _, _, st in decodes:
            assert 0 < st["w_max"] <= 1.0
    # each decode span holds its launch's phases, one each (the spec draft
    # span holds its own inputs and device phases)
    inner = decodes + by.get("engine.draft", [])
    for name in PHASES:
        assert all(len(_inside(s, inner)) == 1 for s in by[name]), name
    for d in decodes:
        kids = [s[0] for s in spans if _inside(s, [d]) and s is not d]
        want = PHASES[1:] if kind == "spec" else PHASES
        assert sorted(kids) == sorted(want), kids

    spans = _profiled(tmp_path / "new", lambda: _serve(eng, (9,), 20))
    new = [st["key"] for name, *_, st in spans if name == "engine.build"]
    assert builds.value == warm + 1
    assert new == [str((9, eng.admission_mode.depth))]


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_chaos():
    """One paged+spec chaos run under tracing, shared by the acceptance
    checks below: fault-free reference first (its own registry), then a
    3-failover ping-pong run on a shared traced Observability."""
    ref_eng = _factory(paged=PagedLayout(page_size=4),
                       speculative=SpecConfig(ks=(2,)))()
    counter = FailurePlan()
    sup0 = ExecutorSupervisor(lambda: ref_eng, failure_plan=counter)
    sup0.run_trace(_trace())
    assert sup0.failovers == 0
    totals = dict(counter.site_counts)

    obs = Observability(trace=True)
    factory = _factory(obs=obs, paged=PagedLayout(page_size=4),
                       speculative=SpecConfig(ks=(2,)))
    sites = ["paged_decode", "verify", "prefill"]
    assert all(totals.get(s, 0) >= 1 for s in sites), totals
    plan = FailurePlan(at_sites=tuple((s, min(2, totals[s])) for s in sites))
    engines = [factory(), factory()]
    idx = [0]

    def pingpong():
        idx[0] ^= 1
        return engines[idx[0]]

    sup = ExecutorSupervisor(pingpong, failure_plan=plan,
                             max_failovers=len(plan.at_sites),
                             observability=obs)
    summary = sup.run_trace(_trace())
    assert summary["failovers"] == len(plan.at_sites)
    return obs, sup, ref_eng


def test_chaos_trace_chrome_schema(traced_chaos):
    """Every launch span is a matched, non-overlapping B/E pair; every
    request is one async b..e lane whose instants sit between them."""
    obs, sup, _ = traced_chaos
    trace = sup.engine.export_trace()
    events = trace["traceEvents"]
    assert events and trace["displayTimeUnit"] == "ms"
    json.dumps(trace)  # loads in Perfetto / chrome://tracing
    depth = 0
    open_name = None
    spans = {}  # rid -> [n_begin, n_end, n_instant]
    for ev in events:
        assert set(ev) >= {"ph", "name", "ts", "pid", "tid"}
        if ev["ph"] == "B":
            assert depth == 0, "engine launches never overlap"
            depth, open_name = 1, ev["name"]
            assert ev["name"] in ("decode", "paged_decode", "verify",
                                  "tree_verify", "prefill")
            assert ev["args"]["tokens"] >= 0
            assert ev["args"]["occupancy"] >= 1
        elif ev["ph"] == "E":
            assert depth == 1 and ev["name"] == open_name
            depth = 0
        elif ev["ph"] in ("b", "n", "e"):
            rid = ev["id"]
            c = spans.setdefault(rid, [0, 0, 0])
            c["bne".index(ev["ph"])] += 1
    assert depth == 0, "unclosed launch span"
    done = {r.rid for r in sup.engine.completed}
    assert set(spans) == done
    for rid, (nb, ni, ne) in spans.items():
        assert nb == 1 and ne == 1, (rid, nb, ne)
        assert ni >= 1  # at least the first-token instant


def test_chaos_trace_accounts_every_committed_token(traced_chaos):
    """Acceptance: launch-span token counts sum exactly to the tokens the
    run committed, and to the per-request totals the end events report —
    across three failovers (rolled-back partial ticks excluded)."""
    obs, sup, ref_eng = traced_chaos
    eng = sup.engine
    events = eng.export_trace()["traceEvents"]
    launched = sum(ev["args"]["tokens"] for ev in events if ev["ph"] == "B")
    committed = sum(len(r.generated) for r in eng.completed) + \
        sum(len(r.generated) for r in eng.expired)
    ended = sum(ev["args"]["tokens"] for ev in events if ev["ph"] == "e")
    assert launched == committed == ended
    # identical streams to the fault-free run (chaos exactness under trace)
    assert {r.rid: tuple(r.generated) for r in eng.completed} == \
        {r.rid: tuple(r.generated) for r in ref_eng.completed}
    # failover replays are marked on the surviving request lanes
    replays = [ev for ev in events
               if ev["ph"] == "n" and ev["args"]["event"] == "failover_replay"]
    assert replays, "no failover_replay instants in a 3-failover run"


def test_chaos_metrics_match_fault_free(traced_chaos):
    """Post-recovery registry counters land exactly on the fault-free run's
    (timing-valued counters excluded, and ``engine_program_builds``, which
    counts each engine's own cache misses: both ping-pong engines warm up
    into the shared registry, and a standby replays through its own
    caches): snapshot/restore carries metrics and the redone tick re-earns
    its increments."""
    obs, sup, ref_eng = traced_chaos

    def deterministic(eng):
        out = {k: v for k, v in eng.export_metrics()["counters"].items()
               if k not in ("engine_prefill_s", "engine_program_builds")}
        return out

    assert deterministic(sup.engine) == deterministic(ref_eng)
    # the supervisor recorded one recovery latency per failover
    h = obs.registry.histograms["failover_recovery_ms"]
    assert h.count == sup.failovers
    assert len(obs.registry.streams["supervisor_failover"]) == sup.failovers


def test_disabled_recorder_quiet_on_tick_path():
    """Default engines trace nothing: the recorder's event list stays empty
    across a full serve loop (the no-op guard never allocates)."""
    eng = _factory(speculative=SpecConfig(ks=(2,)))()
    for r in _trace(6, seed=3):
        eng.submit(r)
    n = 0
    while (eng.queue or eng.n_active) and n < 300:
        eng.step()
        n += 1
    assert eng.completed
    assert eng._rec.events == [] and eng._rec.dropped == 0
    assert eng.export_trace()["traceEvents"] == []
    # metrics still flow: histograms + structured counters populated
    m = eng.export_metrics()
    assert m["counters"]["engine_decode_launches"] == eng.decode_launches
    assert m["histograms"]["engine_decode_step_ms"]["count"] > 0
    assert m["gauges"]["engine_completed"] == len(eng.completed)
    assert "# TYPE engine_decode_launches counter" in \
        eng.metrics.prometheus_text()


def test_snapshot_restore_carries_metrics():
    """A restored standby's registry export equals the source's at the
    snapshot point: counters, histograms (windows included), and event
    streams all travel with EngineSnapshot.metrics."""
    obs_a = Observability(trace=True)
    a = _factory(obs=obs_a, speculative=SpecConfig(ks=(2,)))()
    for r in _trace(8, seed=11):
        a.submit(r)
    for _ in range(8):
        a.step()
    snap = a.snapshot()
    ea = a.export_metrics(events=True)
    na = len(obs_a.recorder.events)

    b = _factory(obs=Observability())()  # standby: fresh, untraced registry
    b.restore(snap)
    eb = b.export_metrics(events=True)
    assert eb["counters"] == ea["counters"]
    assert eb["histograms"] == ea["histograms"]
    assert eb["events"] == ea["events"]
    assert eb["gauges"]["engine_step_count"] == ea["gauges"]["engine_step_count"]
    # the recorder state travels too (trace-enabled source -> standby), with
    # the standby's replay marked after the carried events
    rec_b = b.obs.recorder
    assert rec_b.enabled
    assert [e for e in rec_b.events[:na]] == obs_a.recorder.events[:na]
    assert any(e["ph"] == "n" and e["args"]["event"] == "failover_replay"
               for e in rec_b.events[na:])
    # legacy log accessors keep their shapes through restore
    if a.admission_switch_log:
        assert b.admission_switch_log[:] == a.admission_switch_log[:]
    assert list(b.backpressure_log) == list(a.backpressure_log)


# ---------------------------------------------------------------------------
# SLO catch-up after failover
# ---------------------------------------------------------------------------


def test_slo_policy_failover_catchup():
    """After note_failover the policy squeezes the effective budget for
    catchup_ticks decisions (downshifting width/depth while the recovery
    debt drains), logs the decision as a structured event, and defaults the
    debt to the measured recovery histogram."""
    eng = _factory()()
    reg = eng.metrics
    pol = SLOPolicy(CFG, eng.ctrl, batch_size=3, cache_capacity=32,
                    metrics=reg, catchup_ticks=2, catchup_gamma=1.0)
    assert len(eng.ctrl.modes) >= 2, "catch-up needs a mode to downshift to"
    # widest mode fits, but the capped catch-up squeeze (eff = budget / 5)
    # pushes the effective budget below it
    budget = max(pol.analytical.values()) * 2
    base = pol.choose(budget)
    assert pol.last_decision["catchup_penalty"] == 0.0

    pol.note_failover(recovery_ms=budget * 1e3 * 100)  # huge debt
    m1 = pol.choose(budget)
    d1 = dict(pol.last_decision)
    assert d1["catchup_penalty"] > 0
    assert d1["effective_budget_s"] < budget
    assert pol.est_latency(m1) <= pol.est_latency(base)
    assert m1 != base, "huge recovery debt must downshift the mode"
    ev = reg.streams["slo_catchup"][-1]
    assert ev["mode"] == m1.name and ev["catchup_penalty"] > 0
    assert ev["ticks_left"] == 1

    pol.choose(budget)  # second (last) catch-up tick
    post = pol.choose(budget)  # window drained: back to the base choice
    assert pol.last_decision["catchup_penalty"] == 0.0
    assert post == base

    # default recovery_ms comes from the supervisor-recorded histogram p50
    reg.histogram("failover_recovery_ms").observe(40.0)
    reg.histogram("failover_recovery_ms").observe(60.0)
    pol.note_failover()
    assert pol._last_recovery_ms in (40.0, 60.0)
    assert pol._catchup_left == 2
