"""Serving driver: continuous-batching engine with NeuroMorph reconfiguration.

Drives ``repro.runtime.serving.ServingEngine`` — request queue, per-step slot
admission, per-DEPTH slot groups with per-slot runtime widths — while
switching morph modes on the fly. Width switches are a scalar-operand change
inside one executable; only distinct depths compile separately: no weight
movement, no recompilation after warmup (asserted and reported).

``--mesh dpxtp`` runs the same engine SPMD-sharded: a (data, model) mesh from
``launch.mesh.make_serve_mesh``, params placed by ``serve_policy`` specs,
sharded per-slot caches, replicated width operands (``MeshExecutor``). On a
CPU-only host the requested device count is forced via XLA_FLAGS
automatically (the flag must be set before jax initializes, which is why it
is handled at module import).

Two traffic shapes:
  * default: a fixed round of ``--batch`` x enough requests to generate
    ``--tokens`` tokens, cycling the admission mode every ``--switch-every``
    engine steps (the original demo's forced mode churn).
  * ``--budget-ms``: SLO-driven — the admission mode is chosen each tick as
    the widest mode whose predicted step latency (analytical estimate at the
    mesh's DesignPoint(dp, tp), corrected online by measured telemetry) fits
    the budget.

Fault-tolerance drill: ``--fail-at site:occ[,site:occ...]`` injects executor
failures at launch boundaries (sites: decode, paged_decode, verify,
tree_verify, prefill) and ``--tick-timeout-s`` arms hung-tick detection;
either one routes the drive loop through an ``ExecutorSupervisor`` that
snapshots before every tick and rebuilds + replays on failure (recovery
timings are printed per failover). ``--deadline-s`` gives every request a
TTL on the virtual serving clock; requests queued past it finish as
``expired`` instead of occupying slots.

Observability: ``--trace-out trace.json`` records every launch and request
lifecycle as Chrome trace-event JSON (open in Perfetto or chrome://tracing);
``--metrics-dump`` prints the end-of-run metrics registry as Prometheus
exposition text plus a JSON snapshot. Both survive failovers — the whole run
shares one recorder/registry.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
      --tokens 64 --switch-every 16 --mesh 2x4
"""
from __future__ import annotations

import argparse
import json
import sys


from repro.xla_flags import force_host_device_count, mesh_arg


def _parse_mesh(spec: str):
    try:
        dp, tp = (int(x) for x in spec.lower().split("x"))
    except ValueError:
        raise SystemExit(f"--mesh wants DPxTP (e.g. 2x4), got {spec!r}")
    return dp, tp


# --xla_force_host_platform_device_count only takes effect before jax's
# backend initializes, so the --mesh arg is inspected pre-import; malformed
# or missing values are left for argparse to report properly.
_mesh_spec = mesh_arg(sys.argv)
if _mesh_spec is not None:
    try:
        _dp, _tp = _parse_mesh(_mesh_spec)
    except SystemExit:
        pass
    else:
        force_host_device_count(_dp * _tp)

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, smoke_config
from repro.core import elastic
from repro.launch.mesh import make_serve_mesh
from repro.models.model import init_params
from repro.models.paged import PagedLayout
from repro.runtime.fault_tolerance import ExecutorSupervisor, FailurePlan
from repro.runtime.observability import Observability
from repro.runtime.serving import (MeshExecutor, Request, ServingEngine,
                                   SLOPolicy)
from repro.runtime.speculative import SpecConfig

FAILURE_SITES = ("decode", "paged_decode", "verify", "tree_verify", "prefill")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4, help="batch slots per mode")
    ap.add_argument("--tokens", type=int, default=64,
                    help="total tokens to generate across all requests")
    ap.add_argument("--switch-every", type=int, default=16,
                    help="cycle admission mode every N engine steps")
    ap.add_argument("--budget-ms", type=float, default=0.0,
                    help="if > 0, use the SLO policy with this latency budget")
    ap.add_argument("--mesh", default="",
                    help="DPxTP (e.g. 2x4): shard the engine over a "
                         "(data, model) mesh")
    ap.add_argument("--prefill-threshold", type=int, default=8,
                    help="prompts at least this long are consumed by one "
                         "prefill launch instead of token-by-token")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax); per-slot "
                         "PRNG keys keep sampled streams reproducible under "
                         "slot churn")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k sampling truncation (0 = full vocab)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding draft length K (0 = off): "
                         "shallow DistillCycle exits draft K tokens, one "
                         "full-depth launch verifies K+1 positions")
    ap.add_argument("--spec-tree", default="",
                    help="token-tree speculative decoding branching "
                         "schedule, e.g. 2x2x1 (level l: every frontier "
                         "node gets that many sibling candidates); one "
                         "full-depth launch verifies the whole tree and "
                         "commits the accepted root-to-leaf path. May be "
                         "combined with --spec-k (the SLO policy switches "
                         "between the compiled shapes at runtime)")
    ap.add_argument("--spec-draft-depth", type=int, default=0,
                    help="draft exit depth in layer groups (0 = deepest "
                         "exit shallower than each serving depth)")
    ap.add_argument("--kv-page-size", type=int, default=0,
                    help="block-paged KV cache: tokens per physical page "
                         "(0 = dense per-slot buffers). Must divide the "
                         "cache capacity, and the sliding window when the "
                         "arch uses one; shared prompt prefixes then reuse "
                         "physical pages via the radix cache")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="physical page-pool size (0 = worst case: every "
                         "slot at full length + scratch). Requires "
                         "--kv-page-size; undersizing trades admission "
                         "failures for memory")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request TTL in seconds on the virtual serving "
                         "clock (0 = none): requests still queued past it "
                         "finish as 'expired' instead of occupying slots")
    ap.add_argument("--fail-at", default="",
                    help="inject executor failures: comma-separated "
                         "site:occurrence pairs, e.g. decode:3,verify:1 "
                         f"(sites: {', '.join(FAILURE_SITES)}); each kills "
                         "that site's Nth launch, and an ExecutorSupervisor "
                         "rebuilds from the pre-tick snapshot and replays")
    ap.add_argument("--tick-timeout-s", type=float, default=0.0,
                    help="if > 0, supervise ticks with a wall-time timeout: "
                         "a slower tick is treated as a hung executor — its "
                         "results are discarded and the tick is redone on a "
                         "rebuilt engine")
    ap.add_argument("--trace-out", default="",
                    help="enable the trace recorder and write Chrome "
                         "trace-event JSON (open in Perfetto or "
                         "chrome://tracing) to this path at end of run: "
                         "per-launch spans + per-request lifecycle lanes")
    ap.add_argument("--metrics-dump", action="store_true",
                    help="print the end-of-run metrics registry as "
                         "Prometheus exposition text plus a JSON snapshot")
    ap.add_argument("--autoscale", action="store_true",
                    help="online NeuroForge autoscaler: periodically re-run "
                         "the MOGA over the live executable pool (modes x "
                         "draft shapes x page buckets) with telemetry-"
                         "blended objectives; frontier points compile on a "
                         "background thread and publish atomically. "
                         "Requires --budget-ms (the SLO loop hosts the "
                         "autoscale tick)")
    ap.add_argument("--autoscale-interval", type=int, default=8,
                    help="serving ticks between MOGA generations")
    ap.add_argument("--autoscale-table-budget", type=int, default=0,
                    help="compile-table budget (live executables); cold "
                         "unassigned units are retired while the table "
                         "exceeds it (0 = no eviction)")
    ap.add_argument("--autoscale-ks", default="",
                    help="comma-separated candidate draft lengths the "
                         "autoscaler may adopt beyond the warmed table, "
                         "e.g. 4,6")
    ap.add_argument("--autoscale-pop", type=int, default=16,
                    help="MOGA population per online generation")
    ap.add_argument("--autoscale-gens", type=int, default=4,
                    help="MOGA generations per online re-run")
    ap.add_argument("--autoscale-explore-modes", action="store_true",
                    help="let admission move across the frontier's modes "
                         "(default: pinned mode — adoption only changes "
                         "draft shapes/buckets, keeping committed streams "
                         "bit-identical to a fixed-mode run)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    failure_plan = None
    if args.fail_at:
        at_sites = []
        for part in args.fail_at.split(","):
            site, sep, occ = part.strip().partition(":")
            if site not in FAILURE_SITES or not sep or not occ.isdigit() \
                    or int(occ) < 1:
                ap.error(f"--fail-at wants site:occurrence pairs with sites "
                         f"in {FAILURE_SITES} and occurrence >= 1, got "
                         f"{part!r}")
            at_sites.append((site, int(occ)))
        failure_plan = FailurePlan(at_sites=tuple(at_sites))

    if args.batch < 1:
        ap.error(f"--batch must be >= 1, got {args.batch}")
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    modes = cfg.elastic.modes(cfg.n_groups)

    spec_trees = ()
    if args.spec_tree:
        try:
            branching = tuple(int(b) for b in
                              args.spec_tree.lower().split("x"))
        except ValueError:
            branching = ()
        if not branching or any(b < 1 for b in branching):
            ap.error(f"--spec-tree wants a branching schedule of levels "
                     f">= 1 like 2x2x1, got {args.spec_tree!r}")
        spec_trees = (branching,)
    per_req = max(4, args.tokens // (2 * args.batch))
    n_requests = max(args.batch, (args.tokens + per_req - 1) // per_req)
    # drafted-window headroom: linear K or the deepest tree level count
    draft_depth_max = max([args.spec_k] + [len(t) for t in spec_trees])
    capacity = per_req + 8 + draft_depth_max

    executor = None
    dp = tp = 1
    if args.mesh:
        dp, tp = _parse_mesh(args.mesh)
        executor = MeshExecutor(make_serve_mesh(dp, tp))
    speculative = None
    if args.spec_k > 0 or spec_trees:
        speculative = SpecConfig(
            ks=(args.spec_k,) if args.spec_k > 0 else (),
            trees=spec_trees,
            draft_depth=args.spec_draft_depth or None,
            top_k=args.top_k)
    paged = None
    if args.kv_pages and not args.kv_page_size:
        ap.error("--kv-pages requires --kv-page-size (a pool needs a page "
                 "geometry)")
    if args.kv_page_size:
        # round the derived capacity up to a page boundary so the layout
        # validates for any --tokens/--batch combination
        capacity += (-capacity) % args.kv_page_size
        paged = PagedLayout(page_size=args.kv_page_size,
                            n_pages=args.kv_pages or None)
        try:
            paged.validate(cfg, capacity)
        except ValueError as e:
            ap.error(str(e))
    # one Observability shared by every engine this run builds (failover
    # standbys included), so the trace and metrics cover the whole run
    obs = Observability(trace=bool(args.trace_out))

    def build_engine():
        return ServingEngine(params, cfg, batch_size=args.batch,
                             cache_capacity=capacity, modes=modes,
                             executor=executor,
                             prefill_threshold=args.prefill_threshold,
                             speculative=speculative,
                             temperature=args.temperature, top_k=args.top_k,
                             sample_seed=args.seed, paged=paged,
                             observability=obs)

    engine = build_engine()
    mesh_note = (f" mesh=dp{dp}xtp{tp} policy={engine.executor.policy}"
                 if args.mesh else "")
    paged_note = ""
    if paged is not None:
        pool = paged.pool_pages(cfg, args.batch, capacity)
        paged_note = f" kv=paged({args.kv_page_size} tok/page, {pool} pages)"
    print(f"[serve] {cfg.name}: modes = {[m.name for m in modes]} "
          f"requests={n_requests} x {per_req} tokens, batch={args.batch}"
          f"{mesh_note}{paged_note}")
    engine.warmup()

    supervisor = None
    if failure_plan is not None or args.tick_timeout_s > 0:
        warmed = [engine]

        def factory():
            if warmed:  # first call adopts the already-warmed engine
                return warmed.pop()
            eng = build_engine()
            eng.warmup()
            return eng

        supervisor = ExecutorSupervisor(
            factory, failure_plan=failure_plan,
            tick_timeout_s=args.tick_timeout_s or None)

    for i in range(n_requests):
        engine.submit(Request(rid=i, prompt=(1 + i % (cfg.vocab_size - 1),),
                              max_new_tokens=per_req,
                              slo_class="interactive" if i % 3 == 0 else "batch",
                              deadline_s=args.deadline_s or None))

    scaler = None
    if args.autoscale and args.budget_ms <= 0:
        ap.error("--autoscale requires --budget-ms (the SLO loop hosts the "
                 "autoscale tick)")
    policy = None
    if args.budget_ms > 0:
        if args.autoscale:
            from repro.runtime.autoscale import (AutoscaleConfig, Autoscaler,
                                                 AutoscalePolicy)
            ks = tuple(int(k) for k in args.autoscale_ks.split(",")
                       if k.strip())
            scaler = Autoscaler(AutoscaleConfig(
                interval_ticks=args.autoscale_interval,
                table_budget=args.autoscale_table_budget or None,
                spec_ks=ks, explore_modes=args.autoscale_explore_modes,
                pop_size=args.autoscale_pop,
                generations=args.autoscale_gens,
                seed=args.seed)).bind(engine)
            policy = AutoscalePolicy(cfg, engine.ctrl, autoscaler=scaler,
                                     batch_size=args.batch,
                                     cache_capacity=capacity, dp=dp, tp=tp,
                                     metrics=engine.metrics)
        else:
            policy = SLOPolicy(cfg, engine.ctrl, batch_size=args.batch,
                               cache_capacity=capacity, dp=dp, tp=tp)
        if supervisor is not None:
            supervisor.attach_policy(policy)

    mode_idx = len(modes) - 1
    busy = 0.0
    while True:
        # a failover swaps the engine out from under the loop
        engine = supervisor.engine if supervisor is not None else engine
        if scaler is not None and scaler.engine is not engine:
            scaler.bind(engine)  # a failover swapped the engine: re-attach
        if not (engine.queue or engine.n_active):
            break
        if policy is not None:
            engine.set_admission_mode(policy.choose(args.budget_ms * 1e-3))
        elif engine.step_count and engine.step_count % args.switch_every == 0:
            mode_idx = (mode_idx - 1) % len(modes)  # degrade then wrap
            engine.set_admission_mode(modes[mode_idx])
        if supervisor is not None:
            busy += supervisor.tick(now_s=busy)
        else:
            busy += engine.step(now_s=busy)
    engine = supervisor.engine if supervisor is not None else engine

    if scaler is not None:
        scaler._drain_publish()  # land any adoption still in flight
        assert engine.ctrl.stats["compiles"] == \
            engine.compiles_after_warmup + scaler.stats["published_keys"], \
            "every post-warmup compile must come through publish_aux"
        assert scaler.stats["tick_stalls"] == 0, \
            "background compilation stalled a serving tick"
    else:
        assert engine.ctrl.stats["compiles"] == engine.compiles_after_warmup, \
            "runtime switch must not recompile"
    if supervisor is not None:
        if failure_plan is not None:
            missed = set(failure_plan.at_sites) - failure_plan.fired_sites
            if missed:
                print(f"[serve] warning: planned failures never reached "
                      f"(too few launches at those sites): {sorted(missed)}")
        for e in supervisor.failover_log:
            ftok = (f"{e['first_token_s'] * 1e3:.0f} ms"
                    if e["first_token_s"] is not None else "n/a")
            print(f"[serve] failover @step {e['step']}: {e['cause']} | "
                  f"rebuild {e['rebuild_s'] * 1e3:.0f} ms, "
                  f"replay {e['replay_s'] * 1e3:.0f} ms, "
                  f"first token {ftok}")
    ctrl = engine.ctrl
    generated = sum(len(r.generated) for r in engine.completed)
    print(f"[serve] completed={len(engine.completed)} "
          f"expired={len(engine.expired)} "
          f"failovers={supervisor.failovers if supervisor else 0} "
          f"generated={generated} "
          f"switches={ctrl.stats['switches']} "
          f"admission_switches={len(engine.admission_switch_log)} "
          f"recompiles_after_warmup=0 dispatches={ctrl.stats['dispatches']} "
          f"executables={ctrl.stats['compiles']} (per depth) "
          f"decode_launches={engine.decode_launches} "
          f"(per-mode baseline {engine.per_mode_launch_equiv}) "
          f"prefills={engine.prefills} "
          f"tokens/s={generated / busy if busy else 0.0:.1f}")
    for name, t in ctrl.telemetry_summary().items():
        mode = ctrl.mode_by_name[name]
        frac = elastic.flops_fraction(cfg, mode)
        print(f"  mode {name:8s} p50 {t['p50_ms']:8.2f} ms  p95 {t['p95_ms']:8.2f} ms  "
              f"{t['tokens_per_s']:8.1f} tok/s  active-FLOPs {frac * 100:5.1f}%")
    for path, t in engine.spec_telemetry_summary().items():
        print(f"  spec {path:10s} accept {t['accept_rate'] * 100:5.1f}%  "
              f"accepted/launch {t['accepted_per_launch']:.2f}  "
              f"tokens/launch {t['tokens_per_launch']:.2f} "
              f"(per-slot {t['tokens_per_slot_launch']:.2f})  "
              f"launches {t['launches']}")
    if engine.spec_fallback_log:
        print(f"  spec fallbacks: {list(engine.spec_fallback_log)}")
    if scaler is not None:
        st = scaler.stats
        print(f"[serve] autoscale generations={st['generations']} "
              f"published={st['published']} retired={st['retired']} "
              f"front={len(scaler.front)} "
              f"table={ctrl.compile_table_size} "
              f"tick_stalls={st['tick_stalls']}")
        for pt, obj in zip(scaler.front, scaler.front_objectives):
            print(f"  front d{pt.depth} w{pt.width} spec_k={pt.spec_k} "
                  f"tree={pt.spec_tree} bucket={pt.bucket} "
                  f"lat/tok={obj[0] * 1e3:.2f} ms")
        scaler.close()
    if paged is not None:
        engine.check_paged_invariants()
        for depth, st in sorted(engine.page_pool_stats().items()):
            print(f"  pages depth={depth}: pool {st['n_pages']} "
                  f"peak {st['peak_in_use']} allocs {st['allocs']} "
                  f"radix hit-rate {st['radix_hit_rate'] * 100:.0f}% "
                  f"({st['radix_nodes']} nodes)")
    if args.trace_out:
        obs.recorder.write(args.trace_out)
        n_ev = len(obs.recorder.events)
        dropped = (f" ({obs.recorder.dropped} dropped at the event cap)"
                   if obs.recorder.dropped else "")
        print(f"[serve] wrote {n_ev} trace events{dropped} to "
              f"{args.trace_out} (open in Perfetto / chrome://tracing)")
    if args.metrics_dump:
        print("[serve] metrics (prometheus):")
        print(engine.metrics.prometheus_text(), end="")
        print("[serve] metrics (json):")
        print(json.dumps(engine.export_metrics(), indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
