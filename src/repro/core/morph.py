"""NeuroMorph runtime controller — mode switching without redeployment.

On the FPGA, NeuroMorph toggles clock gates to activate a subnetwork; the
weights stay in place, nothing is reprogrammed. The TPU analogue implemented
here mirrors that split along the two morph axes:

* **Width is a runtime operand, not a compile-time shape.** The serving
  controller (``make_serve_controller``) compiles ONE decode executable per
  *depth*, taking the full parameter pytree, a full-width cache, and an
  ``active`` dict of per-slot active inner-dim sizes (see
  ``elastic.active_widths_batch``). Those integers flow into
  ``kernels.morph_matmul`` where out-of-width tiles issue no MXU work — a
  width switch is literally a different scalar operand, the clock-gate flip.
  Slots of *different* widths share a single launch.

* **Depth stays compile-time.** Depth changes the layer-group scan's trip
  count, so each distinct depth is its own executable over the same donated
  weight buffers (``compile_key`` groups modes by depth). After warmup,
  ``stats["compiles"] == len(distinct depths)``, not ``len(modes)``.

``MorphController`` records switch telemetry (compile count, dispatch count,
per-mode latency percentiles) so tests can assert the no-copy/no-recompile
invariants, and the serve controller carries a ``trace_counter`` incremented
only when jax actually traces — the measured single-executable claim.

Both morph axes survive sharding: with a mesh, ``make_serve_controller``
compiles each per-depth executable SPMD (``NamedSharding``-annotated jit over
placed params, a sharded donated cache, replicated width operands, and
activation constraints from ``sharding.decode_specs``) with the same
``compile_key`` — depth picks the executable, width stays runtime data, and
the sharded step is token-identical to the local one (logits match to float
tolerance; collective reduction order moves the last bits).
"""
from __future__ import annotations

import bisect
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, Hashable, List, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, MorphMode
from repro.core import elastic
from repro.models.model import decode_step
from repro.parallel import sharding as _sh


def paged_decode_compile_key(depth: int, bucket: int) -> Tuple:
    """Compile key of the paged one-token decode executable for one
    (depth, page-table-width bucket). Disjoint from the per-depth dense keys
    and the speculative aux keys."""
    return ("paged_decode", depth, bucket)


class ModeTelemetry:
    """Online per-mode step-latency / throughput statistics.

    Latencies are kept sorted in a bounded window: percentile queries are
    O(1); recording is O(window) worst case (sorted-list insert/evict) —
    trivial at serving tick rates with the default window of 512.
    ``tokens_per_s`` is aggregate over everything recorded.
    """

    def __init__(self, window: int = 512):
        self._window = window
        self._sorted: List[float] = []  # sorted latencies, bounded
        self._fifo: Deque[float] = deque()  # same values in arrival order
        self.steps = 0
        self.tokens = 0
        self.total_s = 0.0

    def record(self, dt_s: float, tokens: int = 0) -> None:
        self.steps += 1
        self.tokens += tokens
        self.total_s += dt_s
        bisect.insort(self._sorted, dt_s)
        self._fifo.append(dt_s)
        if len(self._fifo) > self._window:
            old = self._fifo.popleft()
            self._sorted.pop(bisect.bisect_left(self._sorted, old))

    def _quantile(self, q: float) -> float:
        if not self._sorted:
            return 0.0
        i = min(len(self._sorted) - 1, int(q * len(self._sorted)))
        return self._sorted[i]

    @property
    def p50_s(self) -> float:
        return self._quantile(0.50)

    @property
    def p95_s(self) -> float:
        return self._quantile(0.95)

    @property
    def p99_s(self) -> float:
        return self._quantile(0.99)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / self.total_s if self.total_s > 0 else 0.0

    def summary(self) -> Dict[str, float]:
        return {"steps": self.steps, "tokens": self.tokens,
                "p50_ms": self.p50_s * 1e3, "p95_ms": self.p95_s * 1e3,
                "p99_ms": self.p99_s * 1e3,
                "tokens_per_s": self.tokens_per_s}

    def state_dict(self) -> Dict:
        """Copy of the full telemetry state (plain lists/scalars).

        Snapshot/restore seam for fault-tolerant serving: a rebuilt engine
        must keep steering the SLO policy with the measured history the lost
        executor accumulated, not restart from the analytical cold start.
        """
        return {"window": self._window, "fifo": list(self._fifo),
                "steps": self.steps, "tokens": self.tokens,
                "total_s": self.total_s}

    def load_state(self, st: Dict) -> None:
        self._window = int(st["window"])
        self._fifo = deque(st["fifo"])
        self._sorted = sorted(self._fifo)
        self.steps = int(st["steps"])
        self.tokens = int(st["tokens"])
        self.total_s = float(st["total_s"])


class MorphController:
    """Dispatches train/serve steps to specialized executables.

    ``compile_key`` maps a mode to its executable's cache key: the default
    (mode name) specializes per mode; the serving controller passes
    ``lambda m: m.depth`` so all width modes of a depth share one executable
    (width arrives as a runtime operand instead).
    """

    def __init__(self, cfg: ModelConfig, step_factory: Callable[[MorphMode], Callable],
                 modes: Optional[Tuple[MorphMode, ...]] = None,
                 compile_key: Callable[[MorphMode], Hashable] = lambda m: m.name):
        self.cfg = cfg
        self.modes = tuple(modes or cfg.elastic.modes(cfg.n_groups))
        self.mode_by_name = {m.name: m for m in self.modes}
        self._factory = step_factory
        self._compile_key = compile_key
        self._compiled: Dict[Hashable, Callable] = {}
        # auxiliary executables (e.g. speculative draft/verify steps) share
        # the compile cache, compile counter and warmup with the mode table
        self._aux_factories: Dict[Hashable, Callable[[], Callable]] = {}
        # builder kinds the serving wiring exposes for post-warmup
        # registration (autoscaler frontier points); see make_serve_controller
        self.aux_builders: Dict[str, Callable] = {}
        # dispatch count at each executable's last use — the autoscaler's
        # coldness signal for compile-table eviction
        self.last_dispatch: Dict[Hashable, int] = {}
        self.stats = {"compiles": 0, "dispatches": 0, "switches": 0}
        self.telemetry: Dict[str, ModeTelemetry] = {m.name: ModeTelemetry()
                                                   for m in self.modes}
        # per-set_mode-change structured event stream; bounded for long
        # serves. Lazy import: repro.runtime imports this module at package
        # init, so the reverse import must wait until construction time.
        from repro.runtime.observability import Counter, EventStream, TraceRecorder
        self.switch_events = EventStream(
            "controller_mode_switch", ("dispatch", "from_mode", "to_mode"))
        self.last_step_s = 0.0  # latency of the most recent timed_step
        # injectable for deterministic tests / virtual-clock supervision
        # (the serving engine points it at its Observability clock)
        self.clock: Callable[[], float] = time.perf_counter
        # phase spans and the build counter; the serving engine points them
        # at its own recorder and its registry's ``engine_program_builds``
        self.recorder = TraceRecorder()
        self.builds = Counter("engine_program_builds")
        self._mode = self.modes[-1]  # full model by default

    @property
    def switch_log(self):
        """Legacy tuple view of ``switch_events``: (dispatch#, from, to)."""
        from repro.runtime.observability import _TupleView
        return _TupleView(self.switch_events)

    @property
    def mode(self) -> MorphMode:
        return self._mode

    def set_mode(self, mode: MorphMode) -> None:
        if mode.name not in self.mode_by_name:
            raise KeyError(f"mode {mode.name} not in deployed mode table")
        if mode.name != self._mode.name:
            self.stats["switches"] += 1
            self.switch_events.emit(dispatch=self.stats["dispatches"],
                                    from_mode=self._mode.name,
                                    to_mode=mode.name)
        self._mode = mode

    def build(self, key: Hashable, make: Callable[[], Any]) -> Any:
        """``make()`` for a cache miss on the serving path (an executable or
        a device operand), inside one ``engine.build`` span naming ``key``
        and counted in ``builds``."""
        with self.recorder.span("engine.build", key=str(key)):
            self.builds.add()
            return make()

    def _get(self, mode: MorphMode) -> Callable:
        key = self._compile_key(mode)
        fn = self._compiled.get(key)
        if fn is None:
            fn = self.build(key, lambda: self._factory(mode))
            self._compiled[key] = fn
            self.stats["compiles"] += 1
        self.last_dispatch[key] = self.stats["dispatches"]
        return fn

    def register_aux(self, key: Hashable, factory: Callable[[], Callable]) -> None:
        """Register an auxiliary executable (compiled lazily / at warmup).

        Used by the speculative-decoding wiring: one draft executable per
        (draft_depth, K) and one verify executable per (depth, K), keyed by
        tuples disjoint from the per-depth decode keys. Registering the same
        key twice is an error — keys name executables, not variants.
        """
        if key in self._aux_factories or key in self._compiled:
            raise KeyError(f"aux executable {key!r} already registered")
        self._aux_factories[key] = factory

    def aux_step(self, key: Hashable) -> Callable:
        """The compiled auxiliary executable for ``key`` (compiling it on
        first use, counted in ``stats['compiles']`` like any mode step)."""
        fn = self._compiled.get(key)
        if fn is None:
            fn = self.build(key, self._aux_factories[key])
            self._compiled[key] = fn
            self.stats["compiles"] += 1
        self.last_dispatch[key] = self.stats["dispatches"]
        return fn

    def publish_aux(self, key: Hashable, fn: Callable,
                    factory: Optional[Callable[[], Callable]] = None) -> None:
        """Atomically install an ALREADY-COMPILED auxiliary executable.

        The autoscaler's publish-then-swap seam: a background thread traces
        and warms ``fn``, then the serving thread installs it with two dict
        assignments — no compile can ever land on a serving tick. Counted in
        ``stats['compiles']`` (the trace happened, just elsewhere).
        ``factory`` keeps a rebuild path for re-warmup after eviction.
        """
        if key in self._aux_factories or key in self._compiled:
            raise KeyError(f"aux executable {key!r} already registered")
        self._aux_factories[key] = factory if factory is not None else (lambda: fn)
        self._compiled[key] = fn
        self.stats["compiles"] += 1
        self.last_dispatch[key] = self.stats["dispatches"]

    def unregister_aux(self, key: Hashable) -> None:
        """Retire an auxiliary executable: drop its factory and compiled
        artifact (the compile-table eviction seam — ``register_aux`` treats
        re-registration as an error, so eviction must be explicit). The mode
        table itself is not evictable; ``stats['compiles']`` stays monotone.
        """
        if key not in self._aux_factories:
            raise KeyError(f"aux executable {key!r} is not registered")
        del self._aux_factories[key]
        self._compiled.pop(key, None)
        self.last_dispatch.pop(key, None)

    @property
    def compile_table_size(self) -> int:
        """Number of live compiled executables (modes + aux)."""
        return len(self._compiled)

    def compiled_keys(self) -> Tuple[Hashable, ...]:
        return tuple(self._compiled)

    def aux_keys(self) -> Tuple[Hashable, ...]:
        """Registered auxiliary keys (the evictable part of the table)."""
        return tuple(self._aux_factories)

    def coldness(self, key: Hashable) -> int:
        """Dispatches elapsed since ``key`` was last used (0 = hot)."""
        return self.stats["dispatches"] - self.last_dispatch.get(key, 0)

    def warmup(self) -> None:
        """Pre-compile every distinct executable (the deploy-time 'single
        bitstream'); modes sharing a compile key share one compile."""
        for m in self.modes:
            self._get(m)
        for key in self._aux_factories:
            self.aux_step(key)

    def __call__(self, *args, **kw):
        self.stats["dispatches"] += 1
        return self._get(self._mode)(*args, **kw)

    def timed_step(self, *args, mode: Optional[MorphMode] = None, tokens: int = 0,
                   **kw):
        """Dispatch one step, block on the result, record telemetry.

        ``mode`` dispatches a specific executable WITHOUT going through
        ``set_mode``: a serving engine interleaving draining mode groups is
        not making policy decisions, and must not inflate the switch
        counter/log. ``tokens`` is the number of useful tokens this step
        produced (active batch slots), feeding ``tokens_per_s``. The measured
        latency is the online correction signal an SLO policy blends with
        the analytical estimate.
        """
        m = self._mode if mode is None else mode
        self.stats["dispatches"] += 1
        fn = self._get(m)
        with self.recorder.span("engine.device"):
            t0 = self.clock()
            out = fn(*args, **kw)
            jax.block_until_ready(out)
            dt = self.clock() - t0
        self.telemetry[m.name].record(dt, tokens)
        self.last_step_s = dt
        return out

    def step_for(self, mode: MorphMode) -> Callable:
        return self._get(mode)

    def force_mode(self, mode: MorphMode) -> None:
        """Set the active mode WITHOUT counting/logging a switch.

        Snapshot restore re-materializes a policy decision that was already
        made (and logged) once on the lost executor; routing it through
        ``set_mode`` would double-count it in ``stats['switches']``.
        """
        if mode.name not in self.mode_by_name:
            raise KeyError(f"mode {mode.name} not in deployed mode table")
        self._mode = mode

    def telemetry_summary(self) -> Dict[str, Dict[str, float]]:
        return {name: t.summary() for name, t in self.telemetry.items()
                if t.steps}

    def telemetry_state(self) -> Dict[str, Dict]:
        """Snapshot-able per-mode telemetry (see ModeTelemetry.state_dict)."""
        return {name: t.state_dict() for name, t in self.telemetry.items()}

    def load_telemetry_state(self, st: Dict[str, Dict]) -> None:
        for name, s in st.items():
            if name in self.telemetry:
                self.telemetry[name].load_state(s)


def make_serve_controller(params, cfg: ModelConfig,
                          modes: Optional[Tuple[MorphMode, ...]] = None, *,
                          mesh=None, policy: str = "serve_tp",
                          param_shardings=None, cache_shardings=None,
                          activation_specs=None, verify_activation_specs=None,
                          speculative=None, paged_page_size: int = 0,
                          paged_buckets: Tuple[int, ...] = (),
                          fused: bool = False) -> MorphController:
    """Serving controller: ONE jitted decode executable per *depth*.

    Each executable's signature is ``step(params, cache, tokens, active)``:
    full params (the only device-resident weight copy), a FULL-width per-slot
    cache (donated — the update is in place), and ``active`` per-slot width
    operands from ``elastic.active_widths_batch``. Width morphing never
    recompiles: the same executable serves every width, and a single launch
    may mix widths across batch slots. ``ctrl.trace_counter["n"]`` advances
    only when jax traces a step — the measured zero-recompile invariant.

    With ``mesh``, each per-depth executable is compiled SPMD under
    ``NamedSharding`` annotations instead: params arrive pre-placed by the
    ``policy`` specs (pass ``param_shardings`` to reuse the executor's
    placement), the donated cache keeps the serving-cache layout
    (``cache_shardings``, from ``sharding.serve_cache_specs``), tokens and
    the runtime-width ``active`` scalars are replicated operands, and decode
    activations are constrained inside the step via ``activation_specs``
    (``sharding.decode_specs``). ``compile_key`` is unchanged — one sharded
    executable per depth, width still a runtime operand.

    ``speculative`` (a ``runtime.speculative.SpecConfig``) additionally
    registers the self-speculative executables: for every serving depth with
    a shallower exit available, ONE draft executable per (draft_depth, K)
    — shared by every serving depth drafting at that exit — and ONE fused
    verify+accept+commit executable per (depth, K); token-tree topologies in
    ``SpecConfig.trees`` likewise compile one tree-draft per (draft_depth,
    tree) and one tree-verify per (depth, tree), keyed by the static
    branching schedule. Their compile keys live
    in the same table as the per-depth decode keys, so ``stats['compiles']``
    and the shared ``trace_counter`` measure the whole serving surface:
    after warmup, arbitrary (draft_depth, K) switching, greedy/sampled
    temperature changes, and acceptance churn re-trace nothing. Under a
    mesh the draft/verify executables compile SPMD with the same placement
    as the decode steps (tokens / keys / temperature replicated; the verify
    cache donated and sharded in and out; the draft cache NOT donated — its
    in-scan updates are discarded to keep the committed state rollback-safe).

    ``paged_page_size`` > 0 switches the whole serving surface to the
    block-paged cache layout (``models.paged``): every executable takes a
    trailing traced page-table operand, the one-token decode path is keyed
    ``("paged_decode", depth, bucket)`` for every table-width bucket in
    ``paged_buckets`` (the zero-re-trace discipline over variable-length
    slots: slots whose page counts fall in one bucket share one executable),
    and the speculative draft/verify executables read/write the page pool at
    the full table width. The per-depth mode table is still registered (and
    warmed without tracing) but a paged engine dispatches the bucketed aux
    keys instead.

    ``fused=True`` routes every attention decode/verify/tree-verify through
    the ``kernels.fused_decode`` superkernel (one launch per attention layer
    step instead of QKV + attention + dequant + output). It is a pure
    closure flag — compile keys, the aux table, and the zero-re-trace
    invariants are unchanged: the fused op takes the same traced width/page
    operands the unfused path does.
    """
    trace_counter = {"n": 0}
    if mesh is not None:
        if cache_shardings is None:
            raise ValueError("mesh compile path needs cache_shardings "
                             "(sharding.serve_cache_specs of the engine cache)")
        if param_shardings is None:
            param_shardings = _sh.shardings_for(
                _sh.param_specs(params, cfg, mesh, policy), mesh)
        rep = NamedSharding(mesh, P())
        active_sh = {k: rep for k in elastic.active_widths(cfg, 1.0)}
        in_sh = (param_shardings, cache_shardings, rep, active_sh)
        out_sh = (rep, cache_shardings)  # logits land replicated (host argmax)
        aspecs = (activation_specs if activation_specs is not None
                  else _sh.decode_specs(cfg, mesh, policy))

    def factory(mode: MorphMode):
        depth = mode.depth

        def step(p, cache, tokens, active):
            trace_counter["n"] += 1  # executes at trace time only
            if mesh is None:
                return decode_step(p, cache, tokens, cfg, depth=depth,
                                   active=active, fused=fused)
            # the context manager runs at trace time, which is when the
            # `constrain` calls inside decode_step consult it
            with _sh.activation_sharding(mesh, aspecs):
                return decode_step(p, cache, tokens, cfg, depth=depth,
                                   active=active, fused=fused)

        if mesh is None:
            return jax.jit(step, donate_argnums=(1,))
        return jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(1,))

    ctrl = MorphController(cfg, factory, modes, compile_key=lambda m: m.depth)
    ctrl.trace_counter = trace_counter
    ctrl.spec_plan = {}

    if paged_page_size:
        def paged_factory(depth: int, bucket: int):
            def step(p, cache, tokens, active, pages):
                trace_counter["n"] += 1  # executes at trace time only
                if mesh is None:
                    return decode_step(p, cache, tokens, cfg, depth=depth,
                                       active=active, pages=pages,
                                       page_size=paged_page_size, fused=fused)
                with _sh.activation_sharding(mesh, aspecs):
                    return decode_step(p, cache, tokens, cfg, depth=depth,
                                       active=active, pages=pages,
                                       page_size=paged_page_size, fused=fused)

            if mesh is None:
                return lambda: jax.jit(step, donate_argnums=(1,))
            pd_in = (param_shardings, cache_shardings, rep, active_sh, rep)
            return lambda: jax.jit(step, in_shardings=pd_in,
                                   out_shardings=out_sh, donate_argnums=(1,))

        ctrl.aux_builders["paged_decode"] = paged_factory
        for d in sorted({m.depth for m in ctrl.modes}):
            for b in paged_buckets:
                ctrl.register_aux(paged_decode_compile_key(d, b),
                                  paged_factory(d, b))

    if speculative is not None:
        # local import: repro.runtime's package init imports the serving
        # engine, which imports this module — a top-level import would cycle
        from repro.runtime import speculative as _spec

        plan = _spec.spec_plan([m.depth for m in ctrl.modes], speculative)
        ctrl.spec_plan = plan
        top_k = speculative.top_k
        if mesh is not None:
            # the multi-position verify pass needs its own (model-axis
            # replicated) activation pins — by-head propagation at (B, K+1)
            # shapes triggers the XLA CPU partitioner bug decode_specs
            # dodges. Pass batch-aware specs (executor knows the slot count)
            # to keep the batch dim data-sharded like the decode path.
            vspecs = (verify_activation_specs
                      if verify_activation_specs is not None
                      else _sh.verify_specs(cfg, mesh, policy))

        # paged serving appends one replicated traced operand (the page
        # table) to every speculative executable's signature
        pg_tail = (rep,) if (paged_page_size and mesh is not None) else ()

        def draft_factory(draft_depth: int, k: int):
            fn = _spec.make_draft_step(cfg, draft_depth, k, top_k,
                                       page_size=paged_page_size, fused=fused)

            def _run(args):
                trace_counter["n"] += 1  # executes at trace time only
                if mesh is None:
                    return fn(*args)
                with _sh.activation_sharding(mesh, aspecs):
                    return fn(*args)

            if paged_page_size:
                def step(p, cache, tok0, active, keys, temperature, step_ct,
                         pages):
                    return _run((p, cache, tok0, active, keys, temperature,
                                 step_ct, pages))
            else:
                def step(p, cache, tok0, active, keys, temperature, step_ct):
                    return _run((p, cache, tok0, active, keys, temperature,
                                 step_ct))

            if mesh is None:
                return lambda: jax.jit(step)
            d_in = (param_shardings, cache_shardings, rep, active_sh, rep,
                    rep, rep) + pg_tail
            return lambda: jax.jit(step, in_shardings=d_in,
                                   out_shardings=(rep, rep))

        def verify_factory(depth: int, k: int):
            fn = _spec.make_verify_step(cfg, depth, k, top_k,
                                        page_size=paged_page_size, fused=fused)

            def _run(args):
                trace_counter["n"] += 1  # executes at trace time only
                if mesh is None:
                    return fn(*args)
                with _sh.activation_sharding(mesh, vspecs):
                    return fn(*args)

            if paged_page_size:
                def step(p, cache, toks, dlogits, active, keys, temperature,
                         step_ct, pages):
                    return _run((p, cache, toks, dlogits, active, keys,
                                 temperature, step_ct, pages))
            else:
                def step(p, cache, toks, dlogits, active, keys, temperature,
                         step_ct):
                    return _run((p, cache, toks, dlogits, active, keys,
                                 temperature, step_ct))

            if mesh is None:
                return lambda: jax.jit(step, donate_argnums=(1,))
            v_in = (param_shardings, cache_shardings, rep, rep, active_sh,
                    rep, rep, rep) + pg_tail
            v_out = (rep, rep, cache_shardings)
            return lambda: jax.jit(step, in_shardings=v_in,
                                   out_shardings=v_out, donate_argnums=(1,))

        def tree_draft_factory(draft_depth: int, branching):
            fn = _spec.make_tree_draft_step(cfg, draft_depth, branching,
                                            top_k, page_size=paged_page_size,
                                            fused=fused)

            def _run(args):
                trace_counter["n"] += 1  # executes at trace time only
                if mesh is None:
                    return fn(*args)
                # tree drafting scores (B, n_nodes) multi-position passes
                # internally, so it needs the VERIFY pins, not the one-token
                # decode pins (same XLA CPU by-head bug class)
                with _sh.activation_sharding(mesh, vspecs):
                    return fn(*args)

            if paged_page_size:
                def step(p, cache, tok0, active, keys, temperature, step_ct,
                         pages):
                    return _run((p, cache, tok0, active, keys, temperature,
                                 step_ct, pages))
            else:
                def step(p, cache, tok0, active, keys, temperature, step_ct):
                    return _run((p, cache, tok0, active, keys, temperature,
                                 step_ct))

            if mesh is None:
                return lambda: jax.jit(step)
            d_in = (param_shardings, cache_shardings, rep, active_sh, rep,
                    rep, rep) + pg_tail
            return lambda: jax.jit(step, in_shardings=d_in,
                                   out_shardings=(rep, rep))

        def tree_verify_factory(depth: int, branching):
            fn = _spec.make_tree_verify_step(cfg, depth, branching, top_k,
                                             page_size=paged_page_size,
                                             fused=fused)

            def _run(args):
                trace_counter["n"] += 1  # executes at trace time only
                if mesh is None:
                    return fn(*args)
                with _sh.activation_sharding(mesh, vspecs):
                    return fn(*args)

            if paged_page_size:
                def step(p, cache, toks, dlogits, active, keys, temperature,
                         step_ct, pages):
                    return _run((p, cache, toks, dlogits, active, keys,
                                 temperature, step_ct, pages))
            else:
                def step(p, cache, toks, dlogits, active, keys, temperature,
                         step_ct):
                    return _run((p, cache, toks, dlogits, active, keys,
                                 temperature, step_ct))

            if mesh is None:
                return lambda: jax.jit(step, donate_argnums=(1,))
            v_in = (param_shardings, cache_shardings, rep, rep, active_sh,
                    rep, rep, rep) + pg_tail
            v_out = (rep, rep, cache_shardings)
            return lambda: jax.jit(step, in_shardings=v_in,
                                   out_shardings=v_out, donate_argnums=(1,))

        # expose the factory kinds so the autoscaler can build executables
        # for frontier points that were never warmed by hand — same closures,
        # same shardings, registered through publish_aux after a background
        # compile instead of register_aux at deploy time
        ctrl.aux_builders.update(
            draft=draft_factory, verify=verify_factory,
            tree_draft=tree_draft_factory, tree_verify=tree_verify_factory)
        draft_keys = sorted({(e.draft_depth, k)
                             for e in plan.values() for k in e.ks})
        for dd, k in draft_keys:
            ctrl.register_aux(_spec.draft_compile_key(dd, k),
                              draft_factory(dd, k))
        for e in plan.values():
            for k in e.ks:
                ctrl.register_aux(_spec.verify_compile_key(e.depth, k),
                                  verify_factory(e.depth, k))
        tree_draft_keys = sorted({(e.draft_depth, br)
                                  for e in plan.values() for br in e.trees})
        for dd, br in tree_draft_keys:
            ctrl.register_aux(_spec.tree_draft_compile_key(dd, br),
                              tree_draft_factory(dd, br))
        for e in plan.values():
            for br in e.trees:
                ctrl.register_aux(_spec.tree_verify_compile_key(e.depth, br),
                                  tree_verify_factory(e.depth, br))
    return ctrl


def policy_for_budget(cfg: ModelConfig, controller: MorphController,
                      latency_budget_s: float, est_latency: Callable[[MorphMode], float]) -> MorphMode:
    """Pick the most accurate mode fitting a latency budget (paper's runtime
    trade-off loop: accuracy vs latency/power under changing constraints).

    Modes are ranked by active-FLOPs fraction (proxy for accuracy retention,
    monotone under DistillCycle); the largest mode whose estimated latency
    fits is selected.
    """
    ranked = sorted(controller.modes, key=lambda m: elastic.flops_fraction(cfg, m))
    best = ranked[0]
    for m in ranked:
        if est_latency(m) <= latency_budget_s:
            best = m
    return best
