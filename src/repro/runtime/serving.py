"""Continuous-batching NeuroMorph serving engine — sharded, single-executable.

The paper's runtime story is on-the-fly reconfiguration under live traffic:
NeuroMorph flips clock gates while inference requests keep arriving, and a
mode switch costs nothing because nothing is reprogrammed. This engine is
the TPU analogue of that story end-to-end:

* **Request queue + slot admission.** Requests arrive (e.g. from a Poisson
  trace), wait in a two-level priority queue (``interactive`` before
  ``batch`` — ``Request.slo_class``), and are admitted into free batch slots
  *every step* — no waiting for the whole batch to drain (continuous
  batching). Each slot is an independent request at its own sequence offset,
  carried by the per-slot decode state in ``models.model``. A whole
  admission burst is rewound with ONE jitted ``reset_cache_slots`` call (a
  (n_slots,) bool mask), so admission cost does not scale with burst size.

* **Per-DEPTH slot groups; width is per-slot data.** Depth changes the
  decode scan's trip count, so each distinct depth is one compiled
  executable and one slot group with one full-width cache. Width does NOT
  fragment slots: every slot carries its own width fraction, lowered each
  tick to per-slot active-dim vectors (``elastic.active_widths_batch``) that
  ``kernels.morph_matmul`` reads from scalar prefetch — out-of-width tiles
  issue no MXU work. A tick with three widths in flight at one depth issues
  ONE decode launch, not three; warmup compiles ``len(depths)`` executables,
  not ``len(modes)``. A mode switch still only applies to *newly admitted*
  requests — in-flight slots keep the width they started with.

* **Executor seam: host-local or mesh-sharded, same engine.** All device
  decisions go through an executor. ``LocalExecutor`` is the host-local
  reference; ``MeshExecutor`` compiles the same per-depth executables SPMD
  under a TP/DP mesh (``launch.mesh.make_serve_mesh``): params placed once
  by ``sharding.param_specs`` under a ``serve_tp``/``serve_2d`` policy,
  per-slot caches sharded by ``sharding.serve_cache_specs``, decode
  activations constrained via ``sharding.decode_specs``, and tokens /
  runtime-width ``active`` scalars broadcast as replicated operands. Slot
  resets and prefill adoption stay device-side (donated, sharded in and
  out) — no gathers on the admission path. Sharded decode generates
  token-identical output to the local path (logits match to float tolerance
  — collective reduction order moves the last bits) and re-traces nothing
  after warmup.

* **Prefill admission.** Prompts at least ``prefill_threshold`` tokens long
  are consumed in ONE ``models.model.prefill(per_slot=True, slot=...,
  n_slots=...)`` call (compiled per (prompt_len, depth), ``slot`` traced)
  whose engine-layout cache is adopted into the slot device-side
  (``adopt_cache_slot``) — instead of feeding the prompt token by token
  through the decode path. Prompt-consume latency is tracked separately
  (``prefill_s`` / ``prefill_prompt_tokens``).

* **Self-speculative decoding — linear and token-tree drafts.** With
  ``speculative=SpecConfig(...)`` each depth group that has a shallower
  DistillCycle exit drafts candidates at that exit (one cheap launch; the
  committed cache is read, never written) and verifies every candidate in
  ONE full-depth launch that also commits the accepted tokens device-side
  (``runtime.speculative``). Linear drafts chain K tokens; token-tree
  drafts (``SpecConfig.trees``, SpecInfer-style static branching schedules
  like ``(3, 2, 1)``) sample sibling candidates per level so one verify
  launch scores many continuations at once — ancestor-mask attention over
  the flattened tree, per-node SSM state candidates, and a traced
  path-index gather committing the accepted root-to-leaf path. Tree
  drafting is NON-destructive: levels are scored by read-only
  ``verify_tree`` passes at the draft depth, so no transient cache copy
  rides a scan carry. The emitted stream is distribution-identical to
  plain stepping — exactly token-identical under greedy — while accepted
  drafts turn one verify launch into several tokens. Acceptance telemetry
  (``spec_telemetry``: accept rate, accepted and tokens per launch) feeds
  the SLO policy's per-class draft-shape choice (``choose_tree``: tree vs
  linear K vs plain), and a rolling-window acceptance collapse falls the
  group back to plain stepping for a cooloff (``spec_fallback_log``).
  Slots still feeding multi-token prompts tick plainly until the group is
  all-generative; mixed widths ride speculative launches unchanged.

* **SLO-driven morph policy.** ``SLOPolicy`` picks the widest/deepest mode
  whose predicted step latency fits the current latency budget. The
  prediction starts from ``core.neuroforge.analytical.estimate`` at the
  executor's actual ``DesignPoint(dp, tp)`` (the paper's Eq. 4/10-style
  pre-deployment model, multi-chip aware) and is corrected online by the
  controller's measured per-mode telemetry — analytical ordering, measured
  magnitude, sharded where the engine is sharded. ``choose`` additionally
  weighs per-class queue depth against the estimate: a deep queue squeezes
  the effective budget, biasing admission toward shallower/narrower modes
  (and smaller K) that drain backlog — decision inputs are recorded per
  admission switch (``admission_decision_log``).

Slot re-admission relies on position masking (attention) and explicit state
zeroing (SSM) via ``reset_cache_slots``; both are jitted once per cache
structure, so sustained mixed traffic — including arbitrary width churn —
triggers no compilation at all (``ctrl.trace_counter`` measures this).
``decode_launches`` vs ``per_mode_launch_equiv`` quantifies the win over the
old per-(depth, width) grouping.
"""
from __future__ import annotations

import copy
import statistics
import time
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, MorphMode, ShapeCell
from repro.core import elastic
from repro.core.morph import (MorphController, make_serve_controller,
                              paged_decode_compile_key, policy_for_budget)
from repro.core.neuroforge.analytical import estimate, estimate_mode
from repro.core.neuroforge.hw import V5E, HardwareSpec
from repro.core.neuroforge.space import DesignPoint
from repro.kernels.fused_decode import default_impl as fused_impl
from repro.models.model import (adopt_cache_slot, commit_verify,
                                init_decode_cache, prefill,
                                reset_cache_slots, verify_step)
from repro.models.paged import (PagedLayout, adopt_paged_slot, copy_page,
                                init_paged_cache)
from repro.parallel import sharding as SH
from repro.runtime import sampling
from repro.runtime.observability import (MetricsRegistry, Observability,
                                         _TupleView)
from repro.runtime.paged_cache import BlockAllocator, RadixCache
from repro.runtime.speculative import (SpecConfig, SpecTelemetry,
                                       draft_compile_key,
                                       expected_tokens_per_launch,
                                       expected_tokens_per_tree_launch,
                                       per_candidate_accept_rate,
                                       tree_draft_compile_key,
                                       tree_node_budget,
                                       tree_verify_compile_key,
                                       verify_compile_key)


SLO_CLASSES = ("interactive", "batch")


def _shape_label(shape) -> str:
    """Draft-shape label: ``k3`` linear lengths, ``t3x2x1`` tree schedules."""
    if isinstance(shape, tuple):
        return "t" + "x".join(str(b) for b in shape)
    return f"k{shape}"


# ---------------------------------------------------------------------------
# requests and traces
# ---------------------------------------------------------------------------


@dataclass
class Request:
    """One inference request: feed ``prompt`` then generate ``max_new_tokens``."""

    rid: int
    prompt: Tuple[int, ...]
    max_new_tokens: int
    arrival_s: float = 0.0
    slo_class: str = "batch"  # "interactive" admits ahead of "batch"
    # absolute deadline: still queued past this instant -> retired with the
    # terminal "expired" status instead of starving silently (None = no TTL)
    deadline_s: Optional[float] = None
    # runtime state (engine-owned)
    generated: List[int] = field(default_factory=list)
    fed: int = 0  # tokens fed so far (prompt + generated)
    mode_name: str = ""
    admitted_step: int = -1
    finished_s: float = -1.0
    status: str = "queued"  # queued | active | done | expired
    # admitted through the compiled prefill path (vs token-by-token feed);
    # snapshot replay must rebuild the slot through the SAME path
    prefilled: bool = False

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens

    def next_input(self) -> int:
        """Token to feed this step: prompt first, then the last sample."""
        if self.fed < len(self.prompt):
            return self.prompt[self.fed]
        return self.generated[-1] if self.generated else self.prompt[-1]


def poisson_trace(n_requests: int, rate_per_s: float, *, seed: int = 0,
                  prompt_len: Tuple[int, int] = (1, 4),
                  new_tokens: Tuple[int, int] = (4, 12),
                  vocab: int = 256,
                  interactive_frac: float = 0.0) -> List[Request]:
    """Poisson arrivals with uniform prompt/output lengths (open-loop trace).

    ``interactive_frac`` of the requests (chosen i.i.d.) carry the
    ``interactive`` SLO class; the rest are ``batch``.
    """
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for i in range(n_requests):
        t += float(rng.exponential(1.0 / rate_per_s))
        plen = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        out.append(Request(
            rid=i,
            prompt=tuple(int(x) for x in rng.integers(1, vocab, plen)),
            max_new_tokens=int(rng.integers(new_tokens[0], new_tokens[1] + 1)),
            arrival_s=t,
            slo_class=("interactive" if rng.random() < interactive_frac
                       else "batch"),
        ))
    return out


# ---------------------------------------------------------------------------
# SLO-driven morph policy
# ---------------------------------------------------------------------------


class SLOPolicy:
    """Pick the widest mode whose predicted step latency fits the budget.

    Prediction = analytical roofline estimate (``neuroforge.analytical``) at
    the serving deployment's actual parallel degrees (``DesignPoint(dp,
    tp)`` — multi-chip latencies, not single-chip fiction) scaled by an
    online correction learned from the controller's per-mode telemetry.
    Before any traffic the analytical model alone ranks the modes (it is
    exact in *ordering*: narrower/shallower modes do strictly less work);
    once a mode has ``min_samples`` measured steps its own p50 is used
    directly, and the measured/analytical ratio of observed modes corrects
    the still-unobserved ones — under a mesh the measurements are of the
    sharded executables, so the correction absorbs real collective costs the
    estimate only approximates.
    """

    def __init__(self, cfg: ModelConfig, controller: MorphController, *,
                 batch_size: int, cache_capacity: int,
                 hw: HardwareSpec = V5E, min_samples: int = 3,
                 dp: int = 1, tp: int = 1, queue_gamma: float = 0.25,
                 interactive_weight: float = 2.0,
                 metrics: Optional[MetricsRegistry] = None,
                 catchup_ticks: int = 8, catchup_gamma: float = 1.0):
        self.cfg = cfg
        self.controller = controller
        self.min_samples = min_samples
        self.batch_size = batch_size
        # budget-aware admission: how strongly queue depth squeezes the
        # effective latency budget (0 disables), and how much heavier a
        # queued interactive request weighs than a batch one
        self.queue_gamma = queue_gamma
        self.interactive_weight = interactive_weight
        # post-failover catch-up: for ``catchup_ticks`` choose() calls after
        # a failover the effective budget is squeezed by the measured
        # recovery latency (``failover_recovery_ms`` histogram p50 on
        # ``metrics``), downshifting width while the engine re-earns the
        # latency the recovery cost its in-flight requests
        self.metrics = metrics
        self.catchup_ticks = catchup_ticks
        self.catchup_gamma = catchup_gamma
        self._catchup_left = 0
        self._last_recovery_ms = 0.0
        # inputs of the most recent choose() call, for admission-switch logs
        self.last_decision: Dict[str, float] = {}
        cell = ShapeCell("serve_step", seq_len=cache_capacity,
                         global_batch=batch_size, kind="decode")
        pt = DesignPoint(dp=dp, tp=tp, microbatches=1, remat="none",
                         param_dtype=cfg.param_dtype
                         if cfg.param_dtype in ("bfloat16", "float32") else "bfloat16",
                         moment_dtype="float32", grad_comm="allreduce",
                         kv_quant=cfg.kv_quant, attn_chunk=cfg.attn_chunk,
                         capacity_factor=cfg.capacity_factor, width=1.0)
        self.design_point = pt
        self._cell = cell
        self._hw = hw
        self.analytical: Dict[str, float] = {}
        for m in controller.modes:
            self.analytical[m.name] = self._analytical_for(m)

    def _analytical_for(self, mode: MorphMode) -> float:
        """Analytical latency for a mode, computed lazily and cached — modes
        registered after construction (the autoscaler's frontier points) must
        not KeyError."""
        a = self.analytical.get(mode.name)
        if a is None:
            a = estimate_mode(self.cfg, self._cell, self.design_point,
                              depth=mode.depth, width=mode.width,
                              hw=self._hw).latency_s
            self.analytical[mode.name] = a
        return a

    def _correction(self) -> float:
        ratios = []
        for name, t in self.controller.telemetry.items():
            a = self.analytical.get(name, 0.0)
            if t.steps >= self.min_samples and a > 0:
                ratios.append(t.p50_s / a)
        return statistics.median(ratios) if ratios else 1.0

    def est_latency(self, mode: MorphMode) -> float:
        t = self.controller.telemetry.get(mode.name)
        if t is not None and t.steps >= self.min_samples:
            return t.p50_s
        return self._analytical_for(mode) * self._correction()

    def _queue_pressure(self, queue_depths: Optional[Dict[str, int]]) -> float:
        """Weighted queued-request count per batch slot (0 = empty queue)."""
        if not queue_depths:
            return 0.0
        w = sum((self.interactive_weight if c == "interactive" else 1.0) * n
                for c, n in queue_depths.items())
        return w / max(self.batch_size, 1)

    def choose(self, budget_s: float,
               queue_depths: Optional[Dict[str, int]] = None) -> MorphMode:
        """Pick the admission mode for a latency budget, weighed against the
        queue. A deep queue means admitted requests also pay queueing delay,
        so the *effective* per-step budget shrinks —
        ``budget / (1 + queue_gamma * pressure)`` — biasing admission toward
        shallower/narrower modes that drain the backlog faster (the paper's
        latency-vs-throughput dual objective, applied at admission time).
        The decision inputs land in ``last_decision`` so the engine can log
        them on every admission switch.

        During post-failover catch-up (``note_failover``) the budget is
        squeezed further by the measured recovery latency amortized over the
        catch-up window: recovery stole ``recovery_ms`` of serving time, so
        the next ``catchup_ticks`` decisions act as if each tick owed back
        its share — biasing toward narrower/shallower modes until the debt
        drains. The penalty is recorded in ``last_decision`` and, when a
        registry is attached, as an ``slo_catchup`` structured event.
        """
        pressure = self._queue_pressure(queue_depths)
        eff = budget_s / (1.0 + self.queue_gamma * pressure)
        catchup_penalty = 0.0
        if self._catchup_left > 0 and budget_s > 0:
            debt_s = self._last_recovery_ms / 1e3 / max(self.catchup_ticks, 1)
            catchup_penalty = min(self.catchup_gamma * debt_s / budget_s, 4.0)
            eff /= (1.0 + catchup_penalty)
            self._catchup_left -= 1
        mode = policy_for_budget(self.cfg, self.controller, eff,
                                 self.est_latency)
        self.last_decision = {
            "budget_s": budget_s, "effective_budget_s": eff,
            "queue_pressure": pressure, "mode": mode.name,
            "catchup_penalty": catchup_penalty,
            "queued_interactive": (queue_depths or {}).get("interactive", 0),
            "queued_batch": (queue_depths or {}).get("batch", 0),
        }
        if catchup_penalty > 0 and self.metrics is not None:
            self.metrics.events(
                "slo_catchup",
                ("budget_s", "effective_budget_s", "catchup_penalty",
                 "recovery_ms", "ticks_left", "mode"),
            ).emit(budget_s=budget_s, effective_budget_s=eff,
                   catchup_penalty=catchup_penalty,
                   recovery_ms=self._last_recovery_ms,
                   ticks_left=self._catchup_left, mode=mode.name)
        return mode

    def note_failover(self, recovery_ms: Optional[float] = None) -> None:
        """Start a catch-up window after an executor failover.

        ``recovery_ms`` defaults to the ``failover_recovery_ms`` histogram
        p50 on the attached registry — the supervisor records every recovery
        there, so the policy reacts to the *typical* measured cost, not just
        the last one.
        """
        if recovery_ms is None and self.metrics is not None:
            h = self.metrics.histograms.get("failover_recovery_ms")
            if h is not None and h.count:
                recovery_ms = h.p50
        self._last_recovery_ms = float(recovery_ms or 0.0)
        self._catchup_left = self.catchup_ticks if self._last_recovery_ms > 0 \
            else 0

    def choose_spec_k(self, ks: Sequence[int], accept_rate: float,
                      queue_depths: Optional[Dict[str, int]] = None) -> int:
        """Pick the draft length K from the compiled table.

        Ranks each K by expected tokens per verify launch at the measured
        acceptance rate (``expected_tokens_per_launch``) per unit of drafted
        work, then applies queue pressure: a deep queue biases toward smaller
        K — rejected drafts burn launches that queued requests could have
        used. With an empty queue the largest K whose marginal token gain is
        still positive wins.
        """
        ks = sorted(set(ks))
        pressure = self._queue_pressure(queue_depths)
        # marginal value of draft position j is accept_rate^j; keep positions
        # whose expected yield beats the pressure-scaled cost of drafting
        cut = self.queue_gamma * pressure / (1.0 + self.queue_gamma * pressure)
        best = ks[0]
        for k in ks:
            gain = expected_tokens_per_launch(accept_rate, k)
            prev = expected_tokens_per_launch(accept_rate, best)
            if gain - prev > cut * (k - best) / max(max(ks), 1):
                best = k
        return best

    def choose_tree(self, trees: Sequence[Tuple[int, ...]],
                    ks: Sequence[int], accept_rate: float,
                    queue_depths: Optional[Dict[str, int]] = None,
                    min_accept_rate: float = 0.05) -> Tuple[str, object]:
        """Pick the draft shape for the next speculative launches: a token
        tree from the compiled ``trees`` table, a linear K from ``ks``, or
        plain stepping.

        Every candidate is ranked by expected tokens per verify launch at
        the measured acceptance rate (``expected_tokens_per_tree_launch``
        generalizes the linear estimate: a level with b sibling candidates
        survives with prob 1 - (1-a)^b) minus a queue-pressure-scaled node
        cost — under backlog, wide trees burn verify FLOPs queued requests
        could have used, so pressure shrinks the chosen tree exactly as it
        shrinks linear K. When acceptance collapses below
        ``min_accept_rate`` every draft shape is expected waste: the policy
        falls back to ``("plain", None)`` and the engine's cooloff/retune
        loop re-probes later.

        Returns ``("tree", branching)``, ``("linear", k)``, or
        ``("plain", None)``.
        """
        if accept_rate < min_accept_rate:
            return ("plain", None)
        cands: List[Tuple[str, object, int]] = \
            [("linear", k, k) for k in sorted(set(ks))] + \
            [("tree", tuple(br), tree_node_budget(br)) for br in trees]
        if not cands:
            return ("plain", None)
        pressure = self._queue_pressure(queue_depths)
        cut = self.queue_gamma * pressure / (1.0 + self.queue_gamma * pressure)
        max_nodes = max(nodes for _, _, nodes in cands)

        def value(kind, shape, nodes):
            if kind == "linear":
                e = expected_tokens_per_launch(accept_rate, shape)
            else:
                e = expected_tokens_per_tree_launch(accept_rate, shape)
            return e - cut * nodes / max(max_nodes, 1)

        best = max(cands, key=lambda c: value(*c))
        return (best[0], best[1])


# ---------------------------------------------------------------------------
# executor seam — where device placement and compilation decisions live
# ---------------------------------------------------------------------------


class LocalExecutor:
    """Host-local execution backend (single default device).

    The engine delegates every device decision to its executor: parameter
    placement, per-depth controller compilation, cache allocation, and the
    jitted cache-side ops (batched slot reset, prefill, prefill adoption).
    ``MeshExecutor`` overrides each with NamedSharding-annotated variants —
    engine code never branches on mesh-ness.
    """

    mesh = None
    policy = "local"
    dp = 1
    tp = 1
    # launch seam: one hook wraps all five launch boundaries ("decode",
    # "paged_decode", "verify", "tree_verify", "prefill"). The
    # ``ExecutorSupervisor`` installs chaos injection here and the engine's
    # trace recorder observes the same announcements — a chaos plan (or a
    # real health check) can convert any site into an executor loss the
    # supervisor recovers from, and tracing sees exactly the launches the
    # failure model covers.
    launch_hook: Optional[Callable[[str], None]] = None

    def launch(self, site: str) -> None:
        """Announce a launch boundary to the installed hook, if any.
        Raising from the hook simulates the executor dying before that
        launch ran."""
        if self.launch_hook is not None:
            self.launch_hook(site)

    # back-compat aliases: the seam predates the unified hook name
    @property
    def failure_hook(self) -> Optional[Callable[[str], None]]:
        return self.launch_hook

    @failure_hook.setter
    def failure_hook(self, fn: Optional[Callable[[str], None]]) -> None:
        self.launch_hook = fn

    def check_failure(self, site: str) -> None:
        self.launch(site)

    def bind(self, cfg: ModelConfig, batch_size: int, cache_capacity: int,
             paged: Optional[PagedLayout] = None,
             fused: bool = False) -> "LocalExecutor":
        self._cfg = cfg
        self._batch = batch_size
        self._cap = cache_capacity
        self._paged = paged
        self._fused = fused
        return self

    # -- placement ----------------------------------------------------------

    def place_params(self, params):
        return params

    def put(self, x):
        """Small replicated operand (tokens / active widths / reset masks)."""
        return jnp.asarray(x)

    # -- compiled ops -------------------------------------------------------

    def _paged_kwargs(self, cfg: ModelConfig) -> Dict:
        if self._paged is None:
            return {}
        return dict(paged_page_size=self._paged.page_size,
                    paged_buckets=self._paged.buckets(cfg, self._cap))

    def make_controller(self, params, cfg: ModelConfig, modes,
                        speculative: Optional[SpecConfig] = None) -> MorphController:
        return make_serve_controller(params, cfg, modes,
                                     speculative=speculative,
                                     fused=self._fused,
                                     **self._paged_kwargs(cfg))

    def init_cache(self):
        if self._paged is not None:
            return init_paged_cache(self._cfg, self._batch, self._cap,
                                    self._paged)
        return init_decode_cache(self._cfg, self._batch, self._cap,
                                 per_slot=True)

    def reset_fn(self):
        # donate the cache: a burst reset must be an in-place write, not a
        # full cache copy, on the admission hot path
        return jax.jit(reset_cache_slots, donate_argnums=(0,))

    def adopt_fn(self):
        return jax.jit(adopt_cache_slot, donate_argnums=(0,))

    def prefill_fn(self, prompt_len: int, depth: int):
        """Compiled whole-prompt consume: (params, (1, L) tokens, slot) ->
        (last-token logits, engine-layout cache with only ``slot`` live)."""
        cfg, cap, n_slots = self._cfg, self._cap, self._batch

        def pf(params, tokens, slot):
            return prefill(params, {"tokens": tokens}, cfg,
                           cache_extra=cap - prompt_len, per_slot=True,
                           slot=slot, n_slots=n_slots, depth=depth)

        return jax.jit(pf)

    def prefill_adopt_fn(self, prompt_len: int, depth: int, ncp: int):
        """Fused whole-prompt consume + paged adoption: (params, (1, L)
        tokens, slot, cache, (ncp,) physical pages, (ncp,) write mask) ->
        (last-token logits, cache with the prompt scattered into the pool).
        The prefill runs over ``ncp * page_size`` positions; pages masked
        False are already resident via the shared-prefix radix and are NOT
        rewritten (that is what lets one block back many slots)."""
        cfg, n_slots = self._cfg, self._batch
        ps = self._paged.page_size

        def pf(params, tokens, slot, cache, pages, wmask):
            logits, pre = prefill(params, {"tokens": tokens}, cfg,
                                  cache_extra=max(ncp * ps - prompt_len, 0),
                                  per_slot=True, slot=slot, n_slots=n_slots,
                                  depth=depth)
            return logits, adopt_paged_slot(cache, pre, slot, pages, wmask,
                                            ps)

        return jax.jit(pf, donate_argnums=(3,))

    def copy_page_fn(self):
        """Jitted copy-on-write page copy (src/dst are traced scalars)."""
        return jax.jit(copy_page, donate_argnums=(0,))

    def replay_chunk_fn(self, depth: int, n_tokens: int):
        """Compiled multi-token replay: (params, cache, (B, C) committed
        tokens, active) -> cache advanced by C positions on every slot.

        One ``verify_step`` scores all C positions and ``commit_verify``
        force-accepts them (``n_accepted = C - 1``): by the verify path's
        exactness property the cache lands bit-identical to C sequential
        decode launches, in ONE launch instead of C.
        """
        cfg, fused = self._cfg, self._fused
        paged = self._paged

        if paged is None:
            def chunk(params, cache, tokens, active):
                _, pending = verify_step(params, cache, tokens, cfg,
                                         depth=depth, active=active,
                                         fused=fused)
                n_acc = jnp.full((tokens.shape[0],), n_tokens - 1, jnp.int32)
                return commit_verify(cache, pending, n_acc, cfg)

            return jax.jit(chunk, donate_argnums=(1,))

        ps = paged.page_size

        def chunk(params, cache, tokens, active, pages):
            _, pending = verify_step(params, cache, tokens, cfg,
                                     depth=depth, active=active,
                                     pages=pages, page_size=ps, fused=fused)
            n_acc = jnp.full((tokens.shape[0],), n_tokens - 1, jnp.int32)
            return commit_verify(cache, pending, n_acc, cfg, pages=pages,
                                 page_size=ps)

        return jax.jit(chunk, donate_argnums=(1,))


class MeshExecutor(LocalExecutor):
    """SPMD execution backend: the same ops, compiled under a TP/DP mesh.

    ``policy`` defaults to ``sharding.serve_policy(cfg, tp)`` (weight
    footprint decides ``serve_tp`` vs ``serve_2d``). Params are placed once
    (``param_specs``), per-slot caches live sharded (``serve_cache_specs``)
    and are donated through step/reset/adopt so slot churn never gathers,
    and decode activations are pinned by ``decode_specs`` inside the
    compiled step.
    """

    def __init__(self, mesh, policy: Optional[str] = None):
        self.mesh = mesh
        self._policy_arg = policy
        self.tp = dict(mesh.shape).get("model", 1)
        self.dp = 1
        for a in SH.data_axes(mesh):
            self.dp *= mesh.shape[a]
        self._rep = NamedSharding(mesh, P())

    def bind(self, cfg: ModelConfig, batch_size: int, cache_capacity: int,
             paged: Optional[PagedLayout] = None,
             fused: bool = False) -> "MeshExecutor":
        super().bind(cfg, batch_size, cache_capacity, paged=paged,
                     fused=fused)
        if fused and self.mesh.size > 1 and fused_impl() == "pallas":
            # the compiler cannot partition a Mosaic kernel, and the
            # superkernel reads a slot's whole KV sequence, which the mesh
            # splits over the model axis
            raise ValueError("the fused decode superkernel runs on one "
                             "device; serve a multi-device mesh with "
                             "fused=False")
        self.policy = self._policy_arg or SH.serve_policy(cfg, self.tp)
        if paged is not None:
            cstruct = jax.eval_shape(
                lambda: init_paged_cache(cfg, batch_size, cache_capacity,
                                         paged))
        else:
            cstruct = jax.eval_shape(
                lambda: init_decode_cache(cfg, batch_size, cache_capacity,
                                          per_slot=True))
        cspecs = SH.serve_cache_specs(cstruct, cfg, self.mesh, self.policy,
                                      paged=paged is not None)
        self._cache_sh = SH.shardings_for(cspecs, self.mesh)
        self._aspecs = SH.decode_specs(cfg, self.mesh, self.policy, batch_size)
        self._vspecs = SH.verify_specs(cfg, self.mesh, self.policy, batch_size)
        self._param_sh = None
        return self

    def place_params(self, params):
        self._param_sh = SH.shardings_for(
            SH.param_specs(params, self._cfg, self.mesh, self.policy),
            self.mesh)
        return jax.device_put(params, self._param_sh)

    def put(self, x):
        return jax.device_put(jnp.asarray(x), self._rep)

    def make_controller(self, params, cfg: ModelConfig, modes,
                        speculative: Optional[SpecConfig] = None) -> MorphController:
        return make_serve_controller(
            params, cfg, modes, mesh=self.mesh, policy=self.policy,
            param_shardings=self._param_sh, cache_shardings=self._cache_sh,
            activation_specs=self._aspecs,
            verify_activation_specs=self._vspecs, speculative=speculative,
            fused=self._fused, **self._paged_kwargs(cfg))

    def init_cache(self):
        cfg, batch, cap = self._cfg, self._batch, self._cap
        # born sharded: no host round-trip for multi-GB caches
        if self._paged is not None:
            layout = self._paged
            return jax.jit(
                lambda: init_paged_cache(cfg, batch, cap, layout),
                out_shardings=self._cache_sh)()
        return jax.jit(
            lambda: init_decode_cache(cfg, batch, cap, per_slot=True),
            out_shardings=self._cache_sh)()

    def reset_fn(self):
        return jax.jit(reset_cache_slots,
                       in_shardings=(self._cache_sh, self._rep),
                       out_shardings=self._cache_sh, donate_argnums=(0,))

    def adopt_fn(self):
        return jax.jit(adopt_cache_slot,
                       in_shardings=(self._cache_sh, self._cache_sh, self._rep),
                       out_shardings=self._cache_sh, donate_argnums=(0,))

    def prefill_fn(self, prompt_len: int, depth: int):
        cfg, cap, n_slots = self._cfg, self._cap, self._batch
        mesh = self.mesh
        # the prompt pass runs batch-1: same by-head/channel pinning as the
        # decode step, but never sharded over the batch dim (batch=None)
        aspecs = SH.decode_specs(cfg, mesh, self.policy)

        def pf(params, tokens, slot):
            with SH.activation_sharding(mesh, aspecs):
                return prefill(params, {"tokens": tokens}, cfg,
                               cache_extra=cap - prompt_len, per_slot=True,
                               slot=slot, n_slots=n_slots, depth=depth)

        return jax.jit(pf,
                       in_shardings=(self._param_sh, self._rep, self._rep),
                       out_shardings=(self._rep, self._cache_sh))

    def prefill_adopt_fn(self, prompt_len: int, depth: int, ncp: int):
        cfg, n_slots = self._cfg, self._batch
        ps = self._paged.page_size
        mesh = self.mesh
        aspecs = SH.decode_specs(cfg, mesh, self.policy)

        def pf(params, tokens, slot, cache, pages, wmask):
            with SH.activation_sharding(mesh, aspecs):
                logits, pre = prefill(params, {"tokens": tokens}, cfg,
                                      cache_extra=max(ncp * ps - prompt_len, 0),
                                      per_slot=True, slot=slot,
                                      n_slots=n_slots, depth=depth)
            return logits, adopt_paged_slot(cache, pre, slot, pages, wmask,
                                            ps)

        return jax.jit(pf,
                       in_shardings=(self._param_sh, self._rep, self._rep,
                                     self._cache_sh, self._rep, self._rep),
                       out_shardings=(self._rep, self._cache_sh),
                       donate_argnums=(3,))

    def copy_page_fn(self):
        return jax.jit(copy_page,
                       in_shardings=(self._cache_sh, self._rep, self._rep),
                       out_shardings=self._cache_sh, donate_argnums=(0,))

    def replay_chunk_fn(self, depth: int, n_tokens: int):
        cfg, fused = self._cfg, self._fused
        paged = self._paged
        mesh = self.mesh
        vspecs = self._vspecs

        if paged is None:
            def chunk(params, cache, tokens, active):
                with SH.activation_sharding(mesh, vspecs):
                    _, pending = verify_step(params, cache, tokens, cfg,
                                             depth=depth, active=active,
                                             fused=fused)
                    n_acc = jnp.full((tokens.shape[0],), n_tokens - 1,
                                     jnp.int32)
                    return commit_verify(cache, pending, n_acc, cfg)

            return jax.jit(chunk,
                           in_shardings=(self._param_sh, self._cache_sh,
                                         self._rep, self._rep),
                           out_shardings=self._cache_sh, donate_argnums=(1,))

        ps = paged.page_size

        def chunk(params, cache, tokens, active, pages):
            with SH.activation_sharding(mesh, vspecs):
                _, pending = verify_step(params, cache, tokens, cfg,
                                         depth=depth, active=active,
                                         pages=pages, page_size=ps,
                                         fused=fused)
                n_acc = jnp.full((tokens.shape[0],), n_tokens - 1, jnp.int32)
                return commit_verify(cache, pending, n_acc, cfg, pages=pages,
                                     page_size=ps)

        return jax.jit(chunk,
                       in_shardings=(self._param_sh, self._cache_sh,
                                     self._rep, self._rep, self._rep),
                       out_shardings=self._cache_sh, donate_argnums=(1,))


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class _GroupPaging:
    """Host-side page bookkeeping for ONE depth group's paged cache.

    Owns the group's ``BlockAllocator`` (free list + refcounts over the
    physical pool), the ``(n_slots, cap_pages)`` page table shipped to every
    launch, a host mirror of the device position counter (``host_pos`` — the
    engine advances it exactly as the executables advance ``cache['pos']``),
    and — full attention only — the shared-prefix ``RadixCache`` plus one
    permanently-owned scratch page per slot that free slots' table rows point
    at (whole-batch launches write their garbage somewhere harmless).

    Sliding-window groups are ``fixed``: the rolling buffer is always
    ``window // page_size`` pages, so every slot permanently owns its pages —
    no allocator churn, no prefix sharing (the window overwrites pages), no
    scratch.
    """

    def __init__(self, layout: PagedLayout, cfg: ModelConfig, n_slots: int,
                 capacity: int):
        self.layout = layout
        self.ps = layout.page_size
        self.cap_pages = layout.cap_pages(cfg, capacity)
        self.fixed = bool(cfg.sliding_window)
        self.n_slots = n_slots
        self.alloc = BlockAllocator(layout.pool_pages(cfg, n_slots, capacity))
        self.table = np.zeros((n_slots, self.cap_pages), np.int32)
        self.host_pos = np.zeros((n_slots,), np.int64)
        self.pages: List[List[int]] = [[] for _ in range(n_slots)]
        self.scratch: List[int] = []
        self.radix: Optional[RadixCache] = None
        # admission control: worst-case page reservation per slot, booked
        # when a request is admitted and released with the slot — admissions
        # that would overbook the pool are deferred (backpressure) instead
        # of hitting the mid-flight exhaustion hard error
        self.budget: List[int] = [0] * n_slots
        self.budgeted = 0
        if self.fixed:
            for i in range(n_slots):
                self.pages[i] = [self.alloc.alloc()
                                 for _ in range(self.cap_pages)]
                self.table[i, :] = self.pages[i]
        else:
            self.radix = RadixCache(self.alloc)
            for i in range(n_slots):
                s = self.alloc.alloc()
                self.scratch.append(s)
                self.table[i, :] = s

    def _alloc_page(self) -> int:
        """Allocate one page, evicting LRU radix prefixes if the pool is dry.

        Evicting a node only frees its page when no live slot still maps it,
        so the loop keeps evicting until a page actually frees or the tree
        runs out — then exhaustion is a hard error (live slots alone exceed
        the pool)."""
        while not self.alloc.can_alloc():
            if self.radix is None or not self.radix.evict_lru(1):
                raise RuntimeError(
                    "kv page pool exhausted: live slots reference every "
                    "page (raise --kv-pages or lower concurrency)")
        return self.alloc.alloc()

    def ensure_slot(self, i: int, last_pos: int) -> None:
        """Grow slot ``i``'s mapping to cover a write at ``last_pos``."""
        if self.fixed:
            return
        need = min(last_pos // self.ps + 1, self.cap_pages)
        while len(self.pages[i]) < need:
            p = self._alloc_page()
            self.table[i, len(self.pages[i])] = p
            self.pages[i].append(p)

    @property
    def reservable(self) -> int:
        """Pages admissions may budget against: the pool minus scratch.

        Radix-held pages are NOT subtracted — eviction reclaims them on
        demand, so they are slack, not commitment.
        """
        return self.alloc.n_pages - len(self.scratch)

    def can_reserve(self, need: int) -> bool:
        return self.fixed or self.budgeted + need <= self.reservable

    def reserve(self, i: int, need: int) -> None:
        """Book slot ``i``'s worst-case page demand against the pool."""
        if self.fixed:
            return  # fixed groups permanently own their pages
        self.budgeted += need - self.budget[i]
        self.budget[i] = need

    def release(self, i: int) -> None:
        """Drop slot ``i``'s references; its table row falls back to scratch."""
        self.host_pos[i] = 0
        self.budgeted -= self.budget[i]
        self.budget[i] = 0
        if self.fixed:
            return
        for p in self.pages[i]:
            self.alloc.decref(p)
        self.pages[i] = []
        self.table[i, :] = self.scratch[i]

    def trim(self, i: int) -> None:
        """Free tail pages past the committed position (speculative rollback:
        pages grown for rejected draft positions go back to the pool)."""
        if self.fixed:
            return
        keep = min(int(self.host_pos[i]) // self.ps + 1, self.cap_pages)
        while len(self.pages[i]) > keep:
            p = self.pages[i].pop()
            self.alloc.decref(p)
            self.table[i, len(self.pages[i])] = self.scratch[i]

    def cow_pairs(self, i: int, first_pos: int,
                  last_pos: int) -> List[Tuple[int, int]]:
        """Copy-on-write: privatize shared pages in slot ``i``'s write range.

        Returns (src, dst) physical pairs for the engine to copy device-side
        before launching. Shared pages come only from full-page prompt
        prefixes and writes start at >= the prompt length, so this normally
        returns [] — it is the belt-and-braces guarantee that a slot NEVER
        writes a page another slot (or the radix tree) can see."""
        if self.fixed:
            return []
        out: List[Tuple[int, int]] = []
        first = first_pos // self.ps
        last = min(last_pos // self.ps, self.cap_pages - 1)
        for j in range(first, min(last + 1, len(self.pages[i]))):
            p = self.pages[i][j]
            if self.alloc.refcount[p] > 1:
                q = self._alloc_page()
                self.pages[i][j] = q
                self.table[i, j] = q
                self.alloc.decref(p)
                out.append((p, q))
        return out

    # -- accounting (engine invariants / telemetry) -------------------------

    def check_invariants(self) -> None:
        """Exact page accounting: slot refs + scratch + radix == refcounts,
        free-list size matches zero-refcount pages, and every table row maps
        only pages its slot owns (or its scratch). AssertionError on drift."""
        refs = [0] * self.alloc.n_pages
        for i in range(self.n_slots):
            for p in self.pages[i]:
                refs[p] += 1
        for s in self.scratch:
            refs[s] += 1
        if self.radix is not None:
            for p in self.radix.held_pages():
                refs[p] += 1
        assert refs == self.alloc.refcount, (
            f"page refcount drift: expected {refs}, "
            f"allocator has {self.alloc.refcount}")
        n_zero = sum(1 for r in self.alloc.refcount if r == 0)
        assert n_zero == self.alloc.n_free, (
            f"free-list drift: {self.alloc.n_free} free vs "
            f"{n_zero} zero-refcount pages")
        for i in range(self.n_slots):
            own = self.pages[i]
            row = self.table[i]
            assert list(row[: len(own)]) == own, \
                f"slot {i}: table row disagrees with owned pages"
            if not self.fixed:
                tail = {int(x) for x in row[len(own):]}
                assert tail <= {self.scratch[i]}, \
                    f"slot {i}: tail maps non-scratch pages {tail}"
        assert self.budgeted == sum(self.budget), (
            f"admission budget drift: {self.budgeted} booked vs "
            f"per-slot sum {sum(self.budget)}")
        if not self.fixed:
            assert self.budgeted <= self.reservable, (
                f"admission overbooked: {self.budgeted} > "
                f"{self.reservable} reservable pages")
            for i in range(self.n_slots):
                assert self.budget[i] == 0 \
                    or len(self.pages[i]) <= self.budget[i], \
                    f"slot {i} maps {len(self.pages[i])} pages over its " \
                    f"admission budget {self.budget[i]}"

    def stats(self) -> Dict[str, float]:
        out = dict(self.alloc.metric_values())
        out["budgeted"] = self.budgeted
        out["reservable"] = self.reservable
        if self.radix is not None:
            out.update({f"radix_{k}": v
                        for k, v in self.radix.metric_values().items()})
        return out


@dataclass
class _DepthGroup:
    """One compiled executable's slots: a depth, its full-width cache, and
    the per-slot width fraction each occupant was admitted at."""

    depth: int
    cache: Dict
    slots: List[Optional[Request]]
    widths: List[float]  # admission width per slot (stale for free slots)
    # speculative state (None when this depth has no shallower exit to
    # draft at, or speculation is disabled engine-wide)
    keys: Optional[object] = None  # per-slot PRNG keys, device-resident
    spec_k: int = 0  # active linear draft length (0 = no linear drafting)
    # active token-tree branching schedule; takes precedence over spec_k
    # when set (the SLO policy's choose_tree switches between them)
    spec_tree: Optional[Tuple[int, ...]] = None
    accept_window: Deque[float] = field(default_factory=lambda: deque(maxlen=32))
    spec_off_until: int = -1  # tick until which speculation is cooling off
    # host-side page bookkeeping (None when the engine is dense)
    paging: Optional[_GroupPaging] = None

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.slots)

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]


@dataclass
class GroupSnapshot:
    """Host-side truth of one depth group (see ``EngineSnapshot``)."""

    depth: int
    slots: List[Optional[Request]]  # deep copies — snapshot owns them
    widths: List[float]
    spec_k: int
    spec_tree: Optional[Tuple[int, ...]]
    spec_off_until: int
    accept_window: List[float]
    accept_window_maxlen: Optional[int]


@dataclass
class EngineSnapshot:
    """Everything ``ServingEngine.restore`` needs to rebuild serving state
    on a fresh executor — and nothing device-resident.

    Device caches are deliberately NOT captured: every committed token is
    known host-side (``(prompt + generated)[:fed]`` per slot, cache position
    == ``fed``), so restore re-materializes each live slot by replaying its
    committed stream through the compiled paths that produced it.
    Uncommitted speculative work (drafts in flight when the snapshot was
    cut) is not state either — the next tick re-drafts and re-verifies it.
    ``paging_stats`` is informational (pre-failure occupancy for logs); the
    page tables themselves are rebuilt exactly by the replay.
    """

    step_count: int
    admission_mode: str
    queues: Dict[str, List[Request]]
    completed: List[Request]
    expired: List[Request]
    groups: Dict[int, GroupSnapshot]
    counters: Dict[str, float]
    logs: Dict[str, list]
    telemetry: Dict[str, Dict]
    spec_telemetry: Dict
    paging_stats: Dict[int, Dict[str, float]]
    metrics: Optional[Dict] = None  # Observability.state_dict() of the source
    # Autoscaler.state_dict() of the source (None when no autoscaler bound):
    # published/retired units + frontier generation, so a restored engine
    # rebuilds the same executable pool and keeps deciding deterministically
    autoscale: Optional[Dict] = None


class ServingEngine:
    """Continuous-batching decode engine over a per-depth MorphController.

    One engine tick = admit queued requests into the admission mode's depth
    group (interactive class first; long prompts via one prefill launch,
    short ones via one batched slot-reset launch), then run ONE decode
    launch per depth group with active slots — slots of different widths
    ride the same launch via per-slot active-dim operands. The host
    round-trip per tick (argmax + slot bookkeeping) is the simplicity
    tradeoff of this reference engine; the device work itself is the same
    per-depth executable every tick, host-local or mesh-sharded depending on
    the executor.
    """

    # engine counters live in the metrics registry; these attribute names
    # are generated as property aliases over the named Counter objects after
    # the class body (``self.prefills += 1`` keeps working everywhere).
    # ``step_count`` and ``replay_chunk_launches`` stay plain attributes:
    # the former is exported as a gauge, the latter is host-only replay
    # diagnostics that snapshot/restore deliberately never carries.
    _COUNTER_METRICS = {
        "prefills": "engine_prefills",
        "prefill_s": "engine_prefill_s",
        "prefill_prompt_tokens": "engine_prefill_prompt_tokens",
        "decode_launches": "engine_decode_launches",
        "per_mode_launch_equiv": "engine_per_mode_launch_equiv",
        "ticks_with_work": "engine_ticks_with_work",
        "spec_draft_launches": "engine_spec_draft_launches",
        "spec_verify_launches": "engine_spec_verify_launches",
        "spec_tree_launches": "engine_spec_tree_launches",
        "spec_generated_tokens": "engine_spec_generated_tokens",
        "backpressure_events": "engine_backpressure_events",
    }

    def __init__(self, params, cfg: ModelConfig, *, batch_size: int = 4,
                 cache_capacity: int = 64,
                 modes: Optional[Tuple[MorphMode, ...]] = None,
                 controller: Optional[MorphController] = None,
                 executor: Optional[LocalExecutor] = None,
                 prefill_threshold: int = 8,
                 speculative: Optional[SpecConfig] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 sample_seed: int = 0,
                 paged: Optional[PagedLayout] = None,
                 fused: bool = False,
                 observability: Optional[Observability] = None):
        if paged is not None:
            if cfg.is_encdec or cfg.frontend:
                raise ValueError(
                    "paged KV serving needs a token-only decoder (enc-dec / "
                    "frontend archs carry cross-attention state the page "
                    "pool does not cover)")
            paged.validate(cfg, cache_capacity)
        if speculative is not None and (cfg.is_encdec or cfg.frontend):
            raise ValueError("speculative serving needs a token-only decoder "
                             "(enc-dec / frontend archs carry non-token "
                             "prompt operands the draft loop cannot feed)")
        if speculative is not None and cfg.sliding_window:
            # bound every draft shape's depth at the rolling window: the
            # verify commit's scatter would alias buffer slots otherwise
            k_max = max(speculative.ks, default=0)
            if k_max + 1 > cfg.sliding_window:
                raise ValueError(
                    f"speculative K={k_max} needs K+1 <= "
                    f"sliding_window ({cfg.sliding_window}): the verify "
                    f"commit's rolling scatter would alias buffer slots")
            for br in speculative.trees:
                if len(br) + 1 > cfg.sliding_window:
                    raise ValueError(
                        f"speculative tree {br} is {len(br)} levels deep; "
                        f"needs depth+1 <= sliding_window "
                        f"({cfg.sliding_window}): the verify commit's "
                        f"rolling scatter would alias buffer slots")
        if (speculative is not None and top_k and speculative.top_k
                and speculative.top_k != top_k):
            raise ValueError(
                f"engine top_k={top_k} conflicts with SpecConfig.top_k="
                f"{speculative.top_k}: fallback plain stepping and the "
                f"speculative acceptance rule would sample different "
                f"distributions")
        if speculative is not None and top_k and not speculative.top_k:
            # one truncation everywhere: the speculative executables must
            # sample/accept under the same distribution the fallback path uses
            speculative = replace(speculative, top_k=top_k)
        self.cfg = cfg
        self.batch_size = batch_size
        self.cache_capacity = cache_capacity
        self.speculative = speculative
        self.temperature = float(temperature)
        self.sample_seed = sample_seed
        self.paged = paged
        # route every attention decode/verify/tree-verify through the
        # kernels.fused_decode superkernel — a pure closure flag on the
        # compiled steps: same compile keys, same aux table, token-identical
        # output (see core.morph.make_serve_controller)
        self.fused = bool(fused)
        # observability: one registry/recorder/clock shared down the stack.
        # Engine counters live as registry Counters behind property aliases,
        # the ad-hoc log deques as structured EventStreams, and every timing
        # site reads obs.clock so an injected clock makes runs deterministic.
        self.obs = observability or Observability()
        self.metrics = self.obs.registry
        self._rec = self.obs.recorder
        self._clock = self.obs.clock
        self._counter_objs = {m: self.metrics.counter(m)
                              for m in self._COUNTER_METRICS.values()}
        self._h_prefill = self.metrics.histogram("engine_prefill_ms")
        self._h_decode = self.metrics.histogram("engine_decode_step_ms")
        self._h_spec = self.metrics.histogram("engine_spec_tick_ms")
        self.executor = (executor or LocalExecutor()).bind(
            cfg, batch_size, cache_capacity, paged=paged, fused=self.fused)
        self.params = self.executor.place_params(params)
        self.ctrl = controller or self.executor.make_controller(
            self.params, cfg, modes, speculative=speculative)
        self._mode_by_dw = {(m.depth, m.width): m for m in self.ctrl.modes}
        self._spec_plan = getattr(self.ctrl, "spec_plan", {})
        self.groups: Dict[int, _DepthGroup] = {}
        base_keys = sampling.make_slot_keys(sample_seed, batch_size)
        for d in sorted({m.depth for m in self.ctrl.modes}):
            g = _DepthGroup(d, self.executor.init_cache(),
                            [None] * batch_size, [1.0] * batch_size)
            if paged is not None:
                g.paging = _GroupPaging(paged, cfg, batch_size,
                                        cache_capacity)
            plan = self._spec_plan.get(d)
            if plan is not None:
                g.spec_k = max(plan.ks, default=0)
                if plan.trees:
                    # optimistic default until telemetry arrives: the tree
                    # with the best expected tokens/launch at high agreement
                    # (DistillCycle-trained exits are built to agree)
                    g.spec_tree = max(
                        plan.trees,
                        key=lambda br: expected_tokens_per_tree_launch(
                            0.75, br))
                g.accept_window = deque(maxlen=speculative.window)
            # per-(group, slot) keys: slot i of different depth groups must
            # not share a sample stream
            g.keys = self.executor.put(jax.vmap(
                lambda k, d=d: jax.random.fold_in(k, d))(base_keys))
            self.groups[d] = g
        # acceptance telemetry per (depth, draft_depth, K) — feeds the SLO
        # policy's (draft_depth, K) choice and the fallback decision
        self.spec_telemetry: Dict[Tuple[int, int, int], SpecTelemetry] = {}
        # structured event streams replacing the old ad-hoc log deques: one
        # schema + one accessor each, same bounded memory (maxlen=4096); the
        # legacy names remain as read-only property views below
        reg = self.metrics
        self._ev_spec_fallback = reg.events(
            "engine_spec_fallback", ("step", "depth", "rate", "off_until"))
        self._ev_backpressure = reg.events(
            "engine_backpressure",
            ("step", "rid", "need", "budgeted", "reservable"))
        self._ev_admission_switch = reg.events(
            "engine_admission_switch",
            ("step", "from_mode", "to_mode", "queued_interactive",
             "queued_batch", "frontier_gen"))
        self._ev_admission_decision = reg.events(
            "engine_admission_decision",
            ("step", "budget_s", "effective_budget_s", "queue_pressure",
             "mode", "queued_interactive", "queued_batch"))
        reg.attach_events(self.ctrl.switch_events)
        self.ctrl.clock = self._clock
        reg.register_callback(self._metric_gauges, key="engine")
        self.spec_draft_launches = 0
        self.spec_verify_launches = 0
        self.spec_tree_launches = 0  # verify launches that scored a tree
        self.spec_generated_tokens = 0
        # jitted per-slot sampler for the NON-speculative path (temperature
        # is a runtime operand; 0 never reaches it — argmax stays host-side).
        # ``top_k`` applies here; the speculative executables truncate via
        # SpecConfig.top_k (a compile-time choice of their acceptance rule).
        vocab = cfg.vocab_size
        self.top_k = top_k or (speculative.top_k if speculative else 0)
        self._sample_fn = jax.jit(
            lambda lg, keys, t, s, k=self.top_k: sampling.sample_tokens(
                lg, sampling.fold_step(keys, s), t, vocab, k))
        self._temp_op = self.executor.put(np.float32(self.temperature))
        self._reset = self.executor.reset_fn()
        self._adopt = self.executor.adopt_fn()
        self._copy_page = (self.executor.copy_page_fn()
                           if paged is not None else None)
        # compiled prefills, keyed by (prompt_len, depth); ``slot`` is traced
        self._prefills: Dict[Tuple[int, int], Callable] = {}
        # compiled replay chunks (restore-time batched history re-feed),
        # keyed by (depth, chunk length); engine-cached rather than in the
        # controller's aux table — they exist only for failover replay
        self._replay_chunks: Dict[Tuple[int, int], Callable] = {}
        # launches the chunked replay saved vs one-launch-per-token re-feed
        # (host-only diagnostics: restore never snapshots/restores it)
        self.replay_chunk_launches = 0
        self.prefill_threshold = prefill_threshold
        self.prefills = 0
        self.prefill_s = 0.0
        self.prefill_prompt_tokens = 0
        # two-level priority queue: interactive requests admit before batch
        self._queues: Dict[str, Deque[Request]] = {c: deque()
                                                   for c in SLO_CLASSES}
        self.completed: List[Request] = []
        # deadline-retired requests (terminal "expired" status, never admitted)
        self.expired: List[Request] = []
        self.backpressure_events = 0
        self.admission_mode: MorphMode = self.ctrl.modes[-1]
        self.step_count = 0
        self.compiles_after_warmup: Optional[int] = None
        # launch accounting: actual launches (per depth group) vs what the
        # old per-(depth, width) grouping would have issued for the same
        # in-flight population
        self.decode_launches = 0
        self.per_mode_launch_equiv = 0
        self.ticks_with_work = 0
        # per-slot active-dim vectors memoized by widths tuple: widths only
        # change on admission, and the mode table bounds the distinct values
        # — no per-tick morph_config calls or host-to-device puts
        self._active_cache: Dict[Tuple[float, ...], Dict] = {}
        # online-MOGA autoscaler (runtime.autoscale.Autoscaler.bind attaches
        # one); admission-switch events record its frontier generation, and
        # snapshot/restore carries its state through _pending_autoscale when
        # a bare standby absorbs a snapshot before an autoscaler binds
        self.autoscaler = None
        self._pending_autoscale: Optional[Dict] = None
        # paged buckets currently backed by a compiled executable; the
        # autoscaler retires/re-adopts ladder entries through this set (the
        # cap bucket is never retired, so a covering bucket always exists)
        self._avail_buckets = (set(paged.buckets(cfg, cache_capacity))
                               if paged is not None else set())

    def _active_for(self, widths: List[float]) -> Dict:
        key = tuple(widths)
        active = self._active_cache.get(key)
        if active is None:
            if len(self._active_cache) > 1024:  # oscillation backstop
                self._active_cache.clear()
            active = jax.tree_util.tree_map(
                self.executor.put, elastic.active_widths_batch(self.cfg, widths))
            self._active_cache[key] = active
        return active

    # -- observability ------------------------------------------------------

    @property
    def spec_fallback_log(self):
        """(step, depth, window accept rate, off_until) tuples — legacy view
        of the ``engine_spec_fallback`` event stream."""
        return _TupleView(self._ev_spec_fallback)

    @property
    def backpressure_log(self):
        """Structured pool-exhaustion deferral events (dict rows)."""
        return self._ev_backpressure

    @property
    def admission_switch_log(self):
        """(step, from, to, queued interactive, queued batch) tuples —
        legacy view of the ``engine_admission_switch`` event stream (the
        stream itself additionally records ``frontier_gen``; the tuple shape
        predates the autoscaler and stays 5-wide)."""
        return _TupleView(self._ev_admission_switch,
                          fields=("step", "from_mode", "to_mode",
                                  "queued_interactive", "queued_batch"))

    @property
    def admission_decision_log(self):
        """SLO policy decision inputs per admission switch (dict rows)."""
        return self._ev_admission_decision

    def _metric_gauges(self) -> Dict[str, float]:
        """Export-time gauge callback: queue/slot occupancy, per-mode
        latency percentiles, page-pool + radix accounting, and speculative
        acceptance — pulled lazily so hot paths never push them."""
        out = {
            "engine_step_count": float(self.step_count),
            "engine_active_slots": float(self.n_active),
            "engine_queued_interactive":
                float(len(self._queues["interactive"])),
            "engine_queued_batch": float(len(self._queues["batch"])),
            "engine_completed": float(len(self.completed)),
            "engine_expired": float(len(self.expired)),
        }
        for name, t in self.ctrl.telemetry.items():
            if t.steps:
                out[f"mode_{name}_p50_ms"] = t.p50_s * 1e3
                out[f"mode_{name}_p95_ms"] = t.p95_s * 1e3
                out[f"mode_{name}_p99_ms"] = t.p99_s * 1e3
        for d, stats in self.page_pool_stats().items():
            out.update({f"kv_pool_d{d}_{k}": float(v)
                        for k, v in stats.items()})
        for (d, dd, s), t in self.spec_telemetry.items():
            if t.launches:
                out.update(t.metric_values(f"spec_d{d}_{_shape_label(s)}"))
        return out

    def export_metrics(self, events: bool = False) -> Dict:
        """JSON-shaped snapshot of the full metrics registry."""
        return self.metrics.to_json(events=events)

    def export_trace(self) -> Dict:
        """Chrome trace-event JSON of everything recorded so far."""
        return self._rec.export_chrome_trace()

    # -- lifecycle ----------------------------------------------------------

    def warmup(self) -> None:
        """Compile every depth's step + the batched slot-reset, then rewind.

        After this returns, ``self.ctrl.stats['compiles']`` is frozen at
        ``len(depths)`` (NOT ``len(modes)``) plus, when speculative serving
        is on, one draft executable per (draft_depth, K) and one verify
        executable per (depth, K): traffic with arbitrary width/depth churn,
        (draft_depth, K) switching, and greedy/sampled temperature changes
        re-dispatches these executables.
        """
        self.ctrl.warmup()
        tok = self.executor.put(np.zeros((self.batch_size, 1), np.int32))
        active = self._active_for([1.0] * self.batch_size)
        mask = self.executor.put(np.ones((self.batch_size,), bool))
        s_op = self.executor.put(np.uint32(0))
        for d, g in self.groups.items():
            spec_extra = ()
            if g.paging is not None:
                # paged serving never dispatches the dense per-depth steps:
                # trace one executable per (depth, table-width bucket) plus
                # the CoW page copy instead (free slots' tables point at
                # scratch, so the garbage these launches write is harmless)
                cache = g.cache
                for b in self.paged.buckets(self.cfg, self.cache_capacity):
                    fn = self.ctrl.aux_step(paged_decode_compile_key(d, b))
                    pages_b = self.executor.put(g.paging.table[:, :b].copy())
                    logits, cache = fn(self.params, cache, tok, active,
                                       pages_b)
                cache = self._copy_page(cache,
                                        self.executor.put(np.int32(0)),
                                        self.executor.put(np.int32(0)))
                spec_extra = (self.executor.put(
                    g.paging.table[:, :g.paging.cap_pages].copy()),)
            else:
                step = self.ctrl.step_for(self._any_mode_at(d))
                logits, cache = step(self.params, g.cache, tok, active)
            if self.temperature > 0:
                self._sample_fn(logits[:, 0], g.keys, self._temp_op, s_op)
            plan = self._spec_plan.get(d)
            if plan is not None:
                for k in plan.ks:
                    draft = self.ctrl.aux_step(
                        draft_compile_key(plan.draft_depth, k))
                    verify = self.ctrl.aux_step(verify_compile_key(d, k))
                    dtoks, dlg = draft(self.params, cache, tok, active,
                                       g.keys, self._temp_op, s_op,
                                       *spec_extra)
                    full = jnp.concatenate([tok, dtoks], axis=1)
                    _, _, cache = verify(self.params, cache, full, dlg,
                                         active, g.keys, self._temp_op, s_op,
                                         *spec_extra)
                for br in plan.trees:
                    draft = self.ctrl.aux_step(
                        tree_draft_compile_key(plan.draft_depth, br))
                    verify = self.ctrl.aux_step(tree_verify_compile_key(d, br))
                    ttoks, dlg = draft(self.params, cache, tok, active,
                                       g.keys, self._temp_op, s_op,
                                       *spec_extra)
                    _, _, cache = verify(self.params, cache, ttoks, dlg,
                                         active, g.keys, self._temp_op, s_op,
                                         *spec_extra)
            cache = self._reset(cache, mask)
            jax.block_until_ready(cache)
            # rewind: warmup wrote garbage at pos 0 of every slot
            g.cache = self.executor.init_cache()
            if g.paging is not None:
                g.paging.host_pos[:] = 0
        self.compiles_after_warmup = self.ctrl.stats["compiles"]

    def _any_mode_at(self, depth: int) -> MorphMode:
        return next(m for m in self.ctrl.modes if m.depth == depth)

    @property
    def queue(self) -> Tuple[Request, ...]:
        """Waiting requests in admission order (interactive before batch)."""
        return tuple(self._queues["interactive"]) + tuple(self._queues["batch"])

    def submit(self, req: Request) -> None:
        if not req.prompt:
            raise ValueError(f"request {req.rid} has an empty prompt")
        if req.slo_class not in SLO_CLASSES:
            raise ValueError(f"request {req.rid}: unknown slo_class "
                             f"{req.slo_class!r} (want one of {SLO_CLASSES})")
        # the last generated token is never fed back, so the highest cache
        # position written is prompt + new - 2
        need = len(req.prompt) + req.max_new_tokens - 1
        if need > self.cache_capacity:
            raise ValueError(f"request {req.rid} needs {need} cache slots, "
                             f"capacity is {self.cache_capacity}")
        if self.paged is not None:
            # reject-at-submit: a request whose worst case overflows the
            # page budget of EVERY depth group can never be admitted —
            # deferring it would starve it forever (transient shortage is
            # handled at admission time by deferral instead)
            dyn = [g for g in self.groups.values()
                   if g.paging is not None and not g.paging.fixed]
            if dyn:
                needs = [self._worst_case_pages(g, req) for g in dyn]
                resv = min(g.paging.reservable for g in dyn)
                if min(needs) > resv:
                    raise ValueError(
                        f"request {req.rid} can never be admitted: its "
                        f"worst case needs {min(needs)} kv pages but only "
                        f"{resv} are reservable (raise --kv-pages or shrink "
                        f"the request)")
        self._queues[req.slo_class].append(req)
        if self._rec.enabled:
            self._rec.request_begin(req.rid, slo_class=req.slo_class,
                                    prompt_len=len(req.prompt),
                                    max_new_tokens=req.max_new_tokens)

    def _worst_case_pages(self, g: _DepthGroup, req: Request) -> int:
        """Pages slot-admitting ``req`` into ``g`` can ever map at once.

        The highest decode write position is ``prompt + new - 2`` (the last
        generated token is never fed) plus the deepest draft shape the group
        could speculate past it; prefill admission maps ``plen // ps + 1``
        pages up front, which can exceed the decode bound for tiny
        ``max_new_tokens``.
        """
        pg = g.paging
        headroom = 0
        plan = self._spec_plan.get(g.depth)
        if self.speculative is not None and plan is not None:
            shapes = list(plan.ks) + [len(br) for br in plan.trees]
            headroom = max(shapes, default=0)
        last = len(req.prompt) + req.max_new_tokens - 2 + headroom
        need = max(last, len(req.prompt)) // pg.ps + 1
        return min(need, pg.cap_pages)

    def _reserve_pages(self, g: _DepthGroup, slot: int, req: Request) -> bool:
        """Book ``req``'s worst-case page demand for ``slot``; False = the
        pool cannot cover it right now (caller defers the admission)."""
        pg = g.paging
        if pg is None or pg.fixed:
            return True
        need = self._worst_case_pages(g, req)
        if not pg.can_reserve(need):
            return False
        pg.reserve(slot, need)
        return True

    def _pop_next(self) -> Optional[Request]:
        for cls in SLO_CLASSES:
            if self._queues[cls]:
                return self._queues[cls].popleft()
        return None

    def set_admission_mode(self, mode: MorphMode) -> None:
        if mode.name != self.admission_mode.name:
            self._ev_admission_switch.emit(
                step=self.step_count, from_mode=self.admission_mode.name,
                to_mode=mode.name,
                queued_interactive=len(self._queues["interactive"]),
                queued_batch=len(self._queues["batch"]),
                frontier_gen=(self.autoscaler.generation
                              if self.autoscaler is not None else -1))
            # the policy decision is the real "mode switch" — route it
            # through the controller so its switch stats/log record it
            # (group-drain dispatches in step() deliberately don't)
            self.ctrl.set_mode(mode)
        self.admission_mode = mode

    # -- one tick -----------------------------------------------------------

    def _use_prefill(self, req: Request) -> bool:
        # enc-dec / frontend archs need non-token inputs at prompt time; the
        # engine only carries token prompts, so they stay on the token feed
        return (len(req.prompt) >= self.prefill_threshold
                and not self.cfg.is_encdec and not self.cfg.frontend)

    def _expire_queued(self, now_s: float) -> None:
        """Retire queued requests past their deadline (both SLO classes).

        Terminal ``expired`` status — the request is never admitted and
        never completes; serving it after its TTL would waste launches the
        live queue could use. In-flight requests are never expired: their
        cache state is paid for, finishing is strictly cheaper than the
        admission it displaced.
        """
        for cls in SLO_CLASSES:
            q = self._queues[cls]
            if not any(r.deadline_s is not None for r in q):
                continue
            kept: Deque[Request] = deque()
            for r in q:
                if r.deadline_s is not None and now_s > r.deadline_s:
                    r.status = "expired"
                    r.finished_s = now_s
                    self.expired.append(r)
                    if self._rec.enabled:
                        self._rec.request_end(r.rid, "expired",
                                              tokens=len(r.generated))
                else:
                    kept.append(r)
            self._queues[cls] = kept

    def _admit(self, now_s: float = 0.0) -> None:
        self._expire_queued(now_s)
        g = self.groups[self.admission_mode.depth]
        mask = np.zeros(self.batch_size, bool)
        prefills = []
        for slot in g.free_slots():
            req = self._pop_next()
            if req is None:
                break
            if not self._reserve_pages(g, slot, req):
                # graceful degradation: the page pool cannot cover this
                # request's worst case right now — defer it (head of its
                # class queue, FIFO order kept) and log a backpressure
                # event; completions release budget and it admits later
                self._queues[req.slo_class].appendleft(req)
                pg = g.paging
                self.backpressure_log.append(dict(
                    step=self.step_count, rid=req.rid,
                    need=self._worst_case_pages(g, req),
                    budgeted=pg.budgeted, reservable=pg.reservable))
                self.backpressure_events += 1
                break
            g.slots[slot] = req
            g.widths[slot] = self.admission_mode.width
            req.status = "active"
            req.mode_name = self.admission_mode.name
            req.admitted_step = self.step_count
            if self._rec.enabled:
                self._rec.request_event(req.rid, "admit",
                                        step=self.step_count, slot=slot,
                                        depth=g.depth,
                                        width=self.admission_mode.width)
            if self._use_prefill(req):
                prefills.append((slot, req))
            else:
                mask[slot] = True
        if mask.any():
            # ONE batched reset per tick, however large the admission burst
            g.cache = self._reset(g.cache, self.executor.put(mask))
            if g.paging is not None:
                # the reset zeroed the device position counters; mirror it
                g.paging.host_pos[mask] = 0
        for slot, req in prefills:
            self._admit_prefill(g, slot, req, now_s)

    def _complete(self, g: _DepthGroup, slot: int, req: Request,
                  now_s: float) -> None:
        """Retire a finished request: terminal status, slot + pages freed."""
        req.finished_s = now_s
        req.status = "done"
        self.completed.append(req)
        g.slots[slot] = None
        if g.paging is not None:
            g.paging.release(slot)
        if self._rec.enabled:
            self._rec.request_end(req.rid, "done",
                                  tokens=len(req.generated))

    def _prefill_launch(self, g: _DepthGroup, slot: int,
                        prompt: Tuple[int, ...]):
        """One compiled whole-prompt consume + dense slot adoption.

        The launch-only half of prefill admission, shared with snapshot
        replay (``_replay_prefill``) so a restored slot's prompt K/V comes
        from the SAME executable its admission used. Returns the prompt's
        last-position logits.
        """
        plen = len(prompt)
        key = (plen, g.depth)
        fn = self._prefills.get(key)
        if fn is None:
            # backstop for unbounded prompt-length churn (cf. _active_cache):
            # a long-lived engine must not retain one executable per distinct
            # prompt length forever. Length bucketing would cap compiles at
            # O(log capacity) but needs padding-safe prefill (SSM state sees
            # every padded token), so the simple bound stands in for now.
            if len(self._prefills) > 256:
                self._prefills.clear()
            fn = self.executor.prefill_fn(plen, g.depth)
            self._prefills[key] = fn
        toks = self.executor.put(np.asarray([prompt], np.int32))
        slot_op = self.executor.put(np.int32(slot))
        logits, pre = fn(self.params, toks, slot_op)
        g.cache = self._adopt(g.cache, pre, slot_op)
        return logits

    def _prefill_launch_paged(self, g: _DepthGroup, slot: int,
                              prompt: Tuple[int, ...]):
        """Paged whole-prompt consume with shared-prefix block reuse.

        The prompt's full pages are radix-matched under (depth, width): a
        resident prefix is mapped into the slot's table (incref'd, write-
        masked — the fused prefill recomputes identical K/V for those
        positions but does NOT write them, so many slots share one physical
        block). Fresh pages cover the rest; afterwards the prompt's full
        pages are inserted into the tree for the next arrival. Shared with
        snapshot replay, which re-establishes the same sharing. Returns the
        prompt's last-position logits.
        """
        pg = g.paging
        ps = pg.ps
        plen = len(prompt)
        rkey = (g.depth, g.widths[slot])
        if pg.fixed:
            # sliding window: the dense prefill already emits the ROLLED
            # lane (token t at slot t % window), which is exactly the fixed
            # page row's layout — adopt all cap_pages pages, no sharing (the
            # rolling buffer overwrites pages, so blocks can't be shared)
            ncp = pg.cap_pages
            chunks, n_full = [], 0
            pages_list = list(pg.pages[slot])
            wmask = np.ones(ncp, bool)
        else:
            ncp = min(plen // ps + 1, pg.cap_pages)
            n_full = min(plen // ps, ncp)
            chunks = [tuple(prompt[j * ps:(j + 1) * ps])
                      for j in range(n_full)]
            shared = pg.radix.match(rkey, chunks)
            for p in shared:
                pg.alloc.incref(p)
            pages_list = shared + [pg._alloc_page()
                                   for _ in range(ncp - len(shared))]
            pg.pages[slot] = list(pages_list)
            pg.table[slot, :] = pg.scratch[slot]
            pg.table[slot, :ncp] = pages_list
            wmask = np.arange(ncp) >= len(shared)
        pg.host_pos[slot] = plen
        key = (plen, g.depth)
        fn = self._prefills.get(key)
        if fn is None:
            if len(self._prefills) > 256:
                self._prefills.clear()
            fn = self.executor.prefill_adopt_fn(plen, g.depth, ncp)
            self._prefills[key] = fn
        toks = self.executor.put(np.asarray([prompt], np.int32))
        slot_op = self.executor.put(np.int32(slot))
        logits, g.cache = fn(
            self.params, toks, slot_op, g.cache,
            self.executor.put(np.asarray(pages_list, np.int32)),
            self.executor.put(wmask))
        if not pg.fixed:
            pg.radix.insert(rkey, chunks, pages_list[:n_full])
        return logits

    def _admit_prefill(self, g: _DepthGroup, slot: int, req: Request,
                       now_s: float) -> None:
        """Consume the whole prompt in one compiled prefill + adoption."""
        self.executor.launch("prefill")
        t0 = self._clock()
        if g.paging is not None:
            logits = self._prefill_launch_paged(g, slot, req.prompt)
        else:
            logits = self._prefill_launch(g, slot, req.prompt)
        req.prefilled = True
        # the prefill's last-position logits yield the first generated token
        # (same contract as the decode step that eats the last prompt token);
        # under sampled serving it must come from the slot's sample stream,
        # not argmax — both admission paths serve the same distribution
        if self.temperature > 0:
            s_op = self.executor.put(np.uint32(self.step_count))
            nxt = int(np.asarray(self._sample_fn(
                logits[:, 0], g.keys[slot:slot + 1], self._temp_op, s_op))[0])
        else:
            nxt = int(np.asarray(jnp.argmax(logits[0, 0, : self.cfg.vocab_size])))
        jax.block_until_ready(g.cache)
        t1 = self._clock()
        self.prefill_s += t1 - t0
        self.prefills += 1
        self.prefill_prompt_tokens += len(req.prompt)
        self._h_prefill.observe((t1 - t0) * 1e3)
        req.fed = len(req.prompt)
        req.generated.append(nxt)
        if self._rec.enabled:
            self._rec.launch("prefill", t0, t1, depth=g.depth,
                             rids=[req.rid], occupancy=1, tokens=1,
                             key=[len(req.prompt), g.depth])
            self._rec.request_event(req.rid, "prefill", t=t1,
                                    prompt_tokens=len(req.prompt))
            self._rec.request_event(req.rid, "first_token", t=t1)
        if req.done:
            self._complete(g, slot, req, now_s)

    def _spec_select(self, g: _DepthGroup):
        """The draft shape to speculate with this tick: ``("tree",
        branching)``, ``("linear", k)``, or ``None`` (plain step).

        A group speculates only when every active slot has consumed its
        prompt up to the last token (drafting against forced prompt tokens
        would just re-predict the prompt) and has draft-depth + 1 cache
        positions of headroom, speculation is not cooling off after an
        acceptance collapse, and the depth has a shallower exit to draft at.
        The active token tree (``spec_tree``) takes precedence over the
        linear draft length when both are compiled.
        """
        if self.speculative is None:
            return None
        if g.depth not in self._spec_plan:
            return None
        if self.step_count < g.spec_off_until:
            return None
        if g.spec_tree is not None:
            sel = ("tree", g.spec_tree)
            draft_depth = len(g.spec_tree)
        elif g.spec_k > 0:
            sel = ("linear", g.spec_k)
            draft_depth = g.spec_k
        else:
            return None
        for r in g.slots:
            if r is None:
                continue
            if r.fed < len(r.prompt) - 1:
                return None
            if r.fed + draft_depth + 1 > self.cache_capacity:
                return None
        return sel

    def _spec_tick(self, g: _DepthGroup, sel, active_ix: List[int],
                   now_s: float) -> float:
        """One speculative step for a depth group: draft candidates at the
        shallow exit (a linear K-token chain or a token tree), verify every
        position in one full-depth launch, commit the accepted prefix/path
        device-side. ONE host transfer brings back (out_tokens, n_accepted)
        for slot bookkeeping."""
        plan = self._spec_plan[g.depth]
        kind, shape = sel
        site = "tree_verify" if kind == "tree" else "verify"
        # launch boundary BEFORE any host page bookkeeping mutates: an
        # injected loss here leaves the tick entirely un-executed, which is
        # what makes the supervisor's pre-tick snapshot an exact replay point
        self.executor.launch(site)
        if kind == "tree":
            draft = self.ctrl.aux_step(
                tree_draft_compile_key(plan.draft_depth, shape))
            verify = self.ctrl.aux_step(
                tree_verify_compile_key(g.depth, shape))
            depth_budget = len(shape)  # max accepted drafts per launch
        else:
            draft = self.ctrl.aux_step(
                draft_compile_key(plan.draft_depth, shape))
            verify = self.ctrl.aux_step(verify_compile_key(g.depth, shape))
            depth_budget = shape
        toks = np.zeros((self.batch_size, 1), np.int32)
        for i in active_ix:
            toks[i, 0] = g.slots[i].next_input()
        active = self._active_for(g.widths)
        tok_op = self.executor.put(toks)
        s_op = self.executor.put(np.uint32(self.step_count))
        pg = g.paging
        extra = ()
        if pg is not None:
            # grow every active slot's mapping to cover the deepest draft
            # write (root + depth_budget positions) and privatize any shared
            # page in that range; the speculative executables always see the
            # FULL-width table (their compile keys are not bucketed)
            for i in active_ix:
                pos = int(pg.host_pos[i])
                pg.ensure_slot(i, pos + depth_budget)
                for src, dst in pg.cow_pairs(i, pos, pos + depth_budget):
                    g.cache = self._copy_page(
                        g.cache, self.executor.put(np.int32(src)),
                        self.executor.put(np.int32(dst)))
            extra = (self.executor.put(pg.table[:, :pg.cap_pages].copy()),)
        t0 = self._clock()
        if kind == "tree":
            ttoks, dlg = draft(self.params, g.cache, tok_op, active, g.keys,
                               self._temp_op, s_op, *extra)
            out, n_acc, g.cache = verify(self.params, g.cache, ttoks, dlg,
                                         active, g.keys, self._temp_op, s_op,
                                         *extra)
        else:
            dtoks, dlg = draft(self.params, g.cache, tok_op, active, g.keys,
                               self._temp_op, s_op, *extra)
            full = jnp.concatenate([tok_op, dtoks], axis=1)
            out, n_acc, g.cache = verify(self.params, g.cache, full, dlg,
                                         active, g.keys, self._temp_op, s_op,
                                         *extra)
        out_h = np.asarray(out)
        n_acc_h = np.asarray(n_acc)
        jax.block_until_ready(g.cache)
        dt = self._clock() - t0
        self.ctrl.stats["dispatches"] += 2
        self.ctrl.last_step_s = dt
        self.spec_draft_launches += 1
        self.spec_verify_launches += 1
        if kind == "tree":
            self.spec_tree_launches += 1

        if pg is not None:
            # mirror commit_verify: pos += n_accepted + 1 for EVERY slot
            # (free slots drift harmlessly — admission resets both counters)
            pg.host_pos += np.asarray(n_acc_h, np.int64) + 1

        rec_on = self._rec.enabled
        rids = [g.slots[i].rid for i in active_ix] if rec_on else None
        produced = 0
        for i in active_ix:
            req = g.slots[i]
            for j in range(int(n_acc_h[i]) + 1):
                if req.done:
                    break
                req.fed += 1
                if req.fed >= len(req.prompt):
                    req.generated.append(int(out_h[i, j]))
                    produced += 1
                    if rec_on and len(req.generated) == 1:
                        self._rec.request_event(req.rid, "first_token")
            if req.done:
                self._complete(g, i, req, now_s)
            elif pg is not None:
                # rollback: pages grown for rejected draft positions free
                pg.trim(i)
        self.spec_generated_tokens += produced
        self._h_spec.observe(dt * 1e3)
        if rec_on:
            self._rec.launch(
                site, t0, t0 + dt, depth=g.depth, rids=rids,
                occupancy=len(active_ix), tokens=produced,
                widths=[g.widths[i] for i in active_ix],
                key=list(tree_verify_compile_key(g.depth, shape)
                         if kind == "tree"
                         else verify_compile_key(g.depth, shape)))

        # speculative tick wall time lives in the SPEC telemetry only: the
        # controller's per-mode p50 is the SLO policy's per-decode-step
        # estimate, and a 2-launch multi-token tick recorded there would
        # inflate it and mis-steer admission
        tel = self.spec_telemetry.setdefault(
            (g.depth, plan.draft_depth, shape),
            SpecTelemetry(k=depth_budget,
                          tree=shape if kind == "tree" else None,
                          nodes=(tree_node_budget(shape) if kind == "tree"
                                 else shape)))
        tel.record([int(n_acc_h[i]) for i in active_ix], len(active_ix), dt)
        # window entries are PER-CANDIDATE acceptance: a tree's depth
        # fraction measures per-level survival (1-(1-a)^b) and must be
        # inverted so tree and linear launches feed the policy (and the
        # collapse threshold) one comparable number
        g.accept_window.append(per_candidate_accept_rate(
            float(np.mean([n_acc_h[i] for i in active_ix])) / depth_budget,
            shape if kind == "tree" else None))
        spec = self.speculative
        if (len(g.accept_window) == g.accept_window.maxlen
                and float(np.mean(g.accept_window)) < spec.min_accept_rate):
            # acceptance collapsed: drafts cost launches without yielding
            # tokens — fall back to plain stepping, retry after the cooloff
            g.spec_off_until = self.step_count + spec.cooloff_ticks
            self._ev_spec_fallback.emit(
                step=self.step_count, depth=g.depth,
                rate=float(np.mean(g.accept_window)),
                off_until=g.spec_off_until)
            g.accept_window.clear()
        return dt

    def step(self, now_s: float = 0.0) -> float:
        """One engine tick. Returns device wall-time spent (seconds)."""
        self._admit(now_s)
        spent = 0.0
        ticked = False
        for g in self.groups.values():
            active_ix = [i for i, r in enumerate(g.slots) if r is not None]
            if not active_ix:
                continue
            ticked = True
            sel = self._spec_select(g)
            if sel is not None:
                spent += self._spec_tick(g, sel, active_ix, now_s)
                continue
            if g.paging is not None:
                spent += self._paged_tick(g, active_ix, now_s)
                continue
            self.executor.launch("decode")
            toks = np.zeros((self.batch_size, 1), np.int32)
            for i in active_ix:
                toks[i, 0] = g.slots[i].next_input()
            active = self._active_for(g.widths)
            # telemetry attribution: the widest width in flight bounds this
            # launch's active compute
            w_max = max(g.widths[i] for i in active_ix)
            mode = self._mode_by_dw[(g.depth, w_max)]
            rec_on = self._rec.enabled
            rids = [g.slots[i].rid for i in active_ix] if rec_on else None
            t0 = self._clock() if rec_on else 0.0
            logits, g.cache = self.ctrl.timed_step(
                self.params, g.cache, self.executor.put(toks), active,
                mode=mode, tokens=len(active_ix))
            spent += self.ctrl.last_step_s
            self._h_decode.observe(self.ctrl.last_step_s * 1e3)
            self.decode_launches += 1
            self.per_mode_launch_equiv += len(
                {(g.depth, g.widths[i]) for i in active_ix})
            if self.temperature > 0:
                s_op = self.executor.put(np.uint32(self.step_count))
                nxt = np.asarray(self._sample_fn(
                    logits[:, 0], g.keys, self._temp_op, s_op))
            else:
                nxt = np.asarray(
                    jnp.argmax(logits[:, 0, : self.cfg.vocab_size], axis=-1))
            produced = 0
            for i in active_ix:
                req = g.slots[i]
                req.fed += 1
                # once the prompt is consumed, each step's argmax is a fresh
                # generated token (the step that eats the last prompt token
                # also yields the first one)
                if req.fed >= len(req.prompt) and not req.done:
                    req.generated.append(int(nxt[i]))
                    produced += 1
                    if rec_on and len(req.generated) == 1:
                        self._rec.request_event(req.rid, "first_token")
                if req.done:
                    self._complete(g, i, req, now_s)
            if rec_on:
                self._rec.launch(
                    "decode", t0, t0 + self.ctrl.last_step_s, depth=g.depth,
                    rids=rids, occupancy=len(active_ix), tokens=produced,
                    widths=[g.widths[i] for i in active_ix],
                    key=["decode", g.depth])
        self.ticks_with_work += ticked
        self.step_count += 1
        return spent

    def _paged_tick(self, g: _DepthGroup, active_ix: List[int],
                    now_s: float) -> float:
        """One plain decode tick through the bucketed paged executable.

        Host page bookkeeping first (grow each active slot's mapping to its
        write position, CoW-copy any shared page in range), then ONE launch
        of the ``("paged_decode", depth, bucket)`` executable — bucket is
        the smallest compiled table width covering every active slot, so
        variable-length slots re-trace nothing.
        """
        self.executor.launch("paged_decode")
        pg = g.paging
        needed = 1
        for i in active_ix:
            pos = int(pg.host_pos[i])
            pg.ensure_slot(i, pos)
            for src, dst in pg.cow_pairs(i, pos, pos):
                g.cache = self._copy_page(g.cache,
                                          self.executor.put(np.int32(src)),
                                          self.executor.put(np.int32(dst)))
            needed = max(needed, min(pos // pg.ps + 1, pg.cap_pages))
        bucket = self._bucket_for(needed)
        pages_op = self.executor.put(pg.table[:, :bucket].copy())
        toks = np.zeros((self.batch_size, 1), np.int32)
        for i in active_ix:
            toks[i, 0] = g.slots[i].next_input()
        active = self._active_for(g.widths)
        w_max = max(g.widths[i] for i in active_ix)
        mode = self._mode_by_dw[(g.depth, w_max)]
        fn = self.ctrl.aux_step(paged_decode_compile_key(g.depth, bucket))
        self.ctrl.stats["dispatches"] += 1
        rec_on = self._rec.enabled
        rids = [g.slots[i].rid for i in active_ix] if rec_on else None
        t0 = self._clock()
        logits, g.cache = fn(self.params, g.cache, self.executor.put(toks),
                             active, pages_op)
        jax.block_until_ready((logits, g.cache))
        dt = self._clock() - t0
        self.ctrl.telemetry[mode.name].record(dt, len(active_ix))
        self.ctrl.last_step_s = dt
        self._h_decode.observe(dt * 1e3)
        pg.host_pos += 1  # mirror the device counter (ALL slots advance)
        self.decode_launches += 1
        self.per_mode_launch_equiv += len(
            {(g.depth, g.widths[i]) for i in active_ix})
        if self.temperature > 0:
            s_op = self.executor.put(np.uint32(self.step_count))
            nxt = np.asarray(self._sample_fn(
                logits[:, 0], g.keys, self._temp_op, s_op))
        else:
            nxt = np.asarray(
                jnp.argmax(logits[:, 0, : self.cfg.vocab_size], axis=-1))
        produced = 0
        for i in active_ix:
            req = g.slots[i]
            req.fed += 1
            if req.fed >= len(req.prompt) and not req.done:
                req.generated.append(int(nxt[i]))
                produced += 1
                if rec_on and len(req.generated) == 1:
                    self._rec.request_event(req.rid, "first_token")
            if req.done:
                self._complete(g, i, req, now_s)
        if rec_on:
            self._rec.launch(
                "paged_decode", t0, t0 + dt, depth=g.depth, rids=rids,
                occupancy=len(active_ix), tokens=produced, bucket=bucket,
                widths=[g.widths[i] for i in active_ix],
                key=list(paged_decode_compile_key(g.depth, bucket)))
        return dt

    def _bucket_for(self, needed: int) -> int:
        """Smallest AVAILABLE compiled page-table bucket covering ``needed``
        pages. The ladder entry ``PagedLayout.bucket_for`` would pick may
        have been retired by the autoscaler; rounding up to the next live
        bucket is bit-identical (the extra table columns are scratch-backed,
        exactly like a free slot's). The cap bucket is never retired, so a
        covering bucket always exists."""
        return min(b for b in self._avail_buckets if b >= needed)

    # -- page-pool accounting ----------------------------------------------

    def check_paged_invariants(self) -> None:
        """Assert exact page accounting in every depth group (no leaks, no
        double assignment, no refcount drift). No-op for dense engines."""
        for g in self.groups.values():
            if g.paging is not None:
                g.paging.check_invariants()

    def page_pool_stats(self) -> Dict[int, Dict[str, float]]:
        """Per-depth-group pool occupancy + radix telemetry (empty if dense)."""
        return {d: g.paging.stats() for d, g in self.groups.items()
                if g.paging is not None}

    # -- snapshot / restore (fault tolerance) -------------------------------

    def snapshot(self) -> EngineSnapshot:
        """Capture the host-side truth needed to rebuild device state.

        Cheap (a deep copy of request metadata + scalar counters; nothing
        device-resident), so a supervisor can cut one before EVERY tick —
        that per-tick cadence is what makes failover replay exact: the
        interrupted tick is redone wholesale, automatically re-enqueuing any
        speculative work the failure interrupted. Raises for enc-dec /
        frontend archs: replay re-feeds committed tokens, and their prompts
        carry non-token operands the engine does not retain.
        """
        if self.cfg.is_encdec or self.cfg.frontend:
            raise ValueError(
                "snapshot/restore needs a token-only decoder (enc-dec / "
                "frontend prompts carry non-token operands replay cannot "
                "re-feed)")
        groups = {}
        for d, g in self.groups.items():
            groups[d] = GroupSnapshot(
                depth=d,
                slots=copy.deepcopy(g.slots),
                widths=list(g.widths),
                spec_k=g.spec_k,
                spec_tree=g.spec_tree,
                spec_off_until=g.spec_off_until,
                accept_window=list(g.accept_window),
                accept_window_maxlen=g.accept_window.maxlen,
            )
        counters = dict(
            prefills=self.prefills, prefill_s=self.prefill_s,
            prefill_prompt_tokens=self.prefill_prompt_tokens,
            decode_launches=self.decode_launches,
            per_mode_launch_equiv=self.per_mode_launch_equiv,
            ticks_with_work=self.ticks_with_work,
            spec_draft_launches=self.spec_draft_launches,
            spec_verify_launches=self.spec_verify_launches,
            spec_tree_launches=self.spec_tree_launches,
            spec_generated_tokens=self.spec_generated_tokens,
            backpressure_events=self.backpressure_events,
        )
        logs = dict(
            # dict rows, not the legacy 5-tuples: the stream carries
            # ``frontier_gen`` the tuple view deliberately hides
            admission_switch_log=copy.deepcopy(
                list(self._ev_admission_switch.rows)),
            admission_decision_log=copy.deepcopy(
                list(self.admission_decision_log)),
            spec_fallback_log=list(self.spec_fallback_log),
            backpressure_log=copy.deepcopy(list(self.backpressure_log)),
        )
        return EngineSnapshot(
            step_count=self.step_count,
            admission_mode=self.admission_mode.name,
            queues={c: copy.deepcopy(list(q))
                    for c, q in self._queues.items()},
            completed=copy.deepcopy(self.completed),
            expired=copy.deepcopy(self.expired),
            groups=groups,
            counters=counters,
            logs=logs,
            telemetry=self.ctrl.telemetry_state(),
            spec_telemetry=copy.deepcopy(self.spec_telemetry),
            paging_stats=self.page_pool_stats(),
            metrics=self.obs.state_dict(),
            autoscale=(self.autoscaler.state_dict()
                       if self.autoscaler is not None else None),
        )

    def restore(self, snap: EngineSnapshot) -> None:
        """Rebuild this engine's full serving state from ``snap``.

        The engine must be geometry-compatible with the snapshot's source:
        same mode table, batch size, capacity, paged layout, speculative
        plan and sample seed — i.e. another instance from the same factory
        (per-slot PRNG keys regenerate deterministically from the seed, and
        restoring ``step_count`` keeps every slot's ``fold_step`` sample
        stream intact). All existing state is discarded, so a warm standby
        can absorb failovers repeatedly. Device caches are re-materialized
        by ``_replay_group``; counters, logs and telemetry are applied LAST
        so replay launches never leak into the restored accounting — the
        redone tick re-earns its increments and the post-recovery totals
        match a fault-free run.
        """
        if snap.admission_mode not in self.ctrl.mode_by_name:
            raise ValueError(f"snapshot admission mode "
                             f"{snap.admission_mode!r} not in this engine's "
                             f"mode table")
        if set(snap.groups) != set(self.groups):
            raise ValueError(f"snapshot depth groups "
                             f"{sorted(snap.groups)} do not match this "
                             f"engine's {sorted(self.groups)}")
        for gs in snap.groups.values():
            if len(gs.slots) != self.batch_size:
                raise ValueError(f"snapshot batch size {len(gs.slots)} != "
                                 f"engine batch size {self.batch_size}")
        self.step_count = snap.step_count
        mode = self.ctrl.mode_by_name[snap.admission_mode]
        self.admission_mode = mode
        self.ctrl.force_mode(mode)
        self._queues = {c: deque(copy.deepcopy(snap.queues.get(c, [])))
                        for c in SLO_CLASSES}
        self.completed = copy.deepcopy(snap.completed)
        self.expired = copy.deepcopy(snap.expired)
        for d, gs in snap.groups.items():
            g = self.groups[d]
            g.slots = copy.deepcopy(gs.slots)
            g.widths = list(gs.widths)
            g.spec_k = gs.spec_k
            g.spec_tree = gs.spec_tree
            g.spec_off_until = gs.spec_off_until
            g.accept_window = deque(gs.accept_window,
                                    maxlen=gs.accept_window_maxlen)
            g.cache = self.executor.init_cache()
            if self.paged is not None:
                g.paging = _GroupPaging(self.paged, self.cfg,
                                        self.batch_size,
                                        self.cache_capacity)
                for i, r in enumerate(g.slots):
                    if r is not None:
                        booked = self._reserve_pages(g, i, r)
                        assert booked, (
                            f"restore: slot {i} budget cannot be re-booked "
                            f"on a fresh pool")
            self._replay_group(g)
        c = snap.counters
        self.prefills = c["prefills"]
        self.prefill_s = c["prefill_s"]
        self.prefill_prompt_tokens = c["prefill_prompt_tokens"]
        self.decode_launches = c["decode_launches"]
        self.per_mode_launch_equiv = c["per_mode_launch_equiv"]
        self.ticks_with_work = c["ticks_with_work"]
        self.spec_draft_launches = c["spec_draft_launches"]
        self.spec_verify_launches = c["spec_verify_launches"]
        self.spec_tree_launches = c["spec_tree_launches"]
        self.spec_generated_tokens = c["spec_generated_tokens"]
        self.backpressure_events = c["backpressure_events"]
        sw = self._ev_admission_switch
        sw.rows = deque(copy.deepcopy(snap.logs["admission_switch_log"]),
                        maxlen=sw.rows.maxlen)
        ad = self._ev_admission_decision
        ad.rows = deque(copy.deepcopy(snap.logs["admission_decision_log"]),
                        maxlen=ad.rows.maxlen)
        fb = self._ev_spec_fallback
        fb.rows = deque((dict(zip(fb.fields, t))
                         for t in snap.logs["spec_fallback_log"]),
                        maxlen=fb.rows.maxlen)
        bp = self._ev_backpressure
        bp.rows = deque(copy.deepcopy(snap.logs["backpressure_log"]),
                        maxlen=bp.rows.maxlen)
        self.ctrl.load_telemetry_state(snap.telemetry)
        self.spec_telemetry = copy.deepcopy(snap.spec_telemetry)
        if snap.metrics is not None:
            # metrics/trace state come back wholesale LAST so any registry
            # updates issued by the replay above are discarded — the redone
            # tick re-earns them, keeping post-recovery exports equal to a
            # fault-free run's
            self.obs.load_state(snap.metrics)
        # the gauge callback closure must be THIS engine's (a standby that
        # absorbed the snapshot, not the dead source); key replacement evicts
        # any stale registration sharing the registry
        self.metrics.register_callback(self._metric_gauges, key="engine")
        if snap.autoscale is not None:
            if self.autoscaler is not None:
                # rebuild the published/retired executable pool so the next
                # generation decides exactly as the source would have (this
                # is the recovery path — synchronous compiles are allowed)
                self.autoscaler.load_state(snap.autoscale)
            else:
                # bare standby: hold the state until an Autoscaler binds
                # (runtime.autoscale.Autoscaler.bind applies it); the groups
                # may reference published draft shapes the bare table lacks,
                # so a bind must happen before the next speculative tick
                self._pending_autoscale = copy.deepcopy(snap.autoscale)
        if self._rec.enabled:
            for g in self.groups.values():
                for r in g.slots:
                    if r is not None:
                        self._rec.request_event(
                            r.rid, "failover_replay",
                            committed=r.fed, generated=len(r.generated))

    def _replay_prefill(self, g: _DepthGroup, slot: int,
                        req: Request) -> None:
        # same executable + page mapping the original admission used, so the
        # prompt K/V (and any radix block sharing) comes back identical; the
        # replay is not a new admission — no counters, no sampling (the
        # first generated token is already in ``req.generated``)
        if g.paging is not None:
            self._prefill_launch_paged(g, slot, req.prompt)
        else:
            self._prefill_launch(g, slot, req.prompt)

    def _replay_launch(self, g: _DepthGroup, toks: np.ndarray,
                       joined: List[int]) -> None:
        """One lockstep decode launch of the replay (same executables as
        normal ticks: the per-depth dense step or the bucketed paged step).
        Advances every slot's device position by one; paged host mirrors
        advance with it. Only JOINED slots get page mappings grown — a
        not-yet-joined slot's garbage writes land on its scratch page,
        exactly like a free slot's do on normal ticks."""
        active = self._active_for(g.widths)
        pg = g.paging
        if pg is not None:
            needed = 1
            for i in joined:
                pos = int(pg.host_pos[i])
                pg.ensure_slot(i, pos)
                for src, dst in pg.cow_pairs(i, pos, pos):
                    g.cache = self._copy_page(
                        g.cache, self.executor.put(np.int32(src)),
                        self.executor.put(np.int32(dst)))
                needed = max(needed, min(pos // pg.ps + 1, pg.cap_pages))
            bucket = self._bucket_for(needed)
            fn = self.ctrl.aux_step(paged_decode_compile_key(g.depth,
                                                             bucket))
            _, g.cache = fn(self.params, g.cache, self.executor.put(toks),
                            active, self.executor.put(
                                pg.table[:, :bucket].copy()))
            pg.host_pos += 1  # mirror the device counter (ALL slots advance)
        else:
            fn = self.ctrl.step_for(self._any_mode_at(g.depth))
            _, g.cache = fn(self.params, g.cache, self.executor.put(toks),
                            active)
        self.ctrl.stats["dispatches"] += 1

    def _replay_chunk(self, g: _DepthGroup, toks: np.ndarray,
                      joined: List[int]) -> None:
        """One batched replay launch: C >= 2 committed tokens per joined
        slot are verify-scored and force-committed (``n_accepted = C - 1``)
        in ONE launch — bit-identical to C lockstep ``_replay_launch``
        calls by the verify path's exactness property, C-1 launches
        cheaper. Every slot's device position advances by C (non-joined
        slots take garbage writes, exactly as they do under the
        single-token lockstep); paged mappings are grown and privatized to
        cover the whole C-token write range up front."""
        C = toks.shape[1]
        active = self._active_for(g.widths)
        pg = g.paging
        extra = ()
        if pg is not None:
            for i in joined:
                pos = int(pg.host_pos[i])
                pg.ensure_slot(i, pos + C - 1)
                for src, dst in pg.cow_pairs(i, pos, pos + C - 1):
                    g.cache = self._copy_page(
                        g.cache, self.executor.put(np.int32(src)),
                        self.executor.put(np.int32(dst)))
            # chunk executables are engine-cached per (depth, C), not
            # bucketed: replay always ships the full-width table, like the
            # speculative executables do
            extra = (self.executor.put(pg.table[:, :pg.cap_pages].copy()),)
        key = (g.depth, C)
        fn = self._replay_chunks.get(key)
        if fn is None:
            fn = self.executor.replay_chunk_fn(g.depth, C)
            self._replay_chunks[key] = fn
        g.cache = fn(self.params, g.cache, self.executor.put(toks), active,
                     *extra)
        if pg is not None:
            pg.host_pos += C  # mirror the device counter (ALL slots advance)
        self.ctrl.stats["dispatches"] += 1
        self.replay_chunk_launches += 1

    def _replay_group(self, g: _DepthGroup) -> None:
        """Re-materialize one depth group's device cache from host truth.

        A live slot's committed stream is ``(prompt + generated)[:fed]``
        (cache position always equals ``fed``). Prefill-admitted slots
        replay their prompt through the SAME compiled prefill+adopt path
        admission used; everything token-fed — short prompts, and every
        decode- or verify-committed generation — is re-fed through the
        group's own decode executable at the slot's admitted width. That
        split is load-bearing: prefill is width-blind (full-width K/V), so
        a narrow slot's token-fed history MUST come back through the
        width-gated decode path or its cache would hold the wrong values.

        Feeds are staggered to END together: slot ``i`` joins the lockstep
        launches at tick ``T - tail_i`` (reset to position 0, or prefill+
        adopt to position ``plen``) and feeds its remaining committed
        tokens in order, so every launch advances all joined slots' device
        positions together and each slot lands exactly at ``pos == fed``.
        Not-yet-joined and free slots take garbage writes meanwhile (dense:
        position-masked after their reset/adopt; paged: routed to scratch
        pages) — identical to how normal admission recycles slots.
        """
        live = [(i, r) for i, r in enumerate(g.slots) if r is not None]
        pg = g.paging
        if not live:
            return
        tails: Dict[int, Tuple[int, Tuple[int, ...]]] = {}
        for i, r in live:
            committed = (tuple(r.prompt) + tuple(r.generated))[:r.fed]
            if r.prefilled:
                tails[i] = (len(r.prompt), committed[len(r.prompt):])
            else:
                tails[i] = (0, committed)
        T = max(len(t) for _, t in tails.values())
        # committed history is known in full up front, so between join
        # events the lockstep feed is batched: up to ``c_max`` tokens ride
        # ONE verify-scored, force-committed launch (``_replay_chunk``)
        # instead of one decode launch each. The verify window is bounded
        # by the sliding window (commit's rolling scatter must not alias).
        c_max = max(min(8, self.cfg.sliding_window or 8), 1)
        joined: List[int] = []
        t = 0
        while t < T:
            mask = np.zeros(self.batch_size, bool)
            for i, (start, tail) in tails.items():
                if T - len(tail) != t:
                    continue
                r = g.slots[i]
                if r.prefilled:
                    self._replay_prefill(g, i, r)  # pos := plen
                else:
                    mask[i] = True  # pos := 0
                    if pg is not None:
                        pg.host_pos[i] = 0
                joined.append(i)
            if mask.any():
                g.cache = self._reset(g.cache, self.executor.put(mask))
            # feed until the next slot joins (or the end), in chunks
            waiting = [T - len(tail) for i2, (_, tail) in tails.items()
                       if i2 not in joined]
            t_next = min([w for w in waiting if w > t], default=T)
            while t < t_next:
                C = min(c_max, t_next - t)
                toks = np.zeros((self.batch_size, C), np.int32)
                for i in joined:
                    _, tail = tails[i]
                    off = t - (T - len(tail))
                    toks[i, :] = tail[off:off + C]
                if C == 1:
                    self._replay_launch(g, toks, joined)
                else:
                    self._replay_chunk(g, toks, joined)
                t += C
        # slots with nothing to feed: fed == 0 (plain reset) or a prefilled
        # prompt with no generation fed past it (adopt after the launches so
        # the lockstep advances can't disturb its position)
        end_mask = np.zeros(self.batch_size, bool)
        for i, (start, tail) in tails.items():
            if tail:
                continue
            r = g.slots[i]
            if r.prefilled:
                self._replay_prefill(g, i, r)
            else:
                end_mask[i] = True
                if pg is not None:
                    pg.host_pos[i] = 0
        # free slots took garbage position advances during the lockstep
        # launches; rewind them (admission would reset them anyway — this
        # keeps device and host mirrors exact for the invariant checks)
        for i in range(self.batch_size):
            if g.slots[i] is None:
                end_mask[i] = True
                if pg is not None:
                    pg.host_pos[i] = 0
        if end_mask.any():
            g.cache = self._reset(g.cache, self.executor.put(end_mask))
        jax.block_until_ready(g.cache)
        if pg is not None:
            for i, r in live:
                assert int(pg.host_pos[i]) == r.fed, (
                    f"replay drift: slot {i} at pos {int(pg.host_pos[i])} "
                    f"!= fed {r.fed}")

    # -- driving loops ------------------------------------------------------

    @property
    def n_active(self) -> int:
        return sum(g.n_active for g in self.groups.values())

    def _generated_total(self) -> int:
        """Tokens generated so far by completed AND in-flight requests."""
        live = sum(len(r.generated) for g in self.groups.values()
                   for r in g.slots if r is not None)
        return sum(len(r.generated) for r in self.completed) + live

    def run(self, trace: Sequence[Request], *,
            budget_fn: Optional[Callable[[float], float]] = None,
            policy: Optional[SLOPolicy] = None,
            max_steps: int = 100_000) -> Dict[str, float]:
        """Drive an arrival trace to completion on a virtual clock.

        The clock advances by measured device time per tick, so arrival
        interleaving and SLO decisions reflect real step latencies. Returns
        a summary dict (sustained tokens/s, latency stats, switch counts).
        """
        if (policy is None) != (budget_fn is None):
            raise ValueError("policy and budget_fn must be passed together "
                             "(one without the other silently disables the "
                             "SLO loop)")
        pending = deque(sorted(trace, key=lambda r: r.arrival_s))
        clock = 0.0
        busy = 0.0
        # baselines: every counter in the summary is a delta over THIS run
        # (the engine is long-lived and run() may be called repeatedly);
        # only "compiles" stays absolute, for comparison against
        # ``compiles_after_warmup``.
        completed0 = len(self.completed)
        # include in-flight requests: a request admitted by manual step()
        # calls before run() must not attribute its pre-run tokens to this
        # run, and one still in flight at max_steps keeps its in-run tokens
        generated0 = self._generated_total()
        adm_switches0 = len(self.admission_switch_log)
        mode_switches0 = self.ctrl.stats["switches"]
        steps0 = self.step_count
        launches0 = self.decode_launches
        permode0 = self.per_mode_launch_equiv
        ticks0 = self.ticks_with_work
        prefills0 = self.prefills
        prefill_s0 = self.prefill_s
        prefill_toks0 = self.prefill_prompt_tokens
        spec_v0 = self.spec_verify_launches
        spec_t0 = self.spec_tree_launches
        spec_tok0 = self.spec_generated_tokens
        expired0 = len(self.expired)
        bp0 = self.backpressure_events
        while (pending or self.queue or self.n_active) \
                and self.step_count - steps0 < max_steps:
            while pending and pending[0].arrival_s <= clock:
                self.submit(pending.popleft())
            if not self.queue and not self.n_active:
                clock = pending[0].arrival_s  # idle: jump to next arrival
                continue
            if policy is not None and budget_fn is not None:
                qd = {c: len(q) for c, q in self._queues.items()}
                mode = policy.choose(budget_fn(clock), queue_depths=qd)
                if mode.name != self.admission_mode.name:
                    self.admission_decision_log.append(
                        dict(step=self.step_count, **policy.last_decision))
                self.set_admission_mode(mode)
                if self.speculative is not None:
                    self._retune_spec(policy, qd)
            dt = self.step(now_s=clock)
            busy += dt
            clock += dt
        total_generated = self._generated_total() - generated0
        launches = self.decode_launches - launches0
        ticks = self.ticks_with_work - ticks0
        prefills = self.prefills - prefills0
        prefill_s = self.prefill_s - prefill_s0
        prefill_toks = self.prefill_prompt_tokens - prefill_toks0
        return {
            "completed": len(self.completed) - completed0,
            "generated_tokens": total_generated,
            "busy_s": busy,
            "clock_s": clock,
            "sustained_tokens_per_s": total_generated / busy if busy > 0 else 0.0,
            "admission_switches": len(self.admission_switch_log) - adm_switches0,
            "mode_switches": self.ctrl.stats["switches"] - mode_switches0,
            "compiles": self.ctrl.stats["compiles"],
            # launches actually issued (per depth group) vs what per-(depth,
            # width) grouping would have issued for the same slot population
            "decode_launches": launches,
            "per_mode_launch_equiv": self.per_mode_launch_equiv - permode0,
            "launches_per_tick": launches / ticks if ticks else 0.0,
            # prefill admission: whole-prompt consumes and their latency
            "prefills": prefills,
            "prefill_prompt_tokens": prefill_toks,
            "prompt_consume_ms_per_token":
                prefill_s / prefill_toks * 1e3 if prefill_toks else 0.0,
            # speculative decoding: verify launches and the tokens they
            # emitted (tokens/launch > 1 is the decode-launch reduction)
            "spec_verify_launches": self.spec_verify_launches - spec_v0,
            "spec_tree_launches": self.spec_tree_launches - spec_t0,
            "spec_generated_tokens": self.spec_generated_tokens - spec_tok0,
            "spec_tokens_per_launch":
                ((self.spec_generated_tokens - spec_tok0)
                 / max(self.spec_verify_launches - spec_v0, 1)
                 if self.spec_verify_launches > spec_v0 else 0.0),
            "spec_fallbacks": len(self.spec_fallback_log),
            # robustness telemetry: deadline expiries + page-pool deferrals
            "expired": len(self.expired) - expired0,
            "backpressure_events": self.backpressure_events - bp0,
        }

    def _retune_spec(self, policy: "SLOPolicy",
                     queue_depths: Dict[str, int]) -> None:
        """Let the SLO policy re-pick each group's draft shape — a token
        tree, a linear K, or plain stepping — from the compiled table, using
        measured acceptance (rolling window first, lifetime telemetry
        second, optimistic default before any data — DistillCycle-trained
        exits are built to agree)."""
        spec = self.speculative
        for g in self.groups.values():
            plan = self._spec_plan.get(g.depth)
            if plan is None:
                continue
            if g.accept_window:
                rate = float(np.mean(g.accept_window))
            else:
                # lifetime fallback: convert each path's depth fraction to
                # the per-candidate rate before averaging — tree and linear
                # denominators (levels vs K) are otherwise incommensurable
                tels = [t for (d, dd, k), t in self.spec_telemetry.items()
                        if d == g.depth and t.drafted and t.slot_launches]
                if tels:
                    rate = (sum(per_candidate_accept_rate(
                        t.accepted / t.drafted, t.tree) * t.slot_launches
                        for t in tels)
                        / sum(t.slot_launches for t in tels))
                else:
                    rate = 0.75
            if plan.trees:
                kind, shape = policy.choose_tree(
                    plan.trees, plan.ks, rate, queue_depths,
                    min_accept_rate=spec.min_accept_rate)
                if kind == "tree":
                    g.spec_tree, g.spec_k = shape, 0
                elif kind == "linear":
                    g.spec_tree, g.spec_k = None, shape
                elif g.accept_window:
                    # plain stepping — but ONLY on fresh window evidence:
                    # cool off like the in-tick collapse fallback, keeping
                    # the shapes so the group re-probes after the cooloff.
                    # With an empty window the rate is stale lifetime data
                    # (frozen while speculation is off); re-extending the
                    # cooloff from it on every admission switch would
                    # disable speculation permanently.
                    g.spec_off_until = max(
                        g.spec_off_until,
                        self.step_count + spec.cooloff_ticks)
                    g.accept_window.clear()
            elif plan.ks:
                g.spec_tree = None
                g.spec_k = policy.choose_spec_k(plan.ks, rate, queue_depths)

    def spec_telemetry_summary(self) -> Dict[str, Dict[str, float]]:
        """Acceptance telemetry per (depth, draft_depth, draft shape) path
        (``k...`` linear draft lengths, ``t...`` tree branching schedules)."""
        return {f"d{d}<-d{dd}{_shape_label(s)}": t.summary()
                for (d, dd, s), t in self.spec_telemetry.items()
                if t.launches}


def _counter_property(metric: str) -> property:
    def _get(self):
        return self._counter_objs[metric].value

    def _set(self, v):
        self._counter_objs[metric].set(v)

    return property(_get, _set)


for _attr, _metric in ServingEngine._COUNTER_METRICS.items():
    setattr(ServingEngine, _attr, _counter_property(_metric))
del _attr, _metric
