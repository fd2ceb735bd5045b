#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). One run:

1. makes the program's weights on the device from ``--seed``;
2. builds ``ServingEngine`` and warms up exactly the shapes the mix uses;
3. serves the mix for ``--seconds`` through ``submit``/``step`` (with
   ``--trace 1`` under the JAX profiler);
4. frees the program's state and checks a seeded sample of the served
   tokens against the plain float32 reference;
5. prints the metrics: with ``--trace 0`` the cell's end-to-end metrics,
   with ``--trace 1`` its per-layer metrics, each read by
   ``bench/metrics/<name>.py``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, optionally
``breakdown``, and last ``compared``: each number the check compared with
its limit); the last lines of standard error repeat the comparison.
Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero before any work and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import spec as S  # noqa: E402

TRACE_DIR = os.path.join(ROOT, "bench", "out", "trace")
# a --trace 1 run profiles the last this many seconds of its window: a
# steady stretch, small enough to write and read back within the run
TRACE_S = 10.0


class Tracer:
    """The JAX profiler around part of the window: device operations and
    the driver's own spans (not every Python call)."""

    def __init__(self, jax, seconds: float):
        self.jax, self.seconds = jax, seconds

    def start(self) -> None:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)

    def stop(self) -> None:
        self.jax.profiler.stop_trace()


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def fullest(peaks):
    """The peak of the fullest device (``None`` where no device reports
    one)."""
    known = [p for p in peaks if p is not None]
    return max(known) if known else None


def _metric_values(readers, window, metrics):
    out = {}
    for m in metrics:
        v = readers[m["name"]](window)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


class Served:
    """A cell's program, built and warmed up: the weights, the engine and
    the driver that serves the mix through it."""

    def __init__(self, cell: S.Cell, *, seed: int, seconds: float,
                 trace: bool, require_accelerator: bool = True):
        import jax

        devices = jax.devices()
        dev = devices[0]
        if require_accelerator and (dev.platform == "cpu"
                                    or len(devices) < cell.chips):
            raise SystemExit(f"no accelerator for {cell.name}: jax found "
                             f"{len(devices)} {dev.platform} device(s), the "
                             f"cell needs {cell.chips}")
        if not os.path.isdir(os.path.join(SRC, "repro")):
            raise SystemExit(f"the program is not at {SRC}")
        if SRC not in sys.path:
            sys.path.insert(0, SRC)
        from repro.compile_cache import enable_compile_cache
        from repro.configs.base import MorphMode
        from repro.runtime.observability import Observability
        from repro.runtime.serving import Request, ServingEngine

        from bench.lib import driver as D
        from bench.lib.peaks import peaks_for
        from bench.lib.traffic import Traffic

        # the chip's programs are cached in the checkout; CPU tests are not
        cache_dir = enable_compile_cache() if require_accelerator else None
        if cache_dir:  # every program, so that set-up loads and never compiles
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.jax, self.dev, self.devices = jax, dev, devices
        # a cell on several chips serves on a 1 x chips tensor-parallel mesh
        self.mesh = mesh = None
        engine_kw = {}
        if cell.chips > 1:
            from repro.launch.mesh import make_serve_mesh
            from repro.runtime.serving import MeshExecutor

            self.mesh = mesh = make_serve_mesh(1, cell.chips)
            engine_kw["executor"] = MeshExecutor(mesh)
        self.cell, self.seed = cell, seed
        self.conf, self.mix = conf, mix = cell.config, cell.traffic
        self.ref = S.reference_module(conf)
        cfg = S.program_config(conf)
        self.modes = [MorphMode(S.engine_depth(conf, d), w)
                      for d, w in S.mode_table(conf, mix)]
        self.traffic = Traffic(mix, seed=seed, seconds=seconds,
                               vocab=conf["vocab_size"])
        self.peaks = peaks_for(dev.device_kind) if require_accelerator else {}
        self.request_cls = Request
        log(f"{cell.name}: {cfg.name} x {mix['engine']['batch_size']} slots, "
            f"cache {mix['engine']['cache_capacity']}, modes "
            f"{sorted({m.name for m in self.modes})}; device "
            f"{dev.device_kind!r} x {len(devices)}; mesh "
            f"{dict(mesh.shape) if mesh else None}; compile cache {cache_dir}")
        t0 = time.perf_counter()
        self.params = S.make_weights(conf, cfg, self.ref.root_key(seed), mesh)
        jax.block_until_ready(self.params)
        self.t_init = time.perf_counter() - t0
        obs = Observability()
        obs.registry.histogram("engine_decode_step_ms", window=D.HIST_WINDOW)
        self.engine = ServingEngine(
            self.params, cfg, batch_size=mix["engine"]["batch_size"],
            cache_capacity=mix["engine"]["cache_capacity"],
            modes=tuple(dict.fromkeys(sorted(self.modes,
                                             key=lambda m: (m.depth, m.width)))),
            observability=obs, **engine_kw)
        annotate = ((lambda name, **kw: jax.profiler.TraceAnnotation(name, **kw))
                    if trace else None)
        self.driver = D.Driver(self.engine, self.traffic, self.modes,
                               annotate=annotate)
        t0 = time.perf_counter()
        self.engine.warmup()
        self.n_warm = self.driver.warm_up(Request)
        self.t_warm = time.perf_counter() - t0

    def free(self) -> None:
        """Drop the program's device state (weights, caches, programs)."""
        self.engine = self.driver = self.params = None
        gc.collect()


def run_cell(cell: S.Cell, *, seed: int, seconds: float, trace: bool,
             require_accelerator: bool = True, control: bool = False):
    """One run of ``cell``. Returns the result dict (the last line).

    ``control`` also judges the control (the reference in float8, see
    ``bench/control.py``) over the same sample, by the same comparison and
    limit, under the key ``control``; the benchmark's own runs never do."""
    from bench.lib import check as C
    from bench.lib import driver as D

    readers = {m["name"]: S.load_reader(m["name"])
               for m in cell.end_to_end + cell.per_layer}
    sv = Served(cell, seed=seed, seconds=seconds, trace=trace,
                require_accelerator=require_accelerator)
    jax, dev, devices = sv.jax, sv.dev, sv.devices
    conf, ref, traffic = sv.conf, sv.ref, sv.traffic
    compiles = D.CompileCounter()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s: weights {sv.t_init:.3f} s, warm-up "
        f"{sv.t_warm:.3f} s ({sv.n_warm} requests)")
    win = sv.driver.run(seconds, sv.request_cls, compiles,
                        tracer=Tracer(jax, min(seconds, TRACE_S)) if trace else None)
    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    win.setup_s = setup_s
    win.config = conf
    win.peaks = sv.peaks
    win.chips = cell.chips
    win.device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": fullest(peaks),
                  "memory_peak_bytes_per_device": peaks}
    late = [r.submit_t - r.due_t for r in win.recs if r.in_window]
    log(f"window: {len(win.ticks)} ticks, {win.counters['decode_launches']} "
        f"decode launches, {win.counters['prefills']} prefills, "
        f"{win.compiles} compilations inside the window; generator late by "
        f"max {max(late, default=0) * 1e3:.3f} ms over {len(late)} requests; "
        f"peak {peaks} of {[s.get('bytes_limit') for s in stats]} bytes")
    if win.compiles:
        log(f"FAIL: {win.compiles} compilations inside the measured window")
    if trace:
        from bench.lib.trace import reduce_trace
        win.trace = reduce_trace(TRACE_DIR, win)
        win.device["busy_s"] = win.trace.busy_s
        win.device["window_s"] = win.trace.window_s
        log(f"traced {win.trace.window_s:.3f} s: busy per device "
            f"{win.trace.busy_per_device_s} s")
    metrics = _metric_values(readers, win, cell.per_layer if trace
                             else cell.end_to_end)
    # a request is attempted once due in the window (open loop) or, from a
    # backlog, once admitted in it; it fails if it never got a token
    tried = [r for r in win.recs if (r.in_window if traffic.kind == "poisson"
             else r.admit_t is not None and r.admit_t < win.t_close)]
    attempted = len(tried)
    failed = sum(not r.token_t for r in tried)

    # the check: free the program's state, then run the reference
    sample = C.draw_sample(win.recs, seed, conf["check"]["sample_tokens"])
    seqs = C.sequences(sample)
    sv.free()
    t0 = time.perf_counter()
    tm = {}
    ref_logits = ref.forward(conf, seed, seqs, timings=tm) if seqs else []
    stats = C.gap_stats(C.seq_gaps(ref_logits, [s["served"] for s in seqs]),
                        seqs)
    # no served token at all leaves nothing to check: not correct
    limits = conf["check"]["limits"]
    compared = C.compare(stats, limits)
    correct = C.within(compared)
    log(f"reference: {len(seqs)} requests, {time.perf_counter() - t0:.3f} s "
        f"({json.dumps(tm)}); gaps {json.dumps(stats)}")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": win.device}
    if trace:
        result["breakdown"] = win.trace.breakdown
    if control:  # the control's tokens in the program's place, same limits
        ctl_logits = (ref.forward(conf, seed, seqs, precision="fp8")
                      if seqs else [])
        ctl_stats = C.gap_stats(C.seq_gaps(
            ref_logits, [lg.argmax(-1) for lg in ctl_logits]), seqs)
        ctl_compared = C.compare(ctl_stats, limits)
        result["control"] = {"correct": C.within(ctl_compared),
                             "compared": ctl_compared,
                             "stats": {"program": stats, "control": ctl_stats}}
        log(f"control: gaps {json.dumps(ctl_stats)}")
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name}: {c['value']} limit {c['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = S.load_cell(args.workload)
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
