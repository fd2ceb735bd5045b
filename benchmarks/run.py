# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark suite entry point.

Each sub-benchmark maps to one paper artifact (see DESIGN.md experiment
index):
  pareto_front        -> Fig. 2   (NeuroForge Pareto front)
  estimator_accuracy  -> Fig. 10 / Table III (analytical vs compiled)
  morph_throughput    -> Table IV (full vs morph throughput + energy)
  depth_morph         -> Fig. 11  (depth-wise reconfiguration)
  width_morph         -> Fig. 12  (width-wise reconfiguration + kernel skip)
  efficiency          -> Table VI (efficiency via reconfiguration)
  dse_speed           -> §II.A    (fast DSE without synthesis-in-loop)
  kernel_bench        -> kernels  (per-kernel microbench)
  roofline_report     -> §Roofline (reads dry-run JSON)
  serve_continuous    -> §Runtime (continuous batching + SLO mode churn)
"""
import sys
import traceback


def main() -> int:
    from benchmarks import (
        depth_morph,
        dse_speed,
        efficiency,
        estimator_accuracy,
        kernel_bench,
        morph_throughput,
        pareto_front,
        roofline_report,
        serve_continuous,
        width_morph,
    )

    from repro.compile_cache import enable_compile_cache
    from repro.kernels.morph_matmul import trace_count

    enable_compile_cache()
    only = sys.argv[1] if len(sys.argv) > 1 else ""
    suites = {
        "pareto_front": pareto_front.run,
        "estimator_accuracy": estimator_accuracy.run,
        "morph_throughput": morph_throughput.run,
        "depth_morph": depth_morph.run,
        "width_morph": width_morph.run,
        "efficiency": efficiency.run,
        "dse_speed": dse_speed.run,
        "kernel_bench": kernel_bench.run,
        "roofline_report": roofline_report.run,
        "serve_continuous": serve_continuous.run,
    }
    if only and only not in suites:
        print(f"unknown suite {only!r}; choose from {sorted(suites)}")
        return 2
    failed = []
    for name, fn in suites.items():
        if only and name != only:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = trace_count()
        try:
            fn()
        except Exception:  # noqa: BLE001 — run the rest, then fail the run
            print(f"{name}/SUITE_ERROR,0.0,{{}}")
            traceback.print_exc()
            failed.append(name)
        # single-executable accounting: morph kernel compiles this suite
        # triggered (width sweeps should add shapes, never widths)
        print(f"# {name}: morph_matmul_compiles={trace_count() - t0}",
              flush=True)
    if failed:
        print(f"# failed suites: {', '.join(failed)}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
