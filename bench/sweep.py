#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate it keeps up
with. Used once, when a cell's rate is fixed; the benchmark's runs never
search.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 20 \
        --rates 1,2,4,6,8 [--seeds 1,2,3]

One process builds and warms the cell's program once (weights from
``--seed``), then serves the mix for ``--seconds`` at each rate in turn,
once for each traffic seed of ``--seeds`` (default ``--seed``), draining
the engine between windows, and prints one JSON line per window:
time-to-first-token and queue-wait percentiles, the inter-token p99,
output tokens per second, and the queue left when the window closed. A rate the engine keeps up with leaves a short
queue and a time to first token that does not grow with the window; the
seeds show how far the order of the same work moves the tails there.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402
from bench.lib import readings as R  # noqa: E402
from bench.lib import spec as S  # noqa: E402
from bench.lib.stats import nearest_rank  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seeds", default=None,
                    help="comma-separated traffic seeds (default: --seed)")
    args = ap.parse_args(argv)
    cell = S.load_cell(args.workload)
    if cell.traffic["arrivals"]["kind"] != "poisson":
        raise SystemExit("a sweep needs open-loop (poisson) arrivals")
    sv = run.Served(cell, seed=args.seed, seconds=args.seconds, trace=False)
    from bench.lib.driver import CompileCounter
    from bench.lib.traffic import Traffic

    compiles = CompileCounter()
    eng = sv.engine
    seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds
             else [args.seed])
    for rate, seed in ((float(r), s) for r in args.rates.split(",")
                       for s in seeds):
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_per_s"] = rate
        sv.driver.traffic = Traffic(mix, seed=seed, seconds=args.seconds,
                                    vocab=sv.conf["vocab_size"])
        win = sv.driver.run(args.seconds, sv.request_cls, compiles)
        queued = sum(1 for r in win.recs if r.in_window and r.admit_t is not None
                     and r.admit_t >= win.t_close)
        print(json.dumps({
            "workload": cell.name, "rate_per_s": rate, "seed": seed,
            "due": sum(r.in_window for r in win.recs),
            "ttft_p50_ms": nearest_rank(R.ttft_ms(win), 0.5),
            "ttft_p90_ms": nearest_rank(R.ttft_ms(win), 0.9),
            "queue_wait_p90_ms": nearest_rank(R.queue_wait_ms(win), 0.9),
            "itl_p99_ms": nearest_rank(R.itl_ms(win), 0.99),
            "output_tokens_per_s": R.tokens_in_window(win) / win.seconds,
            "admitted_after_close": queued,
            "compiles_in_window": win.compiles}), flush=True)
        t0 = time.perf_counter()
        while eng.queue or eng.n_active:
            eng.step()
        run.log(f"drained in {time.perf_counter() - t0:.3f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
