#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's and its control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, one run of the cell as ``bench/run.py`` makes it (weights,
warm-up, a window at the cell's own load, the reference over a seeded
sample of served tokens), and then the control: the same reference in
float8 (e4m3, one scale per tensor), one precision below the bf16 the
configurations state. At every served position the control's first choice
is read against the float32 reference, as a served token is, and judged
by the same comparison and limits. One JSON line per seed: the numbers
compared for the program and for the control, and the spread of both
sets of gaps (``stats``). The program's readings over a dozen seeds give a
limit its lower end, the control's its upper end.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402
from bench.lib import spec as S  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control-seeds", default=None,
                    help="comma-separated seeds whose control is read too "
                         "(default: every seed)")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = S.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl_seeds = (set(seeds) if args.control_seeds is None
                 else {int(s) for s in args.control_seeds.split(",")})
    for seed in seeds:
        res = run.run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                           control=seed in ctl_seeds)
        ctl = res.get("control", {})
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": res["correct"],
                          "compared": res["compared"],
                          "control_correct": ctl.get("correct"),
                          "control_compared": ctl.get("compared"),
                          "stats": ctl.get("stats"),
                          "metrics": res["metrics"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
