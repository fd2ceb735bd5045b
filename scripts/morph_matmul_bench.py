"""Time ``morph_matmul``'s Pallas kernel against the masked XLA dot
(``impl="ref"``) at the attention projections of a 16-slot
granite-moe-1b-a400m decode step, on a TPU.

Each (projection, impl) pair is its own jitted program. All of them run
``--calls`` times inside one profiler session; a pair's device time per
call is the median duration of its program in the trace's ``XLA Modules``
line, so the host's dispatch time is left out. For the kernel it also
gives the median of the Pallas call alone (``XLA Ops``), without the small
operations that build its width operands. The rows of each batch mix the
cell's full and half widths.

    python scripts/morph_matmul_bench.py [--calls 200] [--out FILE]

It needs a TPU and refuses to run without one. It prints one JSON line per
pair and writes them all to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import tempfile
from collections import defaultdict

# (name, K, N, gated dim): x (16, 1, K) @ w (K, N)
CASES = [("q", 1024, 1024, "n"), ("kv", 1024, 512, "n"),
         ("o", 1024, 1024, "k")]
ROWS = 16


def _programs(jax, jnp, morph_matmul):
    progs = {}
    for name, k, n, gate in CASES:
        full = n if gate == "n" else k
        for impl in ("pallas", "ref"):
            def fn(x, w, a, gate=gate, impl=impl):
                if gate == "n":
                    return morph_matmul(x, w, a, None, impl=impl)
                return morph_matmul(x, w, None, a, impl=impl)
            fn.__name__ = f"mm_{name}_{impl}"
            kx, kw = jax.random.split(jax.random.PRNGKey(len(progs)))
            args = (jax.random.normal(kx, (ROWS, 1, k), jnp.bfloat16),
                    jax.random.normal(kw, (k, n), jnp.bfloat16),
                    jnp.array([full, full // 2] * (ROWS // 2), jnp.int32))
            progs[fn.__name__] = (jax.jit(fn), args, k, n)
    return progs


def module_times(trace_dir: str) -> dict:
    """Per program (its name without its id): the durations (ns) of its
    runs on the first TPU's ``XLA Modules`` line, and of the kernel
    (``_morph_matmul_core`` on the ``XLA Ops`` line) inside each run."""
    import bisect
    import glob

    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    mods, kern = [], []
    for plane in ProfileData.from_file(path).planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if line.name == "XLA Modules":
                    mods.append((ev.start_ns, ev.duration_ns,
                                 ev.name.split("(", 1)[0]))
                elif (line.name == "XLA Ops"
                      and ev.name.lstrip("%").startswith("_morph_matmul_core")):
                    kern.append((ev.start_ns, ev.duration_ns))
        break
    mods.sort()
    starts = [m[0] for m in mods]
    out = defaultdict(lambda: {"module": [], "kernel": []})
    for t0, dur, name in mods:
        out[name]["module"].append(dur)
    for t0, dur in kern:
        i = bisect.bisect_right(starts, t0) - 1
        if i >= 0 and t0 < mods[i][0] + mods[i][1]:
            out[mods[i][2]]["kernel"].append(dur)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--out", default="bench/out/morph_matmul_bench.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.kernels.morph_matmul import morph_matmul

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    progs = _programs(jax, jnp, morph_matmul)
    for fn, a, *_ in progs.values():
        jax.block_until_ready(fn(*a))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(args.calls):
                for fn, a, *_ in progs.values():
                    fn(*a)
            jax.block_until_ready([fn(*a) for fn, a, *_ in progs.values()])
        times = module_times(tmp)
    rows = []
    for name, (_, _, k, n) in progs.items():
        t = times.get(f"jit_{name}")
        if t is None:
            raise SystemExit(f"no device events for {name}")
        us = statistics.median(t["module"]) * 1e-3
        rows.append({"program": name, "x": [ROWS, 1, k], "w": [k, n],
                     "calls": len(t["module"]), "device_us_median": us,
                     "device_us_min": min(t["module"]) * 1e-3,
                     "kernel_us_median": (statistics.median(t["kernel"]) * 1e-3
                                          if t["kernel"] else None),
                     "weight_gb_per_s": k * n * 2 / (us * 1e-6) / 1e9,
                     "device_kind": dev.device_kind})
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
