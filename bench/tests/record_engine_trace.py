#!/usr/bin/env python3
"""Record ``bench/tests/engine_data/engine_trace.xplane.pb`` on a TPU.

    python3 bench/tests/record_engine_trace.py [out_path]

Serves the tiny granite-moe chat cell (``bench/tests/tiny.py``: 4 layers of
width 64, 4 slots) through ``ServingEngine`` after its warm-up, and profiles
five ticks, each inside a ``bench.tick`` span carrying its number as the
driver does: ticks 0 and 2 admit requests (a prefill each, prompts of 16
and 32 tokens), the others only decode. The trace is then cut to what the
readers use (``trim``): the host's ``bench.*``/``engine.*``/``host.*``
spans, and the device's module executions and operations, each operation
named by its instruction alone; the programs' HLO goes.
"""
from __future__ import annotations

import glob
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT = os.path.join(ROOT, "bench", "tests", "engine_data",
                   "engine_trace.xplane.pb")
ADMIT = {0: (16, 32), 2: (16,)}  # tick -> prompt lengths submitted before it


def main(out: str = OUT) -> int:
    sys.path.insert(0, ROOT)
    import jax

    from bench import run
    from bench.tests.tiny import tiny_cell

    sv = run.Served(tiny_cell("granite-moe-1b-a400m", "chat"), seed=2**31 + 7,
                    seconds=1.0, trace=True)
    eng = sv.engine
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for tick in range(5):
        for k, plen in enumerate(ADMIT.get(tick, ())):
            eng.submit(sv.request_cls(rid=10 * tick + k,
                                      prompt=tuple(range(1, plen + 1)),
                                      max_new_tokens=8))
        with jax.profiler.TraceAnnotation("bench.tick", tick=tick):
            eng.step()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    trim(path, out)
    print(f"{out}: {os.path.getsize(out)} bytes, device "
          f"{jax.devices()[0].device_kind}")
    return 0


def trim(src: str, dst: str) -> None:
    """Write ``src``'s trace to ``dst`` with only the host plane's
    ``bench.*``/``engine.*``/``host.*`` events and the first TPU's module
    and operation lines."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    tpu = next(p.name for p in space.planes if p.name.startswith("/device:TPU"))
    keep = [p for p in space.planes if p.name in ("/host:CPU", tpu)]
    for plane in keep:
        host = plane.name == "/host:CPU"
        used = set()
        lines = []
        for line in plane.lines:
            if host:
                evs = [e for e in line.events if plane.event_metadata[
                    e.metadata_id].name.startswith(("bench.", "engine.", "host."))]
            elif line.name in ("XLA Modules", "XLA Ops"):
                evs = list(line.events)
            else:
                continue
            if evs:
                del line.events[:]
                line.events.extend(evs)
                used.update(e.metadata_id for e in evs)
                lines.append(line)
        del plane.lines[:]
        plane.lines.extend(lines)
        for mid in list(plane.event_metadata):
            md = plane.event_metadata[mid]
            if mid not in used:
                del plane.event_metadata[mid]
            elif not host:  # an operation's HLO text -> its instruction
                name = md.name.split(" = ")[0]
                md.Clear()
                md.id, md.name = mid, name
    del space.planes[:]
    space.planes.extend(keep)
    del space.hostnames[:]
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
