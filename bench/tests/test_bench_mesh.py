"""A cell on several chips, at a small size on the CPU: the harness serves
it through a ``MeshExecutor`` over a 1 x chips mesh with the weights made
sharded, and the readers that count chips. The mesh runs on four virtual
CPU devices in a subprocess (the device count is fixed before jax starts);
a one-chip cell keeps the one-device path."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import run
from bench.lib import engine_spans as E
from bench.lib import readings as R
from bench.lib.driver import Launch, Tick, Window
from bench.lib.trace import TraceSummary
from bench.tests.tiny import tiny_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**31 + 7

_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from bench import run
from bench.lib import spec as S
from bench.tests.tiny import tiny_cell, tiny_config

what, seed = sys.argv[1], int(sys.argv[2])
if what == "weights":
    from repro.launch.mesh import make_serve_mesh
    conf = tiny_config("phi3-medium-14b", f32=False)
    cfg = S.program_config(conf)
    key = jax.random.PRNGKey(seed % 2**32)
    mesh = make_serve_mesh(1, 4)
    one = jax.tree_util.tree_leaves_with_path(S.make_weights(conf, cfg, key))
    four = jax.tree_util.tree_leaves(S.make_weights(conf, cfg, key, mesh))
    print(json.dumps({
        "leaves": len(one),
        "equal": [bool(np.array_equal(np.asarray(a), np.asarray(b)))
                  for (_, a), b in zip(one, four)],
        "on_mesh": all(b.sharding.mesh.shape == mesh.shape for b in four),
        "split": sum(len(b.sharding.device_set) == 4
                     and not b.sharding.is_fully_replicated for b in four),
        "one_device": all(len(a.sharding.device_set) == 1 for _, a in one)}))
else:
    import jax.numpy as jnp
    from repro.core import morph
    import importlib
    MM = importlib.import_module("repro.kernels.morph_matmul")
    real = morph.decode_step
    if what in ("sharded_kernel", "exchange_dropped"):
        # the chip's path: each projection's kernel per shard in shard_map
        # (the XLA kernel here: the CPU runs no Mosaic kernel)
        sharded = MM.sharded_morph_matmul
        MM.default_impl = lambda *a, **kw: "pallas"
        MM.sharded_morph_matmul = lambda *a, **kw: sharded(*a, **kw,
                                                           impl="ref")
    if what == "exchange_dropped":
        # each shard keeps its own partial sum of a row-split projection
        jax.lax.psum = lambda x, axis_name, **kw: x
    if what == "state_unchanged":
        def step(params, cache, tokens, cfg, **kw):
            logits, _ = real(params, cache, tokens, cfg, **kw)
            return logits, cache
        morph.decode_step = step
    if what == "token_altered":
        def step(params, cache, tokens, cfg, **kw):
            logits, new = real(params, cache, tokens, cfg, **kw)
            return logits.at[..., 7].add(jnp.asarray(1e3, logits.dtype)), new
        morph.decode_step = step
    cell = tiny_cell("phi3-medium-14b", "chat-tp4", chips=4)
    sv_cls = run.Served
    seen = {}

    class Served(sv_cls):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            seen["executor"] = type(self.engine.executor).__name__
            seen["mesh"] = dict(self.mesh.shape)
    run.Served = Served
    res = run.run_cell(cell, seed=seed, seconds=1.5, trace=False,
                       require_accelerator=False, control=what == "control")
    print(json.dumps({"chips": cell.chips, **seen, **res}))
"""


def _sub(what: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [ROOT, os.path.join(ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", _SCRIPT, what, str(SEED)],
                         capture_output=True, text=True, env=env, timeout=900,
                         cwd=ROOT)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_four_chip_cell_is_served_on_a_mesh_and_correct():
    res = _sub("sound")
    assert res["chips"] == 4 and res["device"]["count"] == 4
    assert res["executor"] == "MeshExecutor"
    assert res["mesh"] == {"data": 1, "model": 4}
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_four_chip_control_fails_where_the_program_passes():
    """The reference in float8 in the program's place, judged by the same
    comparison and limit, is not correct; the program is."""
    res = _sub("control")
    assert res["correct"], res["compared"]
    assert res["control"]["correct"] is False, res["control"]


def test_four_chip_cell_with_the_kernel_per_shard_is_correct():
    """The path the chip runs: every width-gated projection's kernel inside
    ``shard_map``, a row split summed over the chips."""
    res = _sub("sharded_kernel")
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered",
                                   "exchange_dropped"])
def test_four_chip_cell_with_a_fault_is_not_correct(fault):
    res = _sub(fault)
    assert res["executor"] == "MeshExecutor"
    assert res["correct"] is False, res["compared"]


def test_sharded_weights_equal_the_one_device_weights():
    res = _sub("weights")
    assert res["leaves"] > 0 and len(res["equal"]) == res["leaves"]
    assert all(res["equal"]), res["equal"]
    assert res["on_mesh"] and res["split"] > 0 and res["one_device"]


def test_one_chip_cell_keeps_the_local_executor_and_one_device_weights():
    import jax

    cell = tiny_cell("granite-moe-1b-a400m", "chat")
    assert cell.chips == 1
    sv = run.Served(cell, seed=SEED, seconds=1.0, trace=False,
                    require_accelerator=False)
    from repro.runtime.serving import LocalExecutor  # on the path once served

    assert sv.mesh is None
    assert type(sv.engine.executor) is LocalExecutor
    for leaf in jax.tree_util.tree_leaves(sv.params):
        assert len(leaf.sharding.device_set) == 1
    sv.free()


# -- the readers that count chips --------------------------------------------


def _mfu_window(chips: int) -> Window:
    ticks = [Tick(0, 1.0, 1.1, [Launch(0, 40, [(1.0, 300), (0.5, 900)])]),
             Tick(1, 1.2, 1.3, [Launch(1, 20, [(1.0, 100)])])]
    win = Window(t_open=0.0, seconds=2.0, recs=[], ticks=ticks, counters={},
                 decode_step_ms=[], compiles=0, chips=chips)
    win.config = tiny_cell("phi3-medium-14b", "chat-tp4", chips=4).config
    win.peaks = {"bf16_flops_per_s": 197e12}
    win.trace = TraceSummary(t0=0.5, t1=2.0, window_s=1.5, busy_s=1.0,
                             decode_s=0.04)
    return win


def test_decode_mfu_counts_every_chip_of_the_cell():
    one, four = R.decode_mfu(_mfu_window(1)), R.decode_mfu(_mfu_window(4))
    assert one > 0
    assert four == pytest.approx(one / 4, rel=1e-12)


MS = 1e6  # ns


def test_decode_collective_time_counts_collectives_inside_paired_steps():
    sp = lambda name, a, b: E.Span(name, a * MS, b * MS, {})  # noqa: E731
    spans = [sp("bench.tick", 0, 100), sp("engine.step", 1, 99),
             sp("engine.prefill", 2, 9),
             sp("engine.decode", 10, 50), sp("engine.decode", 55, 95)]
    modules = [(3, 8, "jit_pf(a)"), (12, 40, "jit_step(a)"),
               (42, 48, "jit_argmax(a)"), (60, 90, "jit_step(b)")]
    collectives = [(4, 5),                 # in the prefill: not counted
                   (13, 14), (20, 23.5),   # in the first step: 4.5 ms
                   (44, 45),               # in the argmax: not counted
                   # in the second step: 3 ms (the last starts inside it
                   # and counts whole)
                   (61, 62), (89, 91)]
    et = E.EngineTrace(lo=0, hi=100 * MS, spans=spans, ticks=spans[:1],
                       modules=[(a * MS, b * MS, n) for a, b, n in modules],
                       collectives=[(a * MS, b * MS) for a, b in collectives])
    assert E.decode_collective_ms(et) == pytest.approx(3.75)
    et.collectives = []  # a cell on one chip: nothing to read
    assert E.decode_collective_ms(et) is None
    assert E.decode_collective_ms(None) is None


def test_collective_operations_are_named_by_instruction():
    from bench.lib.trace import is_collective

    for name in ("%all-reduce.13", "%all-gather.51", "%reduce-scatter.2",
                 "%all-to-all.1", "%collective-permute-done.3",
                 "%all-gather-start.2 = bf16[16] all-gather-start(%x)",
                 "%async-collective-start.1", "%async-collective-done.1",
                 "%psum.3", "%all_gather.2", "%ppermute"):
        assert is_collective(name), name
    for name in ("%fusion.216", "%copy.89", "%all-reducer.1",
                 "%_morph_matmul_core.16", "%while.2", "%psum_fusion.1"):
        assert not is_collective(name), name


def test_memory_peak_is_the_fullest_device():
    assert run.fullest([7_000, 9_500, 8_000, 9_400]) == 9_500
    assert run.fullest([10_251_446_272]) == 10_251_446_272
    assert run.fullest([None, 5]) == 5 and run.fullest([None]) is None


def test_device_planes_are_the_cell_chips_in_order():
    """The trace's busy time is the mean over the planes of the cell's
    chips; the rest is read on chip 0's."""
    from bench.lib.trace import device_planes

    class Plane:
        def __init__(self, name):
            self.name = name

    class PD:
        planes = [Plane(n) for n in ("/host:CPU", "/device:TPU:2",
                                     "/device:TPU:0", "/device:CUSTOM:x",
                                     "/device:TPU:1", "/device:TPU:3")]

    assert [p.name for p in device_planes(PD())] == ["/device:TPU:0"]
    assert [p.name for p in device_planes(PD(), 4)] == [
        "/device:TPU:0", "/device:TPU:1", "/device:TPU:2", "/device:TPU:3"]
