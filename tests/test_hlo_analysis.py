"""Unit tests for the loop-aware HLO cost walker (subprocess: multi-device)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.launch.hlo_analysis import analyze_hlo, _wire_bytes

# ring-cost formulas
assert _wire_bytes("all-reduce", 100.0, 4) == 2 * 0.75 * 100.0
assert _wire_bytes("all-gather", 100.0, 4) == 0.75 * 100.0
assert _wire_bytes("collective-permute", 100.0, 4) == 100.0
assert _wire_bytes("all-reduce", 100.0, 1) == 0.0

mesh = make_mesh((2, 4), ("data", "model"))

def f(x, ws):
    def body(h, w):
        return jax.nn.relu(h @ w), None
    h, _ = jax.lax.scan(body, x, ws)
    return h

xs = jax.ShapeDtypeStruct((32, 64), jnp.float32)
ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
with jax.set_mesh(mesh):
    compiled = jax.jit(
        f, in_shardings=(NamedSharding(mesh, P("data", "model")),
                         NamedSharding(mesh, P(None, "model", None))),
        out_shardings=NamedSharding(mesh, P("data", "model"))
    ).lower(xs, ws).compile()
cost = analyze_hlo(compiled.as_text(), 8)

# loop accounting: 5 iterations of a (16x64)@(64x16) local dot
expect_flops = 5 * 2 * 16 * 64 * 16
assert abs(cost.flops - expect_flops) / expect_flops < 0.25, cost.flops
# all-reduce of f32[16,64] per iteration, ring over model=4
expect_wire = 5 * 2 * (3 / 4) * (16 * 64 * 4)
assert abs(cost.coll_wire_bytes - expect_wire) / expect_wire < 0.01, \
    cost.coll_wire_bytes
assert 5 in cost.while_trips
print("HLO_OK")
"""

COMPRESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.runtime import compressed_psum, init_error_buffer

mesh = make_mesh((8,), ("data",))

grads = {"w": jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4) / 10.0}
errs = init_error_buffer({"w": grads["w"][0]})

def worker(g, e):
    red, new_e = compressed_psum({"w": g}, {"w": e}, "data")
    return red["w"], new_e["w"]

f = jax.shard_map(worker, mesh=mesh, in_specs=(P("data"), P()),
                  out_specs=(P(), P("data")), check_vma=False)
with jax.set_mesh(mesh):
    red, _ = f(grads["w"], errs["w"])
expected = np.mean(np.asarray(grads["w"]), axis=0)
got = np.asarray(red)[0] if red.ndim == 2 else np.asarray(red)
np.testing.assert_allclose(got, expected, atol=0.05)
print("COMPRESS_OK")
"""


def _run(script):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-1500:])
    return out.stdout


def test_walker_loop_accounting_and_ring_costs():
    assert "HLO_OK" in _run(SCRIPT)


def test_compressed_psum_multidevice():
    assert "COMPRESS_OK" in _run(COMPRESS_SCRIPT)
