"""Pipeline-parallel runner: exactness vs sequential on a multi-device mesh."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.parallel.pipeline_parallel import pipeline_apply, bubble_fraction

mesh = make_mesh((4, 2), ("pod", "model"))
S, d = 4, 16
ws = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) / d ** 0.5

def fn(w, h):
    return jax.nn.relu(h @ w)

x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
with jax.set_mesh(mesh):
    y_pp = pipeline_apply(ws, x, fn, mesh, axis="pod", n_micro=4)
h = x
for s in range(S):
    h = fn(ws[s], h)
assert np.allclose(np.asarray(y_pp), np.asarray(h), atol=1e-5), \
    float(jnp.max(jnp.abs(y_pp - h)))
assert abs(bubble_fraction(4, 4) - 3 / 7) < 1e-9
print("PP_OK")
"""


def test_pipeline_parallel_exact_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-1000:])
    assert "PP_OK" in out.stdout
