"""``chip_smoke.py`` phases at smoke size on the CPU, and the compile-cache
helper its ``main()`` calls.

The chip runs the script at published widths; these tests run the same
serving and kernel-comparison code at ``smoke_config`` size, so the script
cannot rot between chip runs. The four-chip phase runs on four virtual CPU
devices in a subprocess (the device count is fixed before jax starts).
"""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import smoke_config
from repro.models.model import init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_and_kernel_checks_at_smoke_size():
    cs = _chip_smoke()
    cfg = smoke_config("granite-moe-1b-a400m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    res = cs.serve_phase(params, cfg, capacity=48, prompt_lens=(8, 12),
                         new_tokens=6)
    eng = res["engine"]
    assert res["generated"] == 8 * 6
    assert res["prefills"] == 8
    assert len(res["modes"]) == 3
    assert eng.compiles_after_warmup == eng.ctrl.stats["compiles"] == 2
    checks = cs.kernel_checks(eng, require_mosaic=False)
    # the smoke config is f32 end to end: the reference adds no noise, and
    # off the chip both comparisons run the same math
    assert checks["xla_bf16_vs_f32_max_rel_l2"] < 1e-5
    for name in ("morph_matmul_vs_xla_dot", "fused_vs_unfused"):
        assert checks[name]["argmax_flips"] == 0
        assert checks[name]["max_rel_l2"] < 1e-5, checks[name]


def test_compare_logits_rejects_disagreement():
    import numpy as np

    cs = _chip_smoke()
    a = np.ones((2, 1, 8), np.float32)
    cs.compare_logits("same", a, a, 8, 0.0)
    b = a.copy()
    b[0, 0, 3] = 1.1
    cs.compare_logits("near", b, a, 8, 0.05)
    with pytest.raises(cs.SmokeFailure):
        cs.compare_logits("far", b, a, 8, 0.01)
    c = a.copy()
    c[1, 0, 0] = np.nan
    with pytest.raises(cs.SmokeFailure):
        cs.compare_logits("nan", c, a, 8, 1.0)


_FOUR_CHIP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro.configs import smoke_config
cfg = dataclasses.replace(smoke_config("phi3-medium-14b"), n_layers=4,
                          param_dtype="bfloat16", dtype="bfloat16")
res = cs.four_chip_phase(cfg, 2, capacity=16, prompt_lens=(2, 8),
                         new_tokens=3, steps=2)
assert res["generated"] == 4 * 3, res
assert res["prefills"] == 2, res
assert len(res["compare"]) == 2
print("FOUR_CHIP_OK")
"""


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_CHIP_SCRIPT,
         os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "FOUR_CHIP_OK" in out.stdout


def test_no_tpu_exits_nonzero_without_ok_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stdout


_CACHE_SCRIPT = r"""
import os, sys
import jax, jax.numpy as jnp
from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache
where = enable_compile_cache()
print("CACHE", where, jax.config.jax_compilation_cache_dir)
if sys.argv[1] == "compile":
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
"""


def test_compile_cache_placement(tmp_path):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and holds what is compiled;
    without it the cache is the checkout's ``.jax_cache``."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT, "compile"],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"CACHE {tmp_path} {tmp_path}" in out.stdout
    assert any(tmp_path.iterdir()), "nothing was cached in the given dir"

    del env["JAX_COMPILATION_CACHE_DIR"]
    out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT, "place"],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    want = os.path.join(REPO, ".jax_cache")
    assert f"CACHE {want} {want}" in out.stdout
