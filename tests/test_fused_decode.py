"""Fused decode/verify superkernel (``kernels.fused_decode``) harness.

The fused path must be a pure implementation detail: flipping ``fused=True``
on the model-level entry points (and the serving engine) may never change a
logit bit off-TPU, never add an executable, and never re-trace under width /
position churn. This file proves it in layers:

* model-level bit-identity: ``decode_step`` / ``verify_step`` /
  ``verify_tree`` with ``fused=True`` vs the unfused primitives, across
  full attention, sliding windows, int8 KV quant, mixed per-slot widths and
  a paged pool (the ref impl mirrors the unfused op sequence exactly, so
  off-TPU equality is exact, not approximate);
* kernel-level: the Pallas superkernel (``interpret=True`` on CPU) against
  the mirrored ref, seeded sweep over widths x SWA x quant x paging;
* zero-retrace: one executable per jitted wrapper regardless of runtime
  width operands (``trace_count`` advances at trace time only);
* engine-level: a ``fused=True`` ServingEngine emits token-identical
  streams with the same ``compiles_after_warmup`` as the unfused engine —
  dense plain serving and paged token-tree speculation, locally and on a
  2x4 CPU mesh subprocess.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import elastic
from repro.kernels import fused_decode as FD
from repro.models.model import (decode_step, init_decode_cache, init_params,
                                verify_step, verify_tree)
from repro.models.paged import PagedLayout, init_paged_cache
from repro.runtime.serving import Request, ServingEngine
from repro.runtime.speculative import SpecConfig, tree_topology

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VARIANTS = {
    "full": lambda: smoke_config("tinyllama-1.1b"),
    "swa": lambda: dataclasses.replace(smoke_config("mixtral-8x22b"),
                                       sliding_window=6),
    "kv_quant": lambda: dataclasses.replace(smoke_config("tinyllama-1.1b"),
                                            kv_quant=True),
}


def _mixed_active(cfg, widths=(0.5, 1.0)):
    return jax.tree_util.tree_map(
        jnp.asarray, elastic.active_widths_batch(cfg, list(widths)))


def _leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


def _assert_tree_equal(a, b, msg=""):
    for (pa, x), (_, y) in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{msg} {jax.tree_util.keystr(pa)}")


def _warm(params, cfg, cache, active, n=3, fused=False):
    for t in range(n):
        tok = jnp.asarray([[3 + t], [5 + t]], jnp.int32)
        _, cache = decode_step(params, cache, tok, cfg, active=active,
                               fused=fused)
    return cache


# ---------------------------------------------------------------------------
# model-level bit-identity (the acceptance bar: fused is a pure detail)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_fused_decode_step_bit_identical(variant):
    cfg = VARIANTS[variant]()
    params = init_params(jax.random.PRNGKey(0), cfg)
    active = _mixed_active(cfg)
    cache = _warm(params, cfg, init_decode_cache(cfg, 2, 16, per_slot=True),
                  active)
    tok = jnp.asarray([[7], [2]], jnp.int32)
    lg_u, c_u = decode_step(params, cache, tok, cfg, active=active)
    lg_f, c_f = decode_step(params, cache, tok, cfg, active=active,
                            fused=True)
    np.testing.assert_array_equal(np.asarray(lg_u), np.asarray(lg_f))
    _assert_tree_equal(c_u, c_f, variant)
    # the fused flag composes with depth truncation (shallow exits)
    lg_u1, _ = decode_step(params, cache, tok, cfg, depth=1, active=active)
    lg_f1, _ = decode_step(params, cache, tok, cfg, depth=1, active=active,
                           fused=True)
    np.testing.assert_array_equal(np.asarray(lg_u1), np.asarray(lg_f1))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_fused_verify_and_tree_bit_identical(variant):
    cfg = VARIANTS[variant]()
    params = init_params(jax.random.PRNGKey(0), cfg)
    active = _mixed_active(cfg)
    cache = _warm(params, cfg, init_decode_cache(cfg, 2, 16, per_slot=True),
                  active)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 3)), jnp.int32)
    lg_u, p_u = verify_step(params, cache, toks, cfg, active=active)
    lg_f, p_f = verify_step(params, cache, toks, cfg, active=active,
                            fused=True)
    np.testing.assert_array_equal(np.asarray(lg_u), np.asarray(lg_f))
    _assert_tree_equal(p_u, p_f, variant)

    topo = tree_topology((2, 1))
    ttoks = jnp.asarray(rng.integers(1, cfg.vocab_size,
                                     (2, topo.n_nodes)), jnp.int32)
    lg_u, p_u = verify_tree(params, cache, ttoks, cfg, tree=topo,
                            active=active)
    lg_f, p_f = verify_tree(params, cache, ttoks, cfg, tree=topo,
                            active=active, fused=True)
    np.testing.assert_array_equal(np.asarray(lg_u), np.asarray(lg_f))
    _assert_tree_equal(p_u, p_f, f"{variant} tree")


def test_fused_paged_decode_bit_identical():
    """Paged pool + table operand: fused and unfused walk the same physical
    pages, bit for bit."""
    cfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    layout = PagedLayout(page_size=4)
    cache = init_paged_cache(cfg, 2, 16, layout)
    table = jnp.asarray(np.arange(2 * 4, dtype=np.int32).reshape(2, 4))
    active = _mixed_active(cfg)
    for t in range(5):  # cross a page boundary
        tok = jnp.asarray([[3 + t], [5 + t]], jnp.int32)
        _, cache = decode_step(params, cache, tok, cfg, active=active,
                               pages=table, page_size=4)
    tok = jnp.asarray([[7], [2]], jnp.int32)
    lg_u, c_u = decode_step(params, cache, tok, cfg, active=active,
                            pages=table, page_size=4)
    lg_f, c_f = decode_step(params, cache, tok, cfg, active=active,
                            pages=table, page_size=4, fused=True)
    np.testing.assert_array_equal(np.asarray(lg_u), np.asarray(lg_f))
    _assert_tree_equal(c_u, c_f, "paged")
    # bucketed table widths (PR 6 compile keys) stay bit-identical too
    for b in (2, 3):
        lg_u, _ = decode_step(params, cache, tok, cfg, active=active,
                              pages=table[:, :b], page_size=4)
        lg_f, _ = decode_step(params, cache, tok, cfg, active=active,
                              pages=table[:, :b], page_size=4, fused=True)
        np.testing.assert_array_equal(np.asarray(lg_u), np.asarray(lg_f))


# ---------------------------------------------------------------------------
# kernel-level: Pallas (interpret) vs the mirrored ref, seeded sweep
# ---------------------------------------------------------------------------


def _layer_operands(cfg, seed, paged=False):
    """One attention layer's params + cache + a warmed position state."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    gp = jax.tree_util.tree_map(lambda a: a[0], params["stack"])
    lp = gp["pos0"]["attn"]
    if paged:
        # page size must divide any sliding window (rolling buffer wraps at
        # page boundaries), so the swa variant (window 6) drops to 2; the
        # rolling buffer also caps each slot's pages at window/ps
        ps = 4 if not (cfg.sliding_window and cfg.sliding_window % 4) else 2
        layout = PagedLayout(page_size=ps)
        cache = init_paged_cache(cfg, 2, 16, layout)
        npg = (cfg.sliding_window or 16) // ps
        pages = jnp.asarray(
            np.arange(2 * npg, dtype=np.int32).reshape(2, npg))
    else:
        cache = init_decode_cache(cfg, 2, 16, per_slot=True)
        pages, ps = None, 0
    active = _mixed_active(cfg)
    cache = _warm(params, cfg, cache, active, n=3) if not paged else cache
    if paged:
        for t in range(3):
            tok = jnp.asarray([[3 + t], [5 + t]], jnp.int32)
            _, cache = decode_step(params, cache, tok, cfg, active=active,
                                   pages=pages, page_size=ps)
    gc = jax.tree_util.tree_map(lambda a: a[0], cache["stack"])["pos0"]
    lc = {k: v for k, v in gc.items() if not k.startswith("cross_")}
    pos = cache["pos"]
    return lp, lc, pos, active, pages, ps


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_pallas_kernel_matches_ref(variant, paged):
    """The superkernel itself (interpret mode off-TPU) against the ref that
    mirrors the unfused op sequence — float tolerance, seeded sweep."""
    cfg = VARIANTS[variant]()
    for seed in (0, 1):
        lp, lc, pos, active, pages, ps = _layer_operands(cfg, seed,
                                                         paged=paged)
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((2, 1, cfg.d_model)),
                        jnp.dtype(cfg.dtype))
        o_r, c_r = FD.fused_decode_step(lp, x, lc, pos, cfg, active=active,
                                        pages=pages, page_size=ps,
                                        impl="ref")
        o_p, c_p = FD.fused_decode_step(lp, x, lc, pos, cfg, active=active,
                                        pages=pages, page_size=ps,
                                        impl="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(o_r, np.float32),
                                   np.asarray(o_p, np.float32),
                                   atol=2e-5, rtol=1e-4)
        for (pa, a), (_, b) in zip(_leaves(c_r), _leaves(c_p)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=2e-5, rtol=1e-4,
                err_msg=f"{variant} seed{seed} {jax.tree_util.keystr(pa)}")


def test_pallas_verify_kernel_matches_ref():
    """Verify + tree-verify superkernel vs ref: the statically baked
    ancestor mask must reproduce the dense additive-bias scores."""
    cfg = smoke_config("tinyllama-1.1b")
    lp, lc, pos, active, _, _ = _layer_operands(cfg, 0)
    rng = np.random.default_rng(2)
    topo = tree_topology((2, 1))
    for nd, tb, S in [(None, None, 3),
                      (topo.depths, topo.ancestor_bias, topo.n_nodes)]:
        x = jnp.asarray(rng.standard_normal((2, S, cfg.d_model)),
                        jnp.dtype(cfg.dtype))
        o_r, kv_r = FD.fused_verify(lp, x, lc, pos, cfg, active=active,
                                    node_depth=nd, tree_bias=tb, impl="ref")
        o_p, kv_p = FD.fused_verify(lp, x, lc, pos, cfg, active=active,
                                    node_depth=nd, tree_bias=tb,
                                    impl="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(o_r, np.float32),
                                   np.asarray(o_p, np.float32),
                                   atol=2e-5, rtol=1e-4)
        for (pa, a), (_, b) in zip(_leaves(kv_r), _leaves(kv_p)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=2e-5, rtol=1e-4, err_msg=jax.tree_util.keystr(pa))


def test_default_impl_dispatch():
    """impl="auto" == morph_matmul's rule: pallas on TPU, ref elsewhere."""
    from repro.kernels.morph_matmul import default_impl as mm_default
    assert FD.default_impl() == mm_default()
    assert FD.default_impl() == (
        "pallas" if jax.default_backend() == "tpu" else "ref")


# ---------------------------------------------------------------------------
# zero-retrace: width churn is data, not a compile key
# ---------------------------------------------------------------------------


def test_fused_zero_retrace_across_widths():
    cfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_decode_cache(cfg, 2, 16, per_slot=True)

    step = jax.jit(lambda p, c, t, a: decode_step(p, c, t, cfg, active=a,
                                                  fused=True))
    FD.reset_trace_count()
    tok = jnp.asarray([[3], [5]], jnp.int32)
    for widths in ([1.0, 1.0], [0.5, 1.0], [1.0, 0.5], [0.5, 0.5]):
        _, cache = step(params, cache, tok, _mixed_active(cfg, widths))
    assert FD.trace_count() == 1, \
        f"width churn re-traced the fused decode: {FD.trace_count()}"

    ver = jax.jit(lambda p, c, t, a: verify_step(p, c, t, cfg, active=a,
                                                 fused=True))
    FD.reset_trace_count()
    toks = jnp.asarray([[3, 4, 5], [5, 6, 7]], jnp.int32)
    for widths in ([1.0, 1.0], [0.5, 1.0]):
        ver(params, cache, toks, _mixed_active(cfg, widths))
    assert FD.trace_count() == 1, \
        f"width churn re-traced the fused verify: {FD.trace_count()}"


# ---------------------------------------------------------------------------
# engine-level: fused serving is a pure flag
# ---------------------------------------------------------------------------

SPECS = [(1, 8), (3, 6), (5, 9), (1, 5)]


def _drive(eng):
    for rid, (plen, n_new) in enumerate(SPECS):
        eng.submit(Request(rid=rid, prompt=tuple(range(1, 1 + plen)),
                           max_new_tokens=n_new))
    while eng.queue or eng.n_active:
        eng.step()
    return {r.rid: tuple(r.generated) for r in eng.completed}


@pytest.mark.parametrize("paged,spec", [
    (None, None),
    (PagedLayout(page_size=4), SpecConfig(ks=(), trees=((2, 1),))),
], ids=["dense_plain", "paged_tree"])
def test_fused_engine_token_identical_no_retrace(paged, spec):
    cfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), cfg)

    def build(fused):
        eng = ServingEngine(params, cfg, batch_size=2, cache_capacity=32,
                            prefill_threshold=4, paged=paged,
                            speculative=spec, fused=fused)
        eng.warmup()
        return eng

    ref = _drive(build(False))
    eng = build(True)
    frozen = eng.ctrl.stats["compiles"]
    traces0 = FD.trace_count()
    out = _drive(eng)
    assert out == ref, "fused engine diverged from unfused streams"
    assert eng.ctrl.stats["compiles"] == frozen
    assert FD.trace_count() == traces0, "fused engine re-traced mid-traffic"
    # the fused flag adds NO executables: same warmup compile count
    assert eng.compiles_after_warmup == build(False).compiles_after_warmup


_MESH_FUSED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import smoke_config
from repro.launch.mesh import make_serve_mesh
from repro.models.model import init_params
from repro.runtime.serving import MeshExecutor, Request, ServingEngine
from repro.runtime.speculative import SpecConfig

SPECS = [(1, 8), (3, 6), (5, 9)]

def drive(eng):
    for rid, (plen, n_new) in enumerate(SPECS):
        eng.submit(Request(rid=rid, prompt=tuple(range(1, 1 + plen)),
                           max_new_tokens=n_new))
    while eng.queue or eng.n_active:
        eng.step()
    return {r.rid: tuple(r.generated) for r in eng.completed}

cfg = smoke_config("tinyllama-1.1b")
params = init_params(jax.random.PRNGKey(0), cfg)
spec = SpecConfig(ks=(2,))
el = ServingEngine(params, cfg, batch_size=2, cache_capacity=32,
                   prefill_threshold=4, speculative=spec, fused=True)
el.warmup()
out_l = drive(el)
em = ServingEngine(params, cfg, batch_size=2, cache_capacity=32,
                   prefill_threshold=4, speculative=spec, fused=True,
                   executor=MeshExecutor(make_serve_mesh(2, 4)))
em.warmup()
assert em.compiles_after_warmup == el.compiles_after_warmup
tr0 = em.ctrl.trace_counter["n"]
out_m = drive(em)
assert out_m == out_l, (out_m, out_l)
assert em.ctrl.trace_counter["n"] == tr0, "mesh fused engine re-traced"
print("MESH_FUSED_OK")
"""


def test_mesh_fused_engine_matches_local():
    """2x4 CPU mesh: the fused linear-spec engine is token-identical to the
    local fused engine and re-traces nothing after warmup."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", _MESH_FUSED_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "MESH_FUSED_OK" in out.stdout


def test_mesh_executor_refuses_fused_pallas_on_many_devices(monkeypatch):
    """Where the superkernel would compile to Mosaic, a mesh of more than
    one device refuses ``fused=True`` at bind, before any compile."""
    from repro.runtime import serving

    # a mesh by shape alone: the test process has one CPU device
    mesh = jax.sharding.AbstractMesh((1, 4), ("data", "model"))
    cfg = smoke_config("tinyllama-1.1b")
    monkeypatch.setattr(serving, "fused_impl", lambda: "pallas")
    with pytest.raises(ValueError, match="one device"):
        serving.MeshExecutor(mesh).bind(cfg, 2, 32, fused=True)
    serving.MeshExecutor(mesh).bind(cfg, 2, 32, fused=False)
