"""Compile the serving path's Pallas kernels for a TPU v5e that is described,
not attached.

The TPU compiler is installed with jax, so it can compile for a chip from
its topology description alone. That catches what interpret-mode tests
cannot: block shapes off the (8, 128) tiling, reshapes Mosaic refuses, and
more VMEM than a kernel may use. Shapes are the published widths of
granite-moe-1b-a400m (d_model 1024, 16 query / 8 KV heads of 64), the
serving engine's batch of 8, and a 2048-token cache.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and pytest-xdist
workers all import this file.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import fused_decode as FD
from repro.kernels.morph_matmul import morph_matmul
from repro.runtime.speculative import tree_topology

CFG = get_config("granite-moe-1b-a400m")
B, CACHE, PAGE = 8, 2048, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)


def _assert_mosaic(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo


def _attn_operands(one_chip, cache_shape):
    dm, H, KV, hd = CFG.d_model, CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    w = CFG.param_dtype
    params = {"wq": _sds(one_chip, (dm, H * hd), w),
              "wk": _sds(one_chip, (dm, KV * hd), w),
              "wv": _sds(one_chip, (dm, KV * hd), w),
              "wo": _sds(one_chip, (H * hd, dm), w)}
    cache = {k: _sds(one_chip, cache_shape + (KV, hd), CFG.dtype)
             for k in ("k", "v")}
    active = {k: _sds(one_chip, (B,), "int32") for k in ("q_dim", "kv_dim")}
    return params, cache, _sds(one_chip, (B,), "int32"), active


@pytest.mark.parametrize("b,m,k,n", [
    (B, 1, CFG.d_model, CFG.q_dim),    # decode: wq / wo
    (B, 1, CFG.d_model, CFG.kv_dim),   # decode: wk / wv
    (1, 256, CFG.d_model, CFG.q_dim),  # a 256-token prompt
], ids=["decode_q", "decode_kv", "prefill_256"])
def test_morph_matmul_compiles_for_v5e(one_chip, b, m, k, n):
    _assert_mosaic(
        lambda x, w, a: morph_matmul(x, w, a, None, impl="pallas"),
        _sds(one_chip, (b, m, k), CFG.dtype), _sds(one_chip, (k, n), CFG.dtype),
        _sds(one_chip, (b,), "int32"))


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_fused_decode_step_compiles_for_v5e(one_chip, paged):
    n_pages = CACHE // PAGE
    shape = (B * n_pages + 1, PAGE) if paged else (B, CACHE)
    params, cache, pos, active = _attn_operands(one_chip, shape)
    x = _sds(one_chip, (B, 1, CFG.d_model), CFG.dtype)
    if paged:
        table = _sds(one_chip, (B, n_pages), "int32")
        _assert_mosaic(
            lambda p, x_, c, pos_, a, t: FD.fused_decode_step(
                p, x_, c, pos_, CFG, active=a, pages=t, page_size=PAGE,
                impl="pallas", interpret=False),
            params, x, cache, pos, active, table)
    else:
        _assert_mosaic(
            lambda p, x_, c, pos_, a: FD.fused_decode_step(
                p, x_, c, pos_, CFG, active=a, impl="pallas",
                interpret=False),
            params, x, cache, pos, active)


@pytest.mark.parametrize("tree", [None, (2, 2, 1)], ids=["linear_s4", "tree"])
def test_fused_verify_compiles_for_v5e(one_chip, tree):
    params, cache, pos, active = _attn_operands(one_chip, (B, CACHE))
    topo_ = tree_topology(tree) if tree else None
    S = topo_.n_nodes if tree else 4
    nd = None if tree is None else np.asarray(topo_.depths)
    tb = None if tree is None else np.asarray(topo_.ancestor_bias)
    _assert_mosaic(
        lambda p, x_, c, pos_, a: FD.fused_verify(
            p, x_, c, pos_, CFG, active=a, node_depth=nd, tree_bias=tb,
            impl="pallas", interpret=False),
        params, _sds(one_chip, (B, S, CFG.d_model), CFG.dtype), cache, pos,
        active)
