"""Per-kernel validation: shape/dtype sweeps vs pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention_bshd, morph_matmul, ssd_scan_bshn
from repro.kernels import ref
from repro.models.ssm import ssd_chunked


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-4


# ---------------------------------------------------------------------------
# morph_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,block", [
    (32, 32, 32, (16, 16, 16)),
    (64, 96, 128, (32, 32, 32)),
    (128, 64, 256, (64, 32, 128)),
])
def test_morph_matmul_full(dtype, m, k, n, block):
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (k, n), dtype)
    y = morph_matmul(x, w, block=block, interpret=True)
    yr = ref.morph_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                               atol=_tol(dtype) * k ** 0.5, rtol=1e-2)


@pytest.mark.parametrize("active_n,active_k", [
    (128, 96), (64, 96), (50, 96), (128, 40), (77, 33), (1, 1), (128, 96)])
def test_morph_matmul_active_widths(active_n, active_k):
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (64, 96), jnp.float32)
    w = jax.random.normal(kw, (96, 128), jnp.float32)
    y = morph_matmul(x, w, active_n, active_k, block=(32, 32, 32), interpret=True)
    yr = ref.morph_matmul_ref(x, w, active_n, active_k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3)
    # inactive columns must be exactly zero (the clock-gating contract)
    assert np.all(np.asarray(y)[:, active_n:] == 0.0)


def test_morph_matmul_one_executable_many_widths():
    """Same jitted kernel instance serves every width (dynamic scalar)."""
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (32, 64), jnp.float32)
    w = jax.random.normal(kw, (64, 64), jnp.float32)
    outs = [morph_matmul(x, w, jnp.int32(a), jnp.int32(64), block=(32, 32, 32),
                         interpret=True) for a in (64, 32, 16)]
    for a, y in zip((64, 32, 16), outs):
        yr = ref.morph_matmul_ref(x, w, a, 64)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3)


@pytest.mark.parametrize("m,k,n,block", [
    (100, 96, 200, (128, 128, 128)),  # regression: non-tile-divisible dims
    (100, 96, 200, (32, 32, 32)),
    (7, 5, 3, (16, 16, 16)),
])
def test_morph_matmul_non_divisible_dims(m, k, n, block):
    """Dims that don't tile must be padded + sliced, not asserted out."""
    kx, kw = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    y = morph_matmul(x, w, block=block, interpret=True)
    assert y.shape == (m, n)
    yr = ref.morph_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3, rtol=1e-3)


def test_morph_matmul_non_divisible_with_active_width():
    kx, kw = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(kx, (100, 96), jnp.float32)
    w = jax.random.normal(kw, (96, 200), jnp.float32)
    y = morph_matmul(x, w, 150, 80, block=(32, 32, 32), interpret=True)
    yr = ref.morph_matmul_ref(x, w, 150, 80)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3)
    assert np.all(np.asarray(y)[:, 150:] == 0.0)


def test_morph_matmul_batched():
    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (3, 32, 64), jnp.float32)
    w = jax.random.normal(kw, (64, 64), jnp.float32)
    y = morph_matmul(x, w, 48, None, block=(32, 32, 32), interpret=True)
    # 0-d array scalars must behave like python ints (not per-batch lists)
    yr = ref.morph_matmul_ref(x, w, jnp.int32(48), None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3)


@pytest.mark.parametrize("impl", ["pallas", "ref"])
@pytest.mark.parametrize("active_n,active_k", [(100, 70), (77, 33), (128, 96)])
def test_morph_matmul_bf16_non_aligned_active(impl, active_n, active_k):
    """bf16 with active widths that straddle tile boundaries, both impls."""
    kx, kw = jax.random.split(jax.random.PRNGKey(6))
    x = jax.random.normal(kx, (64, 96), jnp.bfloat16)
    w = jax.random.normal(kw, (96, 128), jnp.bfloat16)
    y = morph_matmul(x, w, active_n, active_k, block=(32, 32, 32),
                     interpret=True, impl=impl)
    yr = ref.morph_matmul_ref(x, w, active_n, active_k)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=_tol(jnp.bfloat16) * 96 ** 0.5, rtol=2e-2)
    assert np.all(np.asarray(y, np.float32)[:, active_n:] == 0.0)


# (B, M, K, N, block, dtype, per-row active_n, per-row active_k)
_WIDTH_MIX = (3, 32, 64, 96, (32, 32, 32), jnp.bfloat16, [96, 50, 16], [64, 33, 64])
_ROWS = np.random.default_rng(10)
_ROW_CASES = {
    # a decode batch: 16 rows, one row block, a width of its own per row
    "b16m1": (16, 1, 256, 192, (256, 128, 64), jnp.bfloat16,
              [192, 0, 1, 64, 65, 100, 191, 192] + list(_ROWS.integers(0, 193, 8)),
              [256, 1, 0, 128, 129, 200, 255, 256] + list(_ROWS.integers(0, 257, 8))),
    # a verify batch (B slots x K+1 positions): 20 rows, not a multiple of
    # the sublane tile, padded to one block of 32
    "b4m5": (4, 5, 128, 160, (256, 64, 64), jnp.bfloat16,
             [160, 33, 96, 1], [100, 128, 7, 64]),
    "b4m5_f32": (4, 5, 96, 96, (256, 32, 32), jnp.float32,
                 [96, 40, 0, 77], [96, 31, 96, 12]),
    # every row narrow: whole N tiles and K steps skip
    "narrow": (8, 2, 128, 128, (256, 32, 32), jnp.bfloat16,
               [40, 33, 8, 1, 0, 32, 17, 40], [50, 64, 1, 33, 9, 49, 0, 2]),
    # 35 rows over a 16-row tile: three row blocks straddling batch rows
    "rowblocks": (5, 7, 64, 96, (16, 32, 32), jnp.bfloat16,
                  [96, 50, 16, 0, 95], [64, 33, 64, 1, 40]),
}


@pytest.mark.parametrize("impl,case", [
    pytest.param(impl, _WIDTH_MIX, id=impl) for impl in ("pallas", "ref")
] + [
    pytest.param(impl, c, id=f"{impl}-{name}")
    for name, c in _ROW_CASES.items() for impl in ("pallas", "ref")
])
def test_morph_matmul_batched_per_batch_active(impl, case):
    """Each batch row at its OWN (non-tile-aligned) active widths, in one
    launch — the mixed-width serving batch — whether its rows share one
    row block or spread over several."""
    B, M, K, N, block, dtype, ans, aks = case
    kx, kw = jax.random.split(jax.random.PRNGKey(8))
    x = jax.random.normal(kx, (B, M, K), dtype)
    w = jax.random.normal(kw, (K, N), dtype)
    ans, aks = [int(a) for a in ans], [int(a) for a in aks]
    y = morph_matmul(x, w, jnp.array(ans, jnp.int32), jnp.array(aks, jnp.int32),
                     block=block, interpret=True, impl=impl)
    yr = ref.morph_matmul_ref(x, w, ans, aks)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=_tol(dtype) * K ** 0.5, rtol=2e-2)
    for b, an in enumerate(ans):
        assert np.all(np.asarray(y, np.float32)[b, :, an:] == 0.0)


def _pallas_grids(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["grid_mapping"]
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                yield from _pallas_grids(v.jaxpr)
            elif hasattr(v, "eqns"):
                yield from _pallas_grids(v)


def test_morph_matmul_decode_fetches_each_weight_tile_once():
    """A decode batch of 16 rows is ONE row block: the grid is (1, N/bn,
    K/bk) with no batch axis, so each weight tile is visited once per launch
    (not once per batch row); widths switch without a trace."""
    from repro.kernels.morph_matmul import trace_count

    B, K, N = 16, 1024, 768  # N: a width no other test compiles
    kx, kw = jax.random.split(jax.random.PRNGKey(11))
    x = jax.random.normal(kx, (B, 1, K), jnp.bfloat16)
    w = jax.random.normal(kw, (K, N), jnp.bfloat16)
    t0 = trace_count()
    for an in ([N] * B, [N, N // 2] * (B // 2), [1] * B):
        an = jnp.array(an, jnp.int32)
        y = morph_matmul(x, w, an, None, interpret=True)
        yr = ref.morph_matmul_ref(x, w, an, None)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr, np.float32),
                                   atol=_tol(jnp.bfloat16) * K ** 0.5, rtol=2e-2)
    assert trace_count() - t0 == 1, "one trace per logical shape"

    jaxpr = jax.make_jaxpr(lambda x, w, a: morph_matmul(
        x, w, a, None, interpret=True))(x, w, jnp.full((B,), N, jnp.int32))
    (gm,) = list(_pallas_grids(jaxpr.jaxpr))
    w_block = tuple(b.block_size for b in gm.block_mappings[3].block_shape)
    assert gm.grid == (1, N // w_block[1], K // w_block[0]), gm.grid
    assert w_block == (512, 384), w_block  # 384: the widest 128-multiple dividing 768


def test_morph_matmul_pad_path_traces_once():
    """Non-tile-divisible dims must trace the jitted core exactly once per
    logical shape (the old pad path re-entered the jit wrapper, tracing
    twice), and later width changes must not trace at all."""
    from repro.kernels.morph_matmul import trace_count

    kx, kw = jax.random.split(jax.random.PRNGKey(9))
    # padding canonicalizes shapes, so pick dims whose PADDED shape —
    # (48, 32) @ (32, 64) at block 16 — no other test in this suite hits:
    # the counter must start cold for this executable
    x = jax.random.normal(kx, (33, 21), jnp.float32)  # 33 % 16 != 0
    w = jax.random.normal(kw, (21, 53), jnp.float32)  # 53 % 16 != 0
    t0 = trace_count()
    y = morph_matmul(x, w, 40, 17, block=(16, 16, 16), interpret=True)
    assert trace_count() - t0 == 1, "pad path must not re-trace the core"
    t1 = trace_count()
    for an, ak in [(53, 21), (16, 8), (1, 1)]:
        y2 = morph_matmul(x, w, an, ak, block=(16, 16, 16), interpret=True)
        yr = ref.morph_matmul_ref(x, w, an, ak)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(yr), atol=1e-3)
    assert trace_count() == t1, "width switches must not trace"
    yr = ref.morph_matmul_ref(x, w, 40, 17)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3)


_SHARDED_MM_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.kernels.morph_matmul import morph_matmul, sharded_morph_matmul
from repro.launch.mesh import make_mesh

kx, kw = jax.random.split(jax.random.PRNGKey(0))
B, M, K = 3, 2, 256
x = jax.random.normal(kx, (B, M, K), jnp.float32)
# per-slot widths: full, partial (ends inside a shard), none
cases = [(512, jnp.array([512, 200, 0]), None),    # column split
         (512, None, jnp.array([K, 100, 0])),      # row split + psum
         (130, jnp.array([130, 70, 1]), None)]     # 130 % 4: replicated
for shape in ((1, 4), (2, 2)):
    mesh = make_mesh(shape, ("data", "model"))
    for n, an, ak in cases:
        w = jax.random.normal(kw, (K, n), jnp.float32)
        want = morph_matmul(x, w, an, ak, impl="pallas", interpret=True)
        got = jax.jit(lambda x, w: sharded_morph_matmul(
            mesh, x, w, an, ak, interpret=True))(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
print("SHARDED_MM_OK")
"""


def test_sharded_morph_matmul_matches_one_device():
    """On 1x4 and 2x2 meshes of CPU devices, the per-shard kernel with
    clipped active widths equals the one-device kernel."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", _SHARDED_MM_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         env=dict(os.environ, PYTHONPATH=src))
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "SHARDED_MM_OK" in out.stdout


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sq,sk,h,kv,hd,bq,bk", [
    (64, 64, 4, 2, 32, 16, 16),
    (128, 128, 2, 2, 64, 32, 64),
    (32, 32, 4, 1, 16, 32, 32),
])
def test_flash_attention_causal(dtype, sq, sk, h, kv, hd, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B = 2
    q = jax.random.normal(ks[0], (B, sq, h, hd), dtype)
    k = jax.random.normal(ks[1], (B, sk, kv, hd), dtype)
    v = jax.random.normal(ks[2], (B, sk, kv, hd), dtype)
    o = flash_attention_bshd(q, k, v, causal=True, bq=bq, bk=bk, interpret=True)
    group = h // kv
    qf = q.transpose(0, 2, 1, 3).reshape(B * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * kv, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * kv, sk, hd)
    orf = ref.flash_attention_ref(qf, kf, vf, group=group, causal=True)
    orf = orf.reshape(B, h, sq, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(orf, np.float32),
                               atol=_tol(dtype) * 4, rtol=2e-2)


@pytest.mark.parametrize("window", [8, 16, 64])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 2, 32), jnp.float32)
    o = flash_attention_bshd(q, k, v, causal=True, window=window, bq=16, bk=16,
                             interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(2, 64, 32)
    kf = k.transpose(0, 2, 1, 3).reshape(2, 64, 32)
    vf = v.transpose(0, 2, 1, 3).reshape(2, 64, 32)
    orf = ref.flash_attention_ref(qf, kf, vf, group=1, causal=True, window=window)
    orf = orf.reshape(1, 2, 64, 32).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), atol=1e-4, rtol=1e-3)


def test_flash_matches_model_attention():
    """Kernel agrees with the model-zoo chunked attention implementation."""
    from repro.configs import smoke_config
    from repro.models.layers import attention_chunked

    cfg = smoke_config("tinyllama-1.1b")
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, S = 2, 32
    q = jax.random.normal(ks[0], (B, S, cfg.n_heads, cfg.head_dim), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    pos = jnp.arange(S)
    o_model = attention_chunked(q, k, v, cfg.scaled(attn_chunk=16), pos, pos)
    o_kern = flash_attention_bshd(q, k, v, causal=True, bq=16, bk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(o_model), np.asarray(o_kern),
                               atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,p,g,n,chunk", [
    (64, 4, 16, 2, 8, 16),
    (128, 2, 32, 1, 16, 32),
    (32, 8, 8, 8, 4, 8),
])
def test_ssd_scan_vs_chunked(dtype, s, h, p, g, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    b = 2
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B_ = jax.random.normal(ks[3], (b, s, g, n), dtype)
    C_ = jax.random.normal(ks[4], (b, s, g, n), dtype)
    y, fs = ssd_scan_bshn(x.astype(jnp.float32), dt.astype(jnp.float32), A,
                          B_.astype(jnp.float32), C_.astype(jnp.float32),
                          chunk=chunk, interpret=True)
    yr, fsr = ssd_chunked(x.astype(jnp.float32), dt.astype(jnp.float32), A,
                          B_.astype(jnp.float32), C_.astype(jnp.float32), chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-3, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(fs), np.asarray(fsr), atol=5e-3, rtol=1e-2)


def test_ssd_scan_vs_sequential_oracle():
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    b, s, h, p, n = 1, 48, 2, 8, 4
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B_ = jax.random.normal(ks[3], (b, s, 1, n))
    C_ = jax.random.normal(ks[4], (b, s, 1, n))
    y, fs = ssd_scan_bshn(x, dt, A, B_, C_, chunk=16, interpret=True)
    xf = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(b * h, s)
    Bf = jnp.repeat(B_, h, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, n)
    Cf = jnp.repeat(C_, h, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, n)
    yr, fsr = ref.ssd_scan_ref(xf, dtf, jnp.broadcast_to(A, (b, h)).reshape(-1), Bf, Cf)
    yr = yr.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(fs.reshape(b * h, p, n)), np.asarray(fsr),
                               atol=1e-3, rtol=1e-3)
