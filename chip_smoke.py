#!/usr/bin/env python3
"""Chip smoke check: the serving engine's main path on a TPU v5e.

    python chip_smoke.py               # one chip: granite-moe-1b-a400m
    python chip_smoke.py --four-chips  # four chips: phi3-medium-14b, 1x4 mesh

One chip (the default). ``granite-moe-1b-a400m`` at its published widths
(24 layers, d_model 1024, 16/8 heads of 64, 32 experts top-8 of width 512,
vocab 49155, exits at groups 12 and 18) with the config's own dtypes and
random weights from ``--seed`` is served through the normal entry points:
``ServingEngine`` with its default ``LocalExecutor``, ``warmup()``, then
``submit``/``step`` until the queue drains. Eight seeded prompts of 128 and
256 tokens take the prefill path; 32 greedy tokens each, batch 8, cache 512.
Admission switches from full width to half width at full depth, then to an
exit depth, so the width-gated kernels run at both ends and a second depth
group serves. The checks: every request completes with in-vocabulary ids,
nothing compiles after warm-up, the compiled decode step holds a Mosaic
kernel (``tpu_custom_call``), and one decode step agrees across its kernel
paths (Pallas ``morph_matmul`` vs XLA dot, fused superkernel vs unfused).

Four chips (``--four-chips``, this phase only). ``phi3-medium-14b`` with
bf16 weights (28 GB, 7 GB per chip) serves a few requests, short and
prefilled, at all 40 layers through ``MeshExecutor(make_serve_mesh(1, 4))``,
with each width-gated projection's Pallas kernel run per shard under
``shard_map``; then the same decode steps
of an 8-layer cut of it run on a 1x4 mesh and on a 1x1 mesh of one chip,
and their logits must agree.

Timings printed here are smoke timings (host wall clock), not benchmark
numbers. Any failed check prints why and exits non-zero; the last line of a
passing run is one JSON object naming the device. Without a TPU the script
exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

MODEL = "granite-moe-1b-a400m"
MESH_MODEL = "phi3-medium-14b"

# Logit comparisons use the per-slot relative L2 error over the real
# vocabulary. Greedy ids are reported, not checked: with random weights the
# top logits of a 49k vocabulary sit within one bf16 step of each other, so
# any rounding difference can flip them.
#
# Two kernel paths of one bf16 decode step differ only in rounding (f32
# accumulation order; the fused kernel skips bf16 roundings the unfused
# layers make). If each is about as accurate as the XLA path, the triangle
# inequality puts them within twice the XLA path's own distance from an f32
# reference of the same step; that distance is measured in the same run and
# sets the bound. The floor covers f32-only runs (CPU tests), whose noise is
# accumulation order alone. A wrong kernel (mis-mapped head, shifted mask)
# moves logits by O(1) relative; the cap keeps such a result from passing
# under a noisy reference.
NOISE_FLOOR = 1e-3
REL_CAP = 0.25
# 1x4 vs 1x1 mesh: the same bf16 math up to the order of the model-axis
# reductions; a partitioning error (missing reduction, wrong shard) moves
# logits by O(1).
MESH_REL_TOL = 0.1


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def rel_l2(got, ref, vocab: int):
    """Per-slot relative L2 distance of two (B, 1, Vp) logit arrays over the
    real vocabulary, with greedy flips; raises SmokeFailure on a non-finite
    value."""
    import numpy as np

    a = np.asarray(got, np.float32)[:, 0, :vocab]
    b = np.asarray(ref, np.float32)[:, 0, :vocab]
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        raise SmokeFailure("non-finite logits")
    rel = np.linalg.norm(a - b, axis=-1) / np.maximum(
        np.linalg.norm(b, axis=-1), 1e-30)
    return rel, int((a.argmax(-1) != b.argmax(-1)).sum()), \
        float(np.abs(a - b).max())


def compare_logits(name: str, got, ref, vocab: int, tol: float) -> dict:
    """Check every slot of ``got`` is within relative L2 ``tol`` of ``ref``;
    logs the statistics and raises SmokeFailure past the bound."""
    import numpy as np

    try:
        rel, flips, max_abs = rel_l2(got, ref, vocab)
    except SmokeFailure as e:
        raise SmokeFailure(f"{name}: {e}") from None
    out = {"max_rel_l2": float(rel.max()), "median_rel_l2":
           float(np.median(rel)), "tol": tol, "max_abs": max_abs,
           "argmax_flips": flips, "slots": int(rel.size)}
    log(f"{name}: {json.dumps(out)}")
    if rel.max() > tol:
        raise SmokeFailure(f"{name}: logits disagree (max rel L2 "
                           f"{rel.max():.4g} > {tol:.4g})")
    return out


def serve_phase(params, cfg, *, seed: int = 0, batch: int = 8,
                capacity: int = 512, prompt_lens=(128, 256),
                n_requests: int = 8, new_tokens: int = 32) -> dict:
    """Serve seeded traffic through ``ServingEngine`` and check the result.

    Requests arrive in three waves: full width at full depth, then half
    width at full depth, then full width at the shallowest exit depth. Each
    wave is admitted under its own admission mode, so the full-depth group
    runs mixed widths in one launch and the exit-depth group serves too.
    Returns the engine and the run's counts and host wall-clock times.
    """
    import numpy as np

    from repro.configs.base import MorphMode
    from repro.runtime.serving import Request, ServingEngine

    full, exit_depth = cfg.n_groups, min(cfg.elastic.exit_layers)
    waves = (MorphMode(full, 1.0), MorphMode(full, 0.5),
             MorphMode(exit_depth, 1.0))
    # the engine admits under modes[-1] until told otherwise
    modes = (waves[2], waves[1], waves[0])
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=tuple(int(t) for t in rng.integers(
                        1, cfg.vocab_size, prompt_lens[i % len(prompt_lens)])),
                    max_new_tokens=new_tokens)
            for i in range(n_requests)]
    per_wave = -(-n_requests // len(waves))

    engine = ServingEngine(params, cfg, batch_size=batch,
                           cache_capacity=capacity, modes=modes)
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    wave = 0
    while wave < len(waves) or engine.queue or engine.n_active:
        # a new wave every 4 ticks, each under its own admission mode
        if wave < len(waves) and engine.step_count >= 4 * wave:
            engine.set_admission_mode(waves[wave])
            for r in reqs[wave * per_wave:(wave + 1) * per_wave]:
                engine.submit(r)
            wave += 1
        engine.step()
    serve_s = time.perf_counter() - t0

    done = {r.rid: r for r in engine.completed}
    missing = [r.rid for r in reqs if r.rid not in done
               or len(done[r.rid].generated) != new_tokens]
    if missing:
        raise SmokeFailure(f"requests did not complete: {missing}")
    ids = np.asarray([t for r in engine.completed for t in r.generated])
    if ids.min() < 0 or ids.max() >= cfg.vocab_size:
        raise SmokeFailure(f"generated ids outside [0, {cfg.vocab_size}): "
                           f"min {ids.min()} max {ids.max()}")
    if engine.ctrl.stats["compiles"] != engine.compiles_after_warmup:
        raise SmokeFailure(
            f"runtime switches recompiled: {engine.ctrl.stats['compiles']} "
            f"executables vs {engine.compiles_after_warmup} after warm-up")
    used = {r.mode_name for r in engine.completed}
    if used != {m.name for m in waves}:
        raise SmokeFailure(f"admission modes served {sorted(used)}, "
                           f"expected {sorted(m.name for m in waves)}")
    return {"engine": engine, "warmup_s": warmup_s, "serve_s": serve_s,
            "generated": int(ids.size), "prefills": engine.prefills,
            "decode_launches": engine.decode_launches,
            "modes": sorted(used)}


def kernel_checks(engine, *, seed: int = 0, require_mosaic: bool) -> dict:
    """Compare one decode step across its kernel paths on the engine's
    full-depth cache: full active widths (Pallas ``morph_matmul`` on TPU)
    against ``active=None`` (XLA dot), and the fused superkernel against the
    unfused layers, each within a bound measured from an f32 reference of
    the same step (see NOISE_FLOOR). With ``require_mosaic`` the serving
    engine's compiled decode step must hold a Mosaic kernel."""
    import jax
    import numpy as np

    from repro.models.model import decode_step

    cfg, B = engine.cfg, engine.batch_size
    g = engine.groups[cfg.n_groups]
    tok = engine.executor.put(np.random.default_rng(seed + 1).integers(
        1, cfg.vocab_size, (B, 1)).astype(np.int32))
    active = engine._active_for([1.0] * B)
    out = {}
    if require_mosaic:
        step = engine.ctrl.step_for(engine.ctrl.modes[-1])
        hlo = step.lower(engine.params, g.cache, tok, active).compile().as_text()
        n = hlo.count('custom_call_target="tpu_custom_call"')
        if not n:
            raise SmokeFailure("compiled decode step holds no Mosaic kernel "
                               "(no tpu_custom_call in its HLO)")
        out["mosaic_kernels_in_decode_step"] = n
        log(f"decode step HLO: {n} tpu_custom_call sites")

    def logits(c=cfg, **kw):
        fn = jax.jit(lambda p, cache, t: decode_step(p, cache, t, c, **kw)[0])
        return fn(engine.params, g.cache, tok)

    xla = logits(active=None)
    # f32 activations, f32 matmuls: the reference the bound is measured from
    with jax.default_matmul_precision("highest"):
        ref = logits(dataclasses.replace(cfg, dtype="float32"), active=None)
    noise, _, _ = rel_l2(xla, ref, cfg.vocab_size)
    tol = min(max(2 * float(noise.max()), NOISE_FLOOR), REL_CAP)
    out["xla_bf16_vs_f32_max_rel_l2"] = float(noise.max())
    log(f"bf16 XLA step vs f32 reference: max rel L2 {noise.max():.4g} "
        f"-> kernel-path bound {tol:.4g}")
    gated = logits(active=active)
    out["morph_matmul_vs_xla_dot"] = compare_logits(
        "width-gated (morph_matmul) vs active=None (XLA dot)", gated, xla,
        cfg.vocab_size, tol)
    out["fused_vs_unfused"] = compare_logits(
        "fused superkernel vs unfused layers",
        logits(active=active, fused=True), gated, cfg.vocab_size, tol)
    return out


def one_chip(args, jax) -> None:
    from repro.configs import get_config
    from repro.models.model import init_params

    cfg = get_config(MODEL)
    dev = jax.devices()[0]
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads x {cfg.head_dim}, "
        f"{cfg.n_experts} experts top-{cfg.top_k}, vocab {cfg.vocab_size}, "
        f"params {cfg.param_dtype}, activations {cfg.dtype}, "
        f"{cfg.n_params() / 1e9:.3f} B parameters")
    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(args.seed), cfg)
    jax.block_until_ready(params)
    log(f"init: {time.perf_counter() - t0:.3f} s")
    res = serve_phase(params, cfg, seed=args.seed)
    log(f"smoke timing (not a benchmark): warm-up/compile "
        f"{res['warmup_s']:.3f} s; serving loop {res['serve_s']:.3f} s wall "
        f"clock for {res['generated']} generated tokens "
        f"({res['prefills']} prefills, {res['decode_launches']} decode "
        f"launches, modes {res['modes']})")
    t0 = time.perf_counter()
    kernel_checks(res["engine"], seed=args.seed, require_mosaic=True)
    log(f"kernel checks: {time.perf_counter() - t0:.3f} s")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
        f"bytes_limit {stats.get('bytes_limit')}")


def four_chip_phase(cfg, cmp_layers: int, *, seed: int = 0, batch: int = 4,
                    capacity: int = 64, prompt_lens=(4, 16),
                    new_tokens: int = 8, steps: int = 4,
                    require_mosaic: bool = False) -> dict:
    """Serve ``cfg`` at full depth on a 1x4 mesh, then compare the same
    decode steps of a ``cmp_layers`` cut on 1x4 against 1x1. Prompts
    alternate between ``prompt_lens``: one below the engine's
    ``prefill_threshold`` (fed through the decode step), one above it
    (the sharded prefill path). With ``require_mosaic`` both compiled
    decode steps of the cut must hold a Mosaic kernel."""
    import jax
    import numpy as np

    from repro.configs.base import ElasticConfig, MorphMode
    from repro.core import elastic
    from repro.launch.mesh import make_serve_mesh
    from repro.models.model import init_params
    from repro.parallel import sharding as SH
    from repro.runtime.serving import MeshExecutor, Request, ServingEngine

    def sharded_params(c, mesh):
        key = jax.random.PRNGKey(seed)
        shapes = jax.eval_shape(lambda: init_params(key, c))
        sh = SH.shardings_for(SH.param_specs(
            shapes, c, mesh, SH.serve_policy(c, mesh.shape["model"])), mesh)
        return jax.jit(lambda k: init_params(k, c), out_shardings=sh)(key)

    def device_bytes():
        return [d.memory_stats().get("bytes_in_use") if d.memory_stats()
                else None for d in jax.devices()[:4]]

    mesh4 = make_serve_mesh(1, 4)
    full = MorphMode(cfg.n_groups, 1.0)
    params = sharded_params(cfg, mesh4)
    engine = ServingEngine(params, cfg, batch_size=batch,
                           cache_capacity=capacity, modes=(full,),
                           executor=MeshExecutor(mesh4))
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    for i in range(batch):
        engine.submit(Request(rid=i, prompt=tuple(int(t) for t in rng.integers(
            1, cfg.vocab_size, prompt_lens[i % len(prompt_lens)])),
            max_new_tokens=new_tokens))
    t0 = time.perf_counter()
    while engine.queue or engine.n_active:
        engine.step()
    serve_s = time.perf_counter() - t0
    if len(engine.completed) != batch or any(
            len(r.generated) != new_tokens for r in engine.completed):
        raise SmokeFailure("mesh engine did not complete every request")
    ids = [t for r in engine.completed for t in r.generated]
    if min(ids) < 0 or max(ids) >= cfg.vocab_size:
        raise SmokeFailure("mesh engine generated out-of-vocabulary ids")
    if engine.ctrl.stats["compiles"] != engine.compiles_after_warmup:
        raise SmokeFailure("mesh engine recompiled after warm-up")
    if not all(r.prefilled == (len(r.prompt) >= engine.prefill_threshold)
               for r in engine.completed):
        raise SmokeFailure("mesh engine did not prefill exactly the long "
                           "prompts")
    serving_bytes = device_bytes()
    out = {"policy": engine.executor.policy, "warmup_s": warmup_s,
           "serve_s": serve_s, "generated": len(ids),
           "prefills": engine.prefills,
           "bytes_in_use_per_device": serving_bytes}
    del engine, params
    gc.collect()
    out["bytes_in_use_after_release"] = device_bytes()

    # the same decode steps of a cmp_layers cut, on 1x4 and on 1x1
    cut = dataclasses.replace(cfg, n_layers=cmp_layers,
                              elastic=ElasticConfig(exit_layers=()))
    mode = MorphMode(cut.n_groups, 1.0)
    params = sharded_params(cut, mesh4)
    logits = {}
    for name, mesh in (("1x4", mesh4), ("1x1", make_serve_mesh(1, 1))):
        eng = ServingEngine(params, cut, batch_size=batch,
                            cache_capacity=capacity, modes=(mode,),
                            executor=MeshExecutor(mesh))
        step = eng.ctrl.step_for(mode)
        cache = eng.executor.init_cache()
        active = jax.tree_util.tree_map(
            eng.executor.put, elastic.active_widths_batch(cut, [1.0] * batch))
        toks = np.random.default_rng(seed + 1).integers(
            1, cut.vocab_size, (steps, batch, 1)).astype(np.int32)
        if require_mosaic:
            hlo = step.lower(eng.params, cache, eng.executor.put(toks[0]),
                             active).compile().as_text()
            n = hlo.count('custom_call_target="tpu_custom_call"')
            log(f"{name} decode step HLO: {n} tpu_custom_call sites")
            if not n:
                raise SmokeFailure(f"{name} decode step holds no Mosaic "
                                   f"kernel")
        seq = []
        for t in range(steps):
            lg, cache = step(eng.params, cache, eng.executor.put(toks[t]),
                             active)
            seq.append(np.asarray(lg, np.float32))
        logits[name] = seq
        del eng, cache
        gc.collect()
    out["compare"] = [
        compare_logits(f"{cmp_layers}-layer decode step {t}: 1x4 vs 1x1",
                       logits["1x4"][t], logits["1x1"][t], cut.vocab_size,
                       MESH_REL_TOL)
        for t in range(steps)]
    return out


def four_chips(args, jax) -> None:
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(MESH_MODEL), param_dtype="bfloat16")
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads x {cfg.head_dim}; weights "
        f"bf16 (param_dtype=\"bfloat16\"): {cfg.n_params() * 2 / 1e9:.2f} GB")
    log(f"n_kv_heads={cfg.n_kv_heads} does not divide by 4: the decode "
        f"K/V activations stay replicated over the model axis; the KV cache "
        f"itself is split along its sequence dimension")
    res = four_chip_phase(cfg, 8, seed=args.seed, require_mosaic=True)
    log(f"smoke timing (not a benchmark): 1x4 warm-up/compile "
        f"{res['warmup_s']:.3f} s; serving loop {res['serve_s']:.3f} s wall "
        f"clock for {res['generated']} generated tokens at all "
        f"{cfg.n_layers} layers ({res['prefills']} prefills), policy "
        f"{res['policy']}")
    log(f"bytes_in_use per device while serving: "
        f"{res['bytes_in_use_per_device']}; after releasing the 40-layer "
        f"engine: {res['bytes_in_use_after_release']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase (needs 4 chips)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"FAIL: no TPU: jax found {dev.platform} devices only")
        return 2
    # the analytical cost model's peak rates are v5e's (core/neuroforge/hw.py)
    if "v5 lite" not in dev.device_kind and "v5e" not in dev.device_kind:
        log(f"FAIL: device kind {dev.device_kind!r} is not a TPU v5e")
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        log(f"FAIL: this phase needs {need} chips, found {len(devices)}")
        return 2
    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"FAIL: the repro package is not at {SRC}: run from a checkout")
        return 2
    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache

    log(f"jax {jax.__version__}, jaxlib {importlib.metadata.version('jaxlib')},"
        f" libtpu {importlib.metadata.version('libtpu')}; device_kind "
        f"{dev.device_kind!r} x {len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    try:
        (four_chips if args.four_chips else one_chip)(args, jax)
    except SmokeFailure as e:
        log(f"FAIL: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
