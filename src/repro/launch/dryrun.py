import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# test hook (still before any jax import): shrink the host-device pool
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, with ZERO device allocation (ShapeDtypeStruct):
  * ``compiled.memory_analysis()``  — proves the program fits per-chip HBM
  * ``compiled.cost_analysis()``    — per-device FLOPs / bytes for §Roofline
  * collective wire bytes           — from the post-SPMD HLO (loop-aware walk)

Results are cached in a JSON file keyed by (arch, shape, mesh, knobs) so the
full 2x33-cell sweep is resumable. Knob overrides drive the §Perf hillclimb.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch nemotron-4-340b \
      --shape train_4k --mesh single --remat dots --microbatches 8
"""
import argparse
import dataclasses
import hashlib
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPE_BY_NAME, SHAPES, cell_applicable, get_config, list_archs
from repro.configs.base import ModelConfig, MorphMode, ShapeCell
from repro.core import elastic
from repro.core.neuroforge.hw import V5E
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.steps import make_decode_fn, make_prefill_step, make_train_step
from repro.models.model import init_decode_cache, init_params
from repro.optim import OptimizerConfig, init_opt_state
from repro.parallel import sharding as SH

RESULTS_DEFAULT = "benchmarks/results/dryrun.json"


# ---------------------------------------------------------------------------
# knobs (baseline defaults = paper-faithful config; overrides = hillclimb)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Knobs:
    remat: str = "full"
    microbatches: int = 0  # 0 = auto (per-shard batch -> microbatch of 1 seq)
    moment_dtype: str = ""  # "" = auto (bf16 for >50B params)
    param_dtype: str = "bfloat16"
    kv_quant: bool = False
    width: float = 1.0
    depth_frac: float = 1.0  # morph depth fraction (1.0 = full)
    policy: str = ""  # "" = auto
    attn_chunk: int = 1024
    capacity_factor: float = 1.25
    sp: bool = True  # sequence-parallel residual constraint
    grad_dtype: str = "float32"  # gradient reduction dtype (bf16 = hillclimb)
    bf16_grad_matmul: bool = False  # custom-VJP bf16 dW (beyond-paper)

    def key(self) -> str:
        return hashlib.md5(json.dumps(dataclasses.asdict(self),
                                      sort_keys=True).encode()).hexdigest()[:10]


def resolve_cfg(arch: str, knobs: Knobs) -> ModelConfig:
    cfg = get_config(arch)
    return cfg.scaled(param_dtype=knobs.param_dtype, dtype="bfloat16",
                      attn_impl="chunked", attn_chunk=knobs.attn_chunk,
                      kv_quant=knobs.kv_quant,
                      capacity_factor=knobs.capacity_factor)


def auto_knobs(cfg: ModelConfig, cell: ShapeCell, mesh, knobs: Knobs) -> Knobs:
    k = dataclasses.replace(knobs)
    data_sz = 1
    for a in SH.data_axes(mesh):
        data_sz *= mesh.shape[a]
    if not k.moment_dtype:
        k.moment_dtype = "bfloat16" if cfg.n_params() > 50e9 else "float32"
    if k.microbatches == 0:
        per_shard = max(1, cell.global_batch // data_sz)
        k.microbatches = max(1, per_shard // 2)  # 2-seq microbatches default
    if not k.policy:
        k.policy = "train" if cell.kind != "decode" else SH.serve_policy(
            cfg, tp=mesh.shape.get("model", 1))
    return k


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct only — no device allocation)
# ---------------------------------------------------------------------------


def batch_struct(cfg: ModelConfig, B: int, S: int) -> Dict[str, jax.ShapeDtypeStruct]:
    text = S - (cfg.frontend_seq if cfg.frontend == "vision_stub" else 0)
    out = {
        "tokens": jax.ShapeDtypeStruct((B, text), jnp.int32),
        "targets": jax.ShapeDtypeStruct((B, text), jnp.int32),
    }
    if cfg.frontend == "vision_stub":
        out["patches"] = jax.ShapeDtypeStruct((B, cfg.frontend_seq, cfg.frontend_dim),
                                              jnp.bfloat16)
    if cfg.is_encdec:
        out["frames"] = jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.frontend_dim),
                                             jnp.bfloat16)
    return out


def _struct(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _mesh_chips(mesh) -> int:
    n = 1
    for a in mesh.axis_names:
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape: str, multi_pod: bool, knobs: Knobs,
             mesh=None, debug_mesh: bool = False,
             hlo_dir: str = "") -> Dict[str, Any]:
    cell = SHAPE_BY_NAME[shape]
    cfg = resolve_cfg(arch, knobs)
    ok, why = cell_applicable(cfg, cell)
    mesh_name = ("2x2x2" if multi_pod else "2x4") if debug_mesh else \
        ("2x16x16" if multi_pod else "16x16")
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape,
        "mesh": mesh_name,
        "knobs": dataclasses.asdict(knobs),
    }
    if not ok:
        rec.update(status="skip", reason=why)
        return rec
    mesh = mesh or (make_debug_mesh(multi_pod=multi_pod) if debug_mesh
                    else make_production_mesh(multi_pod=multi_pod))
    chips = _mesh_chips(mesh)
    k = auto_knobs(cfg, cell, mesh, knobs)
    rec["resolved_knobs"] = dataclasses.asdict(k)
    rec["policy"] = k.policy

    mode: Optional[MorphMode] = None
    cfg_exec = cfg
    if k.width < 1.0 or k.depth_frac < 1.0:
        depth = max(1, int(round(cfg.n_groups * k.depth_frac)))
        mode = MorphMode(depth=depth, width=k.width)
        cfg_exec = elastic.morph_config(cfg, mode)

    from repro.models.layers import set_bf16_grad_matmul
    set_bf16_grad_matmul(k.bf16_grad_matmul)
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            if cell.kind == "train":
                lowered = _lower_train(cfg_exec, cell, mesh, k)
            elif cell.kind == "prefill":
                lowered = _lower_prefill(cfg_exec, cell, mesh, k)
            else:
                lowered = _lower_decode(cfg, cfg_exec, cell, mesh, k, mode)
            rec["time_lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["time_compile_s"] = round(time.time() - t1, 2)
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        return rec

    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
    }
    live = ma.argument_size_in_bytes + ma.temp_size_in_bytes + \
        max(0, ma.output_size_in_bytes - ma.alias_size_in_bytes)
    mem["live_bytes_per_device"] = live
    mem["fits_16gb"] = bool(live <= V5E.hbm_bytes)
    rec["memory"] = mem

    hlo_text = compiled.as_text()
    if hlo_dir:
        import gzip
        os.makedirs(hlo_dir, exist_ok=True)
        fn = f"{arch}__{shape}__{rec['mesh']}__{knobs.key()}.hlo.gz"
        with gzip.open(os.path.join(hlo_dir, fn), "wt") as f:
            f.write(hlo_text)
        rec["hlo_file"] = fn
    # loop-aware cost model (cost_analysis() counts while bodies once; see
    # repro.launch.hlo_analysis docstring)
    hc = analyze_hlo(hlo_text, chips)
    flops_pd = hc.flops
    bytes_pd = hc.bytes
    ca = compiled.cost_analysis() or {}
    rec["cost"] = {
        "flops_per_device": flops_pd,
        "bytes_per_device": bytes_pd,
        "xla_cost_analysis_flops": float(ca.get("flops", 0.0)),
        "xla_cost_analysis_bytes": float(ca.get("bytes accessed", 0.0)),
        "while_trips": hc.while_trips,
    }
    rec["collectives"] = {
        "wire_bytes_per_chip": hc.coll_wire_bytes,
        "result_bytes": hc.coll_result_bytes,
        "per_op_bytes": dict(hc.per_op_bytes),
        "per_op_count": dict(hc.per_op_count),
    }

    # §Roofline terms
    compute_s = flops_pd / V5E.peak_flops
    memory_s = bytes_pd / V5E.hbm_bw
    coll_s = hc.coll_wire_bytes / V5E.ici_bw
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode"
                                  else 1)
    n_active = cfg_exec.n_active_params()
    if mode is not None:
        n_active = int(n_active * mode.depth / cfg_exec.n_groups)
    model_flops = (6.0 if cell.kind == "train" else 2.0) * n_active * tokens
    hlo_flops_global = flops_pd * chips
    dominant = max(("compute", compute_s), ("memory", memory_s),
                   ("collective", coll_s), key=lambda kv: kv[1])[0]
    ideal = model_flops / (chips * V5E.peak_flops)
    rec["roofline"] = {
        "compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s,
        "dominant": dominant,
        "model_flops": model_flops,
        "hlo_flops_global": hlo_flops_global,
        "useful_ratio": model_flops / hlo_flops_global if hlo_flops_global else 0.0,
        "ideal_s": ideal,
        "step_s": max(compute_s, memory_s, coll_s),
        "roofline_fraction": ideal / max(compute_s, memory_s, coll_s)
        if max(compute_s, memory_s, coll_s) > 0 else 0.0,
    }
    rec["status"] = "ok"
    return rec


def _lower_train(cfg: ModelConfig, cell: ShapeCell, mesh, k: Knobs):
    ocfg = OptimizerConfig(moment_dtype=k.moment_dtype)
    params_s = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    opt_s = jax.eval_shape(lambda: init_opt_state(params_s, ocfg))
    state_s = {"params": params_s, "opt": opt_s}
    pspecs = SH.param_specs(params_s, cfg, mesh, "train")
    step = make_train_step(cfg, ocfg, microbatches=k.microbatches, remat=k.remat,
                           grad_shardings=SH.shardings_for(pspecs, mesh),
                           grad_dtype=k.grad_dtype)
    ospecs = SH.opt_specs(opt_s, pspecs)
    bspecs = SH.batch_specs(batch_struct(cfg, cell.global_batch, cell.seq_len),
                            mesh, "train")
    in_sh = ({"params": SH.shardings_for(pspecs, mesh),
              "opt": SH.shardings_for(ospecs, mesh)},
             SH.shardings_for(bspecs, mesh))
    rspecs = SH.residual_specs(mesh, "train") if k.sp else {}
    with SH.activation_sharding(mesh, rspecs):
        fn = jax.jit(step, in_shardings=in_sh, donate_argnums=(0,))
        return fn.lower(state_s, batch_struct(cfg, cell.global_batch, cell.seq_len))


def _lower_prefill(cfg: ModelConfig, cell: ShapeCell, mesh, k: Knobs):
    step = make_prefill_step(cfg, remat="none")
    params_s = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    pspecs = SH.param_specs(params_s, cfg, mesh, "train")
    bspecs = SH.batch_specs(batch_struct(cfg, cell.global_batch, cell.seq_len),
                            mesh, "train")
    in_sh = (SH.shardings_for(pspecs, mesh), SH.shardings_for(bspecs, mesh))
    rspecs = SH.residual_specs(mesh, "train") if k.sp else {}
    with SH.activation_sharding(mesh, rspecs):
        fn = jax.jit(step, in_shardings=in_sh)
        return fn.lower(params_s, batch_struct(cfg, cell.global_batch, cell.seq_len))


def _lower_decode(cfg_full: ModelConfig, cfg_exec: ModelConfig, cell: ShapeCell,
                  mesh, k: Knobs, mode: Optional[MorphMode]):
    B = cell.global_batch
    # morph modes slice inside jit against FULL params; plain mode uses exec cfg
    if mode is not None:
        params_cfg = cfg_full
        step = make_decode_fn(cfg_full, mode)
    else:
        params_cfg = cfg_exec
        step = make_decode_fn(cfg_exec)
    params_s = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), params_cfg))
    cache_s = jax.eval_shape(lambda: init_decode_cache(cfg_exec, B, cell.seq_len))
    tok_s = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pspecs = SH.param_specs(params_s, params_cfg, mesh, k.policy)
    cspecs = {"pos": P(), "stack": SH.cache_specs(cache_s["stack"], cfg_exec,
                                                  mesh, k.policy)}
    d = SH.data_axes(mesh) or None
    d_sz = 1
    for a in SH.data_axes(mesh):
        d_sz *= mesh.shape[a]
    tok_spec = P(None if (k.policy == "serve_2d" or B % d_sz) else d, None)
    in_sh = (SH.shardings_for(pspecs, mesh),
             SH.shardings_for(cspecs, mesh),
             NamedSharding(mesh, tok_spec))
    rspecs = SH.residual_specs(mesh, k.policy)
    with SH.activation_sharding(mesh, rspecs):
        fn = jax.jit(step, in_shardings=in_sh, donate_argnums=(1,))
        return fn.lower(params_s, cache_s, tok_s)


# ---------------------------------------------------------------------------
# sweep driver with JSON cache
# ---------------------------------------------------------------------------


def cell_key(arch: str, shape: str, mesh_name: str, knobs: Knobs) -> str:
    return f"{arch}|{shape}|{mesh_name}|{knobs.key()}"


def load_results(path: str) -> Dict[str, Any]:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_results(path: str, results: Dict[str, Any]):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=RESULTS_DEFAULT)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for the result key (perf iters)")
    ap.add_argument("--list", action="store_true")
    # knob overrides
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--moment-dtype", default="")
    ap.add_argument("--param-dtype", default="bfloat16")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--width", type=float, default=1.0)
    ap.add_argument("--depth-frac", type=float, default=1.0)
    ap.add_argument("--policy", default="")
    ap.add_argument("--attn-chunk", type=int, default=1024)
    ap.add_argument("--capacity-factor", type=float, default=1.25)
    ap.add_argument("--grad-dtype", default="float32")
    ap.add_argument("--bf16-grad-matmul", action="store_true")
    ap.add_argument("--no-sp", action="store_true")
    ap.add_argument("--debug-mesh", action="store_true",
                    help="use the 8-device debug mesh (CI / REPRO_DRYRUN_DEVICES)")
    ap.add_argument("--save-hlo", default="",
                    help="directory to dump compiled HLO (gzipped) per cell")
    args = ap.parse_args(argv)

    if args.list:
        for a in list_archs():
            print(a)
        return 0

    knobs = Knobs(remat=args.remat, microbatches=args.microbatches,
                  moment_dtype=args.moment_dtype, param_dtype=args.param_dtype,
                  kv_quant=args.kv_quant, width=args.width,
                  depth_frac=args.depth_frac, policy=args.policy,
                  attn_chunk=args.attn_chunk,
                  capacity_factor=args.capacity_factor, sp=not args.no_sp,
                  grad_dtype=args.grad_dtype,
                  bf16_grad_matmul=args.bf16_grad_matmul)

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = [s.name for s in SHAPES] if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = load_results(args.out)
    n_ok = n_skip = n_err = 0
    for multi in meshes:
        mesh_name = ("2x2x2" if multi else "2x4") if args.debug_mesh else \
            ("2x16x16" if multi else "16x16")
        for arch in archs:
            for shape in shapes:
                key = cell_key(arch, shape, mesh_name, knobs) + (
                    f"|{args.tag}" if args.tag else "")
                if key in results and not args.force \
                        and results[key].get("status") in ("ok", "skip"):
                    print(f"[cache] {key} -> {results[key]['status']}")
                    continue
                print(f"[run] {arch} x {shape} x {mesh_name} ...", flush=True)
                rec = run_cell(arch, shape, multi, knobs,
                               debug_mesh=args.debug_mesh,
                               hlo_dir=args.save_hlo)
                rec["tag"] = args.tag
                results[key] = rec
                save_results(args.out, results)
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skip"
                n_err += st == "error"
                if st == "ok":
                    r = rec["roofline"]
                    print(f"  ok: mem={rec['memory']['live_bytes_per_device']/1e9:.2f}GB "
                          f"compute={r['compute_s']*1e3:.1f}ms "
                          f"memory={r['memory_s']*1e3:.1f}ms "
                          f"coll={r['collective_s']*1e3:.1f}ms "
                          f"dom={r['dominant']} frac={r['roofline_fraction']:.3f} "
                          f"(lower {rec['time_lower_s']}s, compile {rec['time_compile_s']}s)",
                          flush=True)
                elif st == "skip":
                    print(f"  skip: {rec['reason']}")
                else:
                    print(f"  ERROR: {rec['error']}")
    print(f"done: ok={n_ok} skip={n_skip} err={n_err}")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
