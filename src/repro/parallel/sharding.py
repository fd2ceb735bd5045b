"""Logical-axis sharding rules for every family and execution kind.

Policies (see DESIGN.md §Sharding):

* ``train`` — 2-D weight sharding: dim0 (d_model/vocab) -> data axes (FSDP /
  ZeRO-3: XLA all-gathers per scan step), inner dims (heads / d_ff / experts /
  d_inner) -> ``model`` (TP). Batch -> data axes. Residual stream sequence ->
  ``model`` between groups (Megatron-style SP) via ``constrain``.
* ``serve_tp`` — weights inner-dim -> ``model`` only (fit small/mid models),
  batch -> data axes, KV cache seq -> ``model``.
* ``serve_2d`` — weights 2-D like train (required to fit >=67B on 16 GB
  chips), batch REPLICATED (decode activations are KB-scale; sharded weights
  still shard the compute), KV cache seq -> all axes (256-way).

MoE experts: EP (experts -> model) when divisible, else expert-TP
(per-expert d_ff -> model).

Serving-cache sharding policy (the elastic morph cache, used by the
continuous-batching engine through its executor seam):

The engine keeps one FULL-width per-slot cache per compiled depth —
``{"pos": (n_slots,), "stack": {... (n_groups, n_slots, ...)}}`` — and width
morphs at runtime via ``active`` operands, so the cache layout (and its
sharding) is identical for every width. ``serve_cache_specs`` maps that
layout: the leading dim of every stack leaf is the layer-group stack
(replicated — the decode scan indexes it), ``n_slots`` goes to the data axes
when divisible (``serve_tp``) or stays replicated (``serve_2d``), KV sequence
goes to ``model``, SSM state heads go to ``model``, and per-slot ``pos``
counters are replicated (host-visible slot bookkeeping). ``decode_specs``
complements it with the activation constraints the decode step applies via
``constrain``: the residual stream plus the post-projection q/kv head tensors
and SSM channel tensors, pinned to head-sharded (divisible) or replicated
layouts so the partitioner never splits attention/SSM math through a head.

The executor seam itself lives in ``runtime.serving``: ``LocalExecutor``
compiles host-local executables, ``MeshExecutor`` compiles the same step /
reset / adopt / prefill ops with ``NamedSharding``-annotated jit using the
specs from this module — engine code never branches on mesh-ness.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# activation-constraint context (used by model code via `constrain`)
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, specs: Dict[str, P]):
    prev = getattr(_CTX, "val", None)
    _CTX.val = (mesh, specs)
    try:
        yield
    finally:
        _CTX.val = prev


def current_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing ``activation_sharding`` context, if any."""
    ctx = getattr(_CTX, "val", None)
    return None if ctx is None else ctx[0]


def constrain(x, name: str):
    ctx = getattr(_CTX, "val", None)
    if ctx is None:
        return x
    mesh, specs = ctx
    spec = specs.get(name)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# axis vocabulary
# ---------------------------------------------------------------------------


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis(mesh: Mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def _spec_like(tree, fn):
    return jax.tree_util.tree_map_with_path(fn, tree)


def param_specs(params_shape, cfg: ModelConfig, mesh: Mesh, policy: str) -> Any:
    """PartitionSpec pytree matching the params structure.

    ``params_shape`` is a ShapeDtypeStruct pytree (from eval_shape) or real
    params; only the tree structure and leaf ranks are consulted.
    """
    m = model_axis(mesh)
    d0: Any = data_axes(mesh) or None
    if policy == "serve_tp":
        d0 = None  # inner-dim sharding only
    ep = bool(cfg.n_experts) and cfg.n_experts % (mesh.shape.get("model", 1)) == 0

    def leaf_spec(path, leaf) -> P:
        keys = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
        flat = "/".join(keys)
        nd = len(leaf.shape)
        stacked = ("stack" in keys) or ("encoder" in keys and "stack" in keys)
        o = 1 if stacked else 0  # leading group axis

        def spec(*axes):
            full = [None] * nd
            for i, ax in enumerate(axes):
                full[o + i] = ax
            return P(*full)

        name = keys[-1]
        parent = keys[-2] if len(keys) >= 2 else ""
        if name == "embed":
            return P(m, d0)
        if name == "unembed":
            return P(d0, m)
        if name == "frontend_proj":
            return P(None, d0)
        if name in ("scale", "bias"):  # norms (incl. ssm_norm)
            if parent == "ssm_norm":
                return spec(m)
            return P(*([None] * nd))
        if parent in ("attn", "cross"):
            if name in ("wq", "wk", "wv"):
                return spec(d0, m)
            if name == "wo":
                return spec(m, d0)
        if parent == "mlp":
            if name in ("wi", "wg"):
                return spec(d0, m)
            if name == "wo":
                return spec(m, d0)
        if parent == "moe":
            if name == "router":
                return spec(d0, None)
            if ep:
                if name in ("wi", "wg"):
                    return spec(m, d0, None)
                if name == "wo":
                    return spec(m, None, d0)
            else:
                if name in ("wi", "wg"):
                    return spec(None, d0, m)
                if name == "wo":
                    return spec(None, m, d0)
        if parent == "ssm":
            if name in ("w_x", "w_z"):
                return spec(d0, m)
            if name == "w_bc":
                return spec(d0, None)
            if name == "w_dt":
                return spec(d0, None)
            if name == "conv_x_w":
                return spec(m, None)
            if name == "conv_x_b":
                return spec(m)
            if name in ("conv_bc_w", "conv_bc_b"):
                return P(*([None] * nd))
            if name in ("A_log", "D", "dt_bias"):
                return P(*([None] * nd))
            if name == "out_proj":
                return spec(m, d0)
        return P(*([None] * nd))

    return _spec_like(params_shape, leaf_spec)


def shardings_for(tree_specs, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# batch / cache / state specs
# ---------------------------------------------------------------------------


def batch_specs(batch_shape, mesh: Mesh, policy: str) -> Any:
    d: Any = data_axes(mesh) or None
    if policy == "serve_2d":
        d = None  # decode activations replicated

    def leaf(path, leafv):
        nd = len(leafv.shape)
        return P(*([d] + [None] * (nd - 1)))

    return _spec_like(batch_shape, leaf)


def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def cache_specs(cache_shape, cfg: ModelConfig, mesh: Mesh, policy: str,
                paged: bool = False) -> Any:
    """Decode-cache specs. Leaves carry leading group axis.

    KV seq dim -> model (serve_tp) or (data, model) (serve_2d, 256-way).
    SSM state heads -> model; batch -> data (serve_tp) / replicated (serve_2d).
    Axes that do not divide a leaf dim fall back to replication (e.g.
    global_batch=1 in long_500k).

    With ``paged=True`` the attention leaves are the block-paged pool
    ``(G, n_pages, page_size, KV, hd)``: the pool has no batch or contiguous
    sequence dim to split, so it shards by KV head on ``model`` (matching the
    ``decode_kv`` activation pins) and the page/offset dims stay replicated —
    page tables index into the pool identically on every shard. SSM leaves
    remain per-slot dense and keep the dense rules.
    """
    m = model_axis(mesh)
    d: Any = data_axes(mesh) or None
    batch_ax = d if policy != "serve_2d" else None
    seq_ax: Any = m if policy != "serve_2d" else ((d, m) if isinstance(d, str)
                                                  else tuple(list(d or ()) + [m]))

    def fit(ax, dim):
        return ax if ax is not None and dim % _axes_size(mesh, ax) == 0 else None

    def leaf(path, leafv):
        keys = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
        name = keys[-1]
        nd = len(leafv.shape)
        if name == "pos":
            return P()
        if paged and name in ("k", "v", "k_scale", "v_scale"):
            # pool (G, n_pages, page_size, KV, hd) / scales (..., KV, 1)
            return P(None, None, None, fit(m, leafv.shape[3]), None)
        # leading dim is the group stack
        if name in ("k", "v", "k_scale", "v_scale", "cross_k", "cross_v"):
            # (G, B, S, KV, hd) or scales (G, B, S, KV, 1)
            return P(None, fit(batch_ax, leafv.shape[1]), fit(seq_ax, leafv.shape[2]),
                     None, None)
        if name == "state":  # (G, B, nh, hp, n)
            return P(None, fit(batch_ax, leafv.shape[1]), fit(m, leafv.shape[2]),
                     None, None)
        if name in ("conv_x",):  # (G, B, k-1, d_inner)
            return P(None, fit(batch_ax, leafv.shape[1]), None,
                     fit(m, leafv.shape[3]))
        if name in ("conv_bc",):
            return P(None, fit(batch_ax, leafv.shape[1]), None, None)
        return P(*([None] * nd))

    return _spec_like(cache_shape, leaf)


def serve_cache_specs(cache_shape, cfg: ModelConfig, mesh: Mesh, policy: str,
                      paged: bool = False) -> Any:
    """Specs for the engine's per-slot morph cache (see module docstring).

    ``cache_shape`` is the full engine cache dict — ``pos`` (n_slots,) plus
    the per-group ``stack`` — as a ShapeDtypeStruct pytree or real cache.
    Stack leaves reuse ``cache_specs`` (n_slots is their batch dim); ``pos``
    stays replicated: it is read on the host every admission tick.
    ``paged=True`` switches the attention leaves to the block-pool rules.
    """
    return {"pos": P(None), "stack": cache_specs(cache_shape["stack"], cfg,
                                                 mesh, policy, paged=paged)}


def decode_specs(cfg: ModelConfig, mesh: Mesh, policy: str,
                 batch: Optional[int] = None) -> Dict[str, P]:
    """Activation constraints for the one-token decode path.

    ``residual`` covers the (B, 1, d_model) stream between layer groups.
    ``decode_q`` / ``decode_kv`` pin the post-projection (B, 1, heads, hd)
    tensors to a by-head layout (model axis when it divides the head count,
    else replicated), and ``decode_ssm`` pins the (B, 1, d_inner) SSM channel
    tensors likewise. Without these the partitioner inherits the fused
    projection's column sharding, which splits head_dim across shards —
    wasteful on TPU and miscompiled by some XLA CPU versions. ``batch``
    enables batch-dim sharding over the data axes only when it divides.
    """
    m = model_axis(mesh)
    d: Any = data_axes(mesh) or None
    if policy == "serve_2d":
        d = None  # decode activations replicated over data axes
    b = d if batch and d is not None and batch % _axes_size(mesh, d) == 0 else None
    tp = mesh.shape.get("model", 1) if m else 1
    specs: Dict[str, P] = {"residual": P(b, None, None)}
    if cfg.n_heads:
        specs["decode_q"] = P(b, None, m if cfg.n_heads % tp == 0 else None, None)
        specs["decode_kv"] = P(b, None, m if cfg.n_kv_heads % tp == 0 else None, None)
    if cfg.ssm_state:
        d_in = cfg.ssm_d_inner
        specs["decode_ssm"] = P(b, None, m if d_in % tp == 0 else None)
    return specs


def verify_specs(cfg: ModelConfig, mesh: Mesh, policy: str,
                 batch: Optional[int] = None) -> Dict[str, P]:
    """Activation constraints for the speculative multi-position verify pass.

    Same constraint names as ``decode_specs`` but pinned REPLICATED over the
    model axis: the XLA CPU partitioner mis-lowers the extended-KV attention
    at (B, S > 1, ...) shapes when by-head sharding propagates into the group
    scan (the same bug class ``decode_specs`` works around for one-token
    decode, observed as wrong logits rather than a crash). This covers every
    multi-position speculative shape: linear verify windows (B, K+1), token
    trees (B, n_nodes) — whose ancestor-masked attention and per-node SSM
    recurrence hit the same mis-lowering — and the tree DRAFT pass, which
    runs (B, n_nodes) verify_tree scoring internally and must be compiled
    under these pins rather than the one-token decode ones. Verify
    activations are a handful of tokens — KB-scale — so replicating their
    math costs one small all-gather per projection while the weights stay
    sharded; the cache commit keeps the sharded serving-cache layout via the
    jit out_shardings.
    """
    d: Any = data_axes(mesh) or None
    if policy == "serve_2d":
        d = None
    b = d if batch and d is not None and batch % _axes_size(mesh, d) == 0 else None
    specs: Dict[str, P] = {"residual": P(b, None, None)}
    if cfg.n_heads:
        specs["decode_q"] = P(b, None, None, None)
        specs["decode_kv"] = P(b, None, None, None)
    if cfg.ssm_state:
        specs["decode_ssm"] = P(b, None, None)
    return specs


def opt_specs(opt_shape, pspecs) -> Any:
    """Optimizer state mirrors param sharding; step is replicated."""
    from repro.optim.optimizer import OptState

    def nu_spec(spec, leafv):
        if leafv.shape == (0,):  # sgdm placeholder
            return P(None)
        return spec

    nu = jax.tree_util.tree_map(nu_spec, pspecs, opt_shape.nu,
                                is_leaf=lambda x: isinstance(x, P))
    return OptState(step=P(), mu=pspecs, nu=nu)


def residual_specs(mesh: Mesh, policy: str) -> Dict[str, P]:
    """Activation constraints (SP): residual (B, S, d)."""
    m = model_axis(mesh)
    d: Any = data_axes(mesh) or None
    if policy == "train":
        return {"residual": P(d, m, None), "logits": P(d, m, None)}
    if policy == "serve_tp":
        return {"residual": P(d, None, None)}
    return {"residual": P(None, None, None)}


def serve_policy(cfg: ModelConfig, tp: int = 16) -> str:
    """Pick serve sharding by per-chip footprint at TP-only sharding."""
    per_chip = cfg.n_params() * 2 / tp  # bf16
    return "serve_2d" if per_chip > 8e9 else "serve_tp"
