"""Token-tree speculative decoding: property harness.

The tree engine multiplies the speculative state-machine surface (arbitrary
static topologies x acceptance paths x SWA/SSM/kv-quant caches), so this
file proves the core claim by construction: for generated tree topologies
and EVERY acceptance path — accept-none through accept-full-path, every
root-to-leaf branch — tree verify+commit leaves the per-slot cache identical
to sequentially decoding the accepted tokens, with mixed widths, rolling
sliding windows, and int8 KV quant included. Plus: the multi-candidate
rejection rule matches the verifier distribution at temperature > 0
(statistical), reduces exactly to greedy at temperature 0, the tree draft
is NON-destructive (no cache-sized scan carry — checked on the jaxpr, with
the linear draft as the copying baseline), greedy tree serving is
token-identical to plain serving with zero re-traces (locally and on
2x4 / 8x1 CPU meshes via subprocess), and the SLO policy's tree/linear/
plain choice behaves under queue pressure."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import elastic
from repro.models.model import (commit_verify, decode_step, init_decode_cache,
                                init_params, verify_tree)
from repro.runtime import sampling
from repro.runtime import speculative as SP
from repro.runtime.serving import Request, ServingEngine, SLOPolicy
from repro.runtime.speculative import (SpecConfig, tree_node_budget,
                                       tree_topology)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# >= 3 distinct topologies exercised against every acceptance path; the
# seeded generator below adds arbitrary schedules on top of these.
TOPOLOGIES = [(2,), (2, 1), (2, 2), (1, 1, 1), (3, 1)]


def _random_branching(rng) -> tuple:
    return tuple(int(b) for b in rng.integers(1, 4, int(rng.integers(1, 4))))


def _leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


# ---------------------------------------------------------------------------
# topology planner invariants
# ---------------------------------------------------------------------------


def test_tree_topology_invariants():
    rng = np.random.default_rng(0)
    for br in TOPOLOGIES + [_random_branching(rng) for _ in range(10)]:
        topo = tree_topology(br)
        assert topo.parents[0] == -1 and topo.depths[0] == 0
        for node in range(1, topo.n_nodes):
            par = int(topo.parents[node])
            assert par < node  # parents precede children (BFS order)
            assert topo.depths[node] == topo.depths[par] + 1
            assert topo.paths[node][:-1] == topo.paths[par]
        # node budget: product-sum of the branching schedule
        frontier, total = 1, 0
        for b in br:
            frontier *= b
            total += frontier
        assert topo.n_draft_nodes == total == tree_node_budget(br)
        # ancestor bias: row i admits exactly path(i)
        for node in range(topo.n_nodes):
            open_cols = np.nonzero(topo.ancestor_bias[node] == 0.0)[0]
            assert tuple(open_cols) == topo.paths[node]


def test_tree_topology_rejects_bad_branching():
    with pytest.raises(ValueError, match="branching"):
        tree_topology((2, 0))


# ---------------------------------------------------------------------------
# rollback property: every topology x every path x every acceptance count
# ---------------------------------------------------------------------------


def _assert_tree_rollback(cfg, branching, *, active=None, widths=None,
                          warm_tokens=3, atol=3e-5):
    """Core property: committing ANY root-to-leaf path at ANY acceptance
    count equals sequentially decoding the accepted tokens — logits AND the
    full cache, leaf by leaf."""
    params = init_params(jax.random.PRNGKey(0), cfg)
    B = 2
    topo = tree_topology(branching)
    if widths is not None:
        active = jax.tree_util.tree_map(
            jnp.asarray, elastic.active_widths_batch(cfg, widths))
    cache = init_decode_cache(cfg, B, 16, per_slot=True)
    for t in range(warm_tokens):
        tok = jnp.asarray([[3 + t], [5 + t]], jnp.int32)
        _, cache = decode_step(params, cache, tok, cfg, active=active)
    rng = np.random.default_rng(hash(branching) % (2**31))
    toks = rng.integers(1, cfg.vocab_size,
                        (B, topo.n_nodes)).astype(np.int32)
    for depth in sorted({1, cfg.n_groups}):
        logits, pending = verify_tree(params, cache, jnp.asarray(toks), cfg,
                                      tree=topo, depth=depth, active=active)
        leaf_nodes = [n for n in range(topo.n_nodes)
                      if topo.depths[n] == topo.n_levels]
        for leaf in leaf_nodes:
            path = list(topo.paths[leaf])
            for m in range(topo.n_levels + 1):
                pn = jnp.asarray(np.asarray([path] * B, np.int32))
                committed = commit_verify(
                    cache, pending, jnp.full((B,), m, jnp.int32), cfg,
                    path_nodes=pn)
                ref = cache
                for t in range(m + 1):
                    node = path[t]
                    lr, ref = decode_step(
                        params, ref, jnp.asarray(toks[:, node:node + 1]),
                        cfg, depth=depth, active=active)
                np.testing.assert_allclose(
                    np.asarray(logits[:, path[m]]), np.asarray(lr[:, 0]),
                    atol=atol, rtol=1e-5,
                    err_msg=f"{branching} d{depth} path{path} m{m} logits")
                for (pa, a), (_, b) in zip(_leaves(committed), _leaves(ref)):
                    np.testing.assert_allclose(
                        np.asarray(a, np.float32), np.asarray(b, np.float32),
                        atol=atol, rtol=1e-5,
                        err_msg=f"{branching} d{depth} path{path} m{m} "
                                f"{jax.tree_util.keystr(pa)}")


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-370m"])
@pytest.mark.parametrize("branching", [(2,), (2, 1), (2, 2)])
def test_tree_verify_rollback_matches_sequential(arch, branching):
    """Attention and SSM archs, mixed per-slot widths, shallow + full depth:
    every acceptance path of the tree is rollback-exact."""
    _assert_tree_rollback(smoke_config(arch), branching, widths=[0.5, 1.0])


def test_tree_verify_rollback_sliding_window():
    """Rolling KV buffers: the ancestor-masked tree verify must read the
    pre-write buffer and the path-gathered commit must preserve rolled
    entries for rejected branches."""
    cfg = dataclasses.replace(smoke_config("mixtral-8x22b"), sliding_window=6)
    _assert_tree_rollback(cfg, (2, 1), warm_tokens=7)  # wrap the buffer


def test_tree_verify_rollback_kv_quant():
    """int8 KV: tree attention must run over the quantize->dequantize round
    trip of new entries; the path commit stores the same quantized values."""
    cfg = dataclasses.replace(smoke_config("tinyllama-1.1b"), kv_quant=True)
    _assert_tree_rollback(cfg, (2, 1))


def test_tree_rollback_arbitrary_generated_topologies():
    """Seeded arbitrary branching schedules (the tier-1 stand-in for the
    hypothesis sweep below, which needs the optional dependency)."""
    rng = np.random.default_rng(42)
    seen = set()
    cfg = smoke_config("tinyllama-1.1b")
    for _ in range(3):
        br = _random_branching(rng)
        while br in seen:
            br = _random_branching(rng)
        seen.add(br)
        _assert_tree_rollback(cfg, br, widths=[0.5, 1.0])


def test_tree_rollback_hypothesis_topologies():
    pytest.importorskip("hypothesis",
                        reason="hypothesis not installed (requirements-dev)")
    from hypothesis import given, settings, strategies as st

    cfg = smoke_config("mamba2-370m")

    @settings(max_examples=5, deadline=None)
    @given(st.lists(st.integers(1, 3), min_size=1, max_size=3))
    def prop(branching):
        _assert_tree_rollback(cfg, tuple(branching))

    prop()


def test_verify_tree_guards():
    cfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_decode_cache(cfg, 1, 8, per_slot=True)
    topo = tree_topology((2,))
    with pytest.raises(ValueError, match="nodes"):
        verify_tree(params, cache, jnp.zeros((1, 5), jnp.int32), cfg,
                    tree=topo)
    cfg_w = dataclasses.replace(smoke_config("mixtral-8x22b"),
                                sliding_window=2)
    params_w = init_params(jax.random.PRNGKey(0), cfg_w)
    cache_w = init_decode_cache(cfg_w, 1, 8, per_slot=True)
    deep = tree_topology((1, 1, 1))
    with pytest.raises(ValueError, match="sliding"):
        verify_tree(params_w, cache_w, jnp.zeros((1, 4), jnp.int32), cfg_w,
                    tree=deep)
    with pytest.raises(ValueError, match="sliding_window"):
        ServingEngine(params_w, cfg_w, batch_size=1, cache_capacity=8,
                      speculative=SpecConfig(ks=(), trees=((1, 1, 1),)))


# ---------------------------------------------------------------------------
# acceptance rule: greedy reduction + distribution identity
# ---------------------------------------------------------------------------


def test_tree_greedy_acceptance_reduction():
    """At temperature 0 the tree walk accepts exactly the child matching the
    verifier argmax — at ANY sibling rank — and emits the argmax on stop."""
    topo = tree_topology((2, 1))  # nodes: 0; 1,2; 3 (child of 1), 4 (of 2)
    B, V = 2, 8
    tokens = np.asarray([[0, 4, 6, 2, 7],
                         [0, 4, 6, 2, 7]], np.int32)
    # slot 0 verifier: argmax 6 at root (rank-1 child), argmax 7 at node 2
    # (its child token), argmax 3 at node 4 -> accept 2 then bonus 3.
    # slot 1 verifier: argmax 5 at root -> no child matches, emit 5.
    v = {0: [6, 5], 2: [7, 0], 4: [3, 0]}
    logits = np.full((B, topo.n_nodes, V), -5.0, np.float32)
    for node, per_slot in v.items():
        for b in range(B):
            logits[b, node, per_slot[b]] = 5.0
    dlogits = np.full((B, topo.n_nodes, V), -5.0, np.float32)
    dlogits[:, 0, 4] = 5.0  # draft argmax at root = rank-0 child token
    dlogits[:, 2, 7] = 5.0
    keys = sampling.make_slot_keys(0, B)
    out, path, n_acc = SP.accept_tree(
        jnp.asarray(logits), jnp.asarray(dlogits), jnp.asarray(tokens),
        topo, keys, 0.0, V)
    out, path, n_acc = np.asarray(out), np.asarray(path), np.asarray(n_acc)
    assert n_acc[0] == 2 and out[0].tolist() == [6, 7, 3]
    assert path[0].tolist() == [0, 2, 4]
    assert n_acc[1] == 0 and out[1, 0] == 5
    assert path[1, 0] == 0 and path[1, 1] == 0  # stop-node padding


def test_tree_accepts_full_path_when_draft_equals_verifier():
    """p == q one-hot down one branch: the walk accepts to the leaf and
    emits the leaf's bonus token."""
    topo = tree_topology((2,))
    B, V = 1, 6
    tokens = np.asarray([[0, 3, 1]], np.int32)
    logits = np.full((B, 3, V), -5.0, np.float32)
    logits[0, 0, 3] = 5.0  # root argmax == rank-0 child
    logits[0, 1, 2] = 5.0  # bonus at the accepted leaf
    dlogits = np.full((B, 3, V), -5.0, np.float32)
    dlogits[0, 0, 3] = 5.0
    out, path, n_acc = SP.accept_tree(
        jnp.asarray(logits), jnp.asarray(dlogits), jnp.asarray(tokens),
        topo, sampling.make_slot_keys(0, B), 0.0, V)
    assert int(np.asarray(n_acc)[0]) == 1
    assert np.asarray(out)[0].tolist() == [3, 2]


def test_tree_acceptance_matches_verifier_distribution():
    """Multi-candidate rejection sampling: with sibling candidates drawn
    i.i.d. from q, the first emitted token is distributed exactly as the
    verifier p — the distribution-identity the linear rule has, extended to
    b > 1. Checked statistically (total-variation bound) at temperature 1."""
    V, b, n = 8, 3, 8192
    topo = tree_topology((b,))
    rng = np.random.default_rng(7)
    p = rng.dirichlet(np.ones(V) * 2.0)
    q = rng.dirichlet(np.ones(V) * 2.0)
    logits = np.broadcast_to(np.log(p), (n, 1 + b, V)).astype(np.float32)
    dlogits = np.broadcast_to(np.log(q), (n, 1 + b, V)).astype(np.float32)
    draws = rng.choice(V, size=(n, b), p=q)  # i.i.d. sibling candidates
    tokens = np.concatenate([np.zeros((n, 1), np.int64), draws],
                            axis=1).astype(np.int32)
    keys = sampling.make_slot_keys(3, n)
    out, _, _ = SP.accept_tree(jnp.asarray(logits), jnp.asarray(dlogits),
                               jnp.asarray(tokens), topo, keys, 1.0, V)
    emp = np.bincount(np.asarray(out)[:, 0], minlength=V) / n
    tv = 0.5 * np.abs(emp - p).sum()
    assert tv < 0.05, (tv, emp, p)


# ---------------------------------------------------------------------------
# non-destructive drafting: no cache-sized scan carry (the ROADMAP item)
# ---------------------------------------------------------------------------


def _scan_carry_byte_sizes(jaxpr):
    """Byte sizes of every lax.scan carry aval, recursively."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            inner = eqn.params["jaxpr"].jaxpr
            nc = eqn.params["num_consts"]
            nk = eqn.params["num_carry"]
            for var in inner.invars[nc:nc + nk]:
                aval = var.aval
                out.append(int(np.prod(aval.shape, initial=1))
                           * aval.dtype.itemsize)
        for sub in eqn.params.values():
            subs = sub if isinstance(sub, (list, tuple)) else (sub,)
            for s in subs:
                if isinstance(s, jax.extend.core.ClosedJaxpr):
                    out.extend(_scan_carry_byte_sizes(s.jaxpr))
    return out


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-370m"])
def test_tree_draft_is_non_destructive_no_cache_copy(arch):
    """The tree draft must never carry the committed cache through a scan
    (the linear draft's transient per-step cache copy). Structural check on
    the jaxpr: no scan carry is as large as a cache KV/state leaf — while
    the linear draft, the copying baseline, has one. Output-wise the draft
    returns no cache at all, so nothing can be written back either."""
    cfg = smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B = 2
    cache = init_decode_cache(cfg, B, 32, per_slot=True)
    keys = sampling.make_slot_keys(0, B)
    tok0 = jnp.asarray([[3], [5]], jnp.int32)
    cache_leaf_bytes = sorted(
        int(np.prod(a.shape, initial=1)) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(cache["stack"]))
    big = cache_leaf_bytes[0]  # every stack leaf is >= this

    tree_fn = SP.make_tree_draft_step(cfg, 1, (2, 1))
    jx_tree = jax.make_jaxpr(tree_fn)(params, cache, tok0, None, keys,
                                      jnp.float32(0.0), jnp.uint32(0))
    tree_carries = _scan_carry_byte_sizes(jx_tree.jaxpr)
    assert all(c < big for c in tree_carries), \
        (f"tree draft carries a cache-sized buffer through a scan: "
         f"{max(tree_carries)} >= {big}")

    linear_fn = SP.make_draft_step(cfg, 1, 3)
    jx_lin = jax.make_jaxpr(linear_fn)(params, cache, tok0, None, keys,
                                       jnp.float32(0.0), jnp.uint32(0))
    lin_carries = _scan_carry_byte_sizes(jx_lin.jaxpr)
    assert any(c >= big for c in lin_carries), \
        "expected the linear draft's scan to carry the cache (baseline)"

    # KV-carrying draft: scan state is O(n_nodes), INDEPENDENT of the
    # committed cache size — doubling capacity must not move a single
    # carry byte-size (the linear draft's cache-sized carry, by contrast,
    # grows with capacity)
    cache2 = init_decode_cache(cfg, B, 64, per_slot=True)
    jx_tree2 = jax.make_jaxpr(tree_fn)(params, cache2, tok0, None, keys,
                                       jnp.float32(0.0), jnp.uint32(0))
    assert sorted(tree_carries) == \
        sorted(_scan_carry_byte_sizes(jx_tree2.jaxpr)), \
        "tree draft scan state scales with committed cache capacity"
    cap_scales = sorted(
        int(np.prod(a.shape, initial=1)) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(cache2["stack"])) \
        != cache_leaf_bytes
    if cap_scales:  # SSM-only caches are capacity-independent to begin with
        jx_lin2 = jax.make_jaxpr(linear_fn)(params, cache2, tok0, None, keys,
                                            jnp.float32(0.0), jnp.uint32(0))
        assert sorted(lin_carries) != \
            sorted(_scan_carry_byte_sizes(jx_lin2.jaxpr)), \
            "baseline lost its cache-sized carry — tighten the tree assertion"


def test_tree_draft_position_count_is_o_n_nodes():
    """The KV-carrying draft processes each non-leaf node exactly once —
    O(n_nodes) positions per launch — strictly fewer than the pre-carry
    level-rescoring pass (O(sum-of-level-prefix-sizes)) for any schedule
    deeper than one level."""
    from repro.models.model import init_tree_draft_carry, tree_carry_nodes

    cfg = smoke_config("tinyllama-1.1b")
    for br in TOPOLOGIES + [(3, 2, 1), (2, 2, 2, 2)]:
        topo = SP.tree_topology(br)
        new = SP.tree_draft_position_count(br)
        old = SP.tree_rescore_position_count(br)
        f0, f1 = topo.level_nodes(topo.n_levels)
        assert new == topo.n_nodes - (f1 - f0)  # every node but the leaves
        assert new <= old
        if topo.n_levels >= 2:
            assert new < old, f"{br}: carry draft did not reduce positions"
        # the carry allocation is exactly the processed-node count per layer
        carry = init_tree_draft_carry(cfg, 2, topo, depth=1)
        for leaf in jax.tree_util.tree_leaves(carry):
            assert leaf.shape[2] == tree_carry_nodes(topo) == new
    assert SP.tree_draft_position_count((2, 2)) == 3
    assert SP.tree_rescore_position_count((2, 2)) == 4
    assert SP.tree_draft_position_count((3, 2, 1)) == 10
    assert SP.tree_rescore_position_count((3, 2, 1)) == 15


def test_tree_draft_leaves_committed_cache_unchanged():
    """Value-level counterpart of the jaxpr check: a draft launch must not
    move the committed cache by a single bit."""
    cfg = smoke_config("mamba2-370m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_decode_cache(cfg, 2, 16, per_slot=True)
    _, cache = decode_step(params, cache, jnp.asarray([[3], [5]], jnp.int32),
                           cfg)
    before = jax.tree_util.tree_map(np.asarray, cache)
    draft = jax.jit(SP.make_tree_draft_step(cfg, 1, (2, 2)))
    draft(params, cache, jnp.asarray([[9], [2]], jnp.int32), None,
          sampling.make_slot_keys(0, 2), jnp.float32(0.9), jnp.uint32(0))
    for (pa, a), (_, b) in zip(_leaves(before), _leaves(cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(pa))


# ---------------------------------------------------------------------------
# engine integration: greedy tree == plain, zero re-trace
# ---------------------------------------------------------------------------

SPECS = [(1, 8), (3, 6), (5, 9), (1, 5), (2, 7)]


def _drive(eng):
    for rid, (plen, n_new) in enumerate(SPECS):
        eng.submit(Request(rid=rid, prompt=tuple(range(1, 1 + plen)),
                           max_new_tokens=n_new))
    while eng.queue or eng.n_active:
        eng.step()
    return {r.rid: tuple(r.generated) for r in eng.completed}


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-370m"])
def test_tree_engine_token_identical_and_no_retrace(arch):
    """Greedy tree speculative serving emits exactly the plain engine's
    tokens, compiles tree draft+verify once at warmup, never re-traces."""
    from repro.kernels.morph_matmul import trace_count

    cfg = smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    plain = ServingEngine(params, cfg, batch_size=2, cache_capacity=32,
                          prefill_threshold=4)
    plain.warmup()
    out_plain = _drive(plain)

    eng = ServingEngine(params, cfg, batch_size=2, cache_capacity=32,
                        prefill_threshold=4,
                        speculative=SpecConfig(ks=(), trees=((2, 1),)))
    eng.warmup()
    depths = {m.depth for m in eng.ctrl.modes}
    # one decode per depth + one tree draft (shared exit) + one tree verify
    # per speculating depth
    assert eng.compiles_after_warmup == len(depths) + 1 + len(depths) - 1
    frozen = eng.ctrl.stats["compiles"]
    traces0 = eng.ctrl.trace_counter["n"]
    ktraces0 = trace_count()
    out_tree = _drive(eng)
    assert out_tree == out_plain
    assert eng.ctrl.stats["compiles"] == frozen
    assert eng.ctrl.trace_counter["n"] == traces0
    assert trace_count() == ktraces0
    assert eng.spec_tree_launches > 0
    (path, tel), = eng.spec_telemetry_summary().items()
    assert tel["tree"] == "2x1" and tel["draft_nodes"] == 4
    assert tel["tokens_per_slot_launch"] >= 1.0


def test_tree_and_linear_shapes_share_one_warmup():
    """ks and trees compile side by side into the aux registry; switching a
    group between them at runtime re-dispatches, never re-traces."""
    cfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, batch_size=2, cache_capacity=32,
                        prefill_threshold=4,
                        speculative=SpecConfig(ks=(2,), trees=((2, 1),)))
    eng.warmup()
    depths = {m.depth for m in eng.ctrl.modes}
    # per depth: decode; plus linear draft+verify and tree draft+verify
    assert eng.compiles_after_warmup == len(depths) + 2 * (1 + len(depths) - 1)
    frozen = eng.ctrl.stats["compiles"]
    traces0 = eng.ctrl.trace_counter["n"]
    g = eng.groups[max(depths)]
    assert g.spec_tree is not None  # tree is the optimistic default
    for rid, (plen, n_new) in enumerate(SPECS):
        eng.submit(Request(rid=rid, prompt=tuple(range(1, 1 + plen)),
                           max_new_tokens=n_new))
    flip = 0
    while eng.queue or eng.n_active:
        # alternate the group's draft shape mid-traffic
        if flip % 2:
            g.spec_tree, g.spec_k = None, 2
        else:
            g.spec_tree, g.spec_k = (2, 1), 0
        flip += 1
        eng.step()
    assert eng.ctrl.stats["compiles"] == frozen
    assert eng.ctrl.trace_counter["n"] == traces0
    assert eng.spec_tree_launches > 0
    assert eng.spec_verify_launches > eng.spec_tree_launches  # linear ran too


def test_tree_respects_capacity_headroom():
    cfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, batch_size=1, cache_capacity=12,
                        prefill_threshold=100,
                        speculative=SpecConfig(ks=(), trees=((2, 2),)))
    eng.warmup()
    eng.submit(Request(rid=0, prompt=(3,), max_new_tokens=12))
    while eng.queue or eng.n_active:
        eng.step()
    r = eng.completed[0]
    assert len(r.generated) == 12
    assert eng.decode_launches > 0  # the tail near capacity stepped plainly


# ---------------------------------------------------------------------------
# policy: expected tokens + tree/linear/plain choice
# ---------------------------------------------------------------------------


def test_expected_tokens_per_tree_launch():
    # b = 1 per level reduces to the linear estimate
    for a in (0.0, 0.3, 0.9, 1.0):
        assert SP.expected_tokens_per_tree_launch(a, (1, 1, 1)) == \
            pytest.approx(SP.expected_tokens_per_launch(a, 3))
    # wider levels survive more often: strictly better at 0 < a < 1
    assert SP.expected_tokens_per_tree_launch(0.35, (3, 2, 1)) > \
        SP.expected_tokens_per_tree_launch(0.35, (1, 1, 1))
    assert SP.expected_tokens_per_tree_launch(0.0, (3, 2)) == \
        pytest.approx(1.0)
    assert SP.expected_tokens_per_tree_launch(1.0, (3, 2)) == \
        pytest.approx(3.0)


def test_per_candidate_accept_rate_inverts_tree_survival():
    """A tree's measured depth fraction is per-level survival 1-(1-a)^b;
    the conversion must recover a (identity for linear drafts) so the
    policy never applies the branching advantage twice."""
    a = 0.35
    for br in [(2, 2), (3, 3), (2,)]:
        b = br[0]  # uniform branching: survival is exact
        s = 1.0 - (1.0 - a) ** b
        assert SP.per_candidate_accept_rate(s, br) == pytest.approx(a, abs=1e-9)
    assert SP.per_candidate_accept_rate(0.4, None) == pytest.approx(0.4)
    assert SP.per_candidate_accept_rate(0.4, (1, 1)) == pytest.approx(0.4)
    assert SP.per_candidate_accept_rate(1.0, (3, 3)) == 1.0
    assert SP.per_candidate_accept_rate(-0.1, (2,)) == 0.0


def test_choose_tree_policy():
    cfg = smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, batch_size=2, cache_capacity=32)
    eng.warmup()
    pol = SLOPolicy(cfg, eng.ctrl, batch_size=2, cache_capacity=32)
    trees, ks = [(2, 2), (2, 1)], [2, 4]
    # moderate acceptance, empty queue: a tree's sibling coverage wins
    kind, shape = pol.choose_tree(trees, ks, accept_rate=0.4)
    assert kind == "tree"
    # deep queue: pressure charges the node budget -> narrower shape
    k2, s2 = pol.choose_tree(trees, ks, accept_rate=0.4,
                             queue_depths={"interactive": 200, "batch": 200})
    budget = {"tree": tree_node_budget, "linear": lambda k: k}[k2](s2)
    assert budget <= tree_node_budget(shape)
    # collapsed acceptance: plain stepping
    assert pol.choose_tree(trees, ks, accept_rate=0.0) == ("plain", None)
    assert pol.choose_tree([], [], accept_rate=0.9) == ("plain", None)


# ---------------------------------------------------------------------------
# mesh case (8-device CPU subprocess: 2x4 and 8x1, same pattern as
# test_serving_mesh / test_speculative)
# ---------------------------------------------------------------------------

_MESH_TREE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import smoke_config
from repro.launch.mesh import make_serve_mesh
from repro.models.model import init_params
from repro.runtime.serving import MeshExecutor, Request, ServingEngine
from repro.runtime.speculative import SpecConfig

SPECS = [(1, 8), (3, 6), (5, 9), (1, 5)]

def drive(eng):
    for rid, (plen, n_new) in enumerate(SPECS):
        eng.submit(Request(rid=rid, prompt=tuple(range(1, 1 + plen)),
                           max_new_tokens=n_new))
    while eng.queue or eng.n_active:
        eng.step()
    return {r.rid: tuple(r.generated) for r in eng.completed}

cfg = smoke_config("tinyllama-1.1b")
params = init_params(jax.random.PRNGKey(0), cfg)
spec = SpecConfig(ks=(), trees=((2, 1),))
el = ServingEngine(params, cfg, batch_size=3, cache_capacity=32,
                   prefill_threshold=4, speculative=spec)
el.warmup()
out_l = drive(el)
for dp, tp in [(2, 4), (8, 1)]:
    em = ServingEngine(params, cfg, batch_size=3, cache_capacity=32,
                       prefill_threshold=4, speculative=spec,
                       executor=MeshExecutor(make_serve_mesh(dp, tp)))
    em.warmup()
    assert em.compiles_after_warmup == el.compiles_after_warmup
    tr0 = em.ctrl.trace_counter["n"]
    out_m = drive(em)
    assert out_m == out_l, (dp, tp, out_m, out_l)
    assert em.ctrl.trace_counter["n"] == tr0, f"{dp}x{tp}: re-traced"
    assert em.spec_tree_launches > 0
print("MESH_TREE_OK")
"""


def test_mesh_tree_engine_matches_local():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", _MESH_TREE_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "MESH_TREE_OK" in out.stdout
