#!/usr/bin/env bash
# Tier-1 CI gate: run the pytest suite with a timeout and print the
# pass/fail delta vs the seed baseline (124 passed / 5 failed / 1 collection
# error at repo seed). Exits non-zero on any failure/error or if passes
# regress below the baseline.
#
#   scripts/ci.sh                # default 1800s timeout
#   CI_TIMEOUT=600 scripts/ci.sh
#   scripts/ci.sh --bench-smoke  # additionally run the morph/serving
#                                # benchmarks in tiny configs so the
#                                # benchmark scripts can't silently rot
#   scripts/ci.sh --mesh-smoke   # additionally run the sharded-serving
#                                # shard (8-device CPU host platform) +
#                                # the --mesh benchmark axes
#   scripts/ci.sh --spec-smoke   # additionally run the speculative-decoding
#                                # tests + the spec_decode benchmark (tiny
#                                # DistillCycle train -> acceptance > 0)
#   scripts/ci.sh --tree-smoke   # additionally run the token-tree
#                                # speculation shard: property harness +
#                                # sampling tests (greedy tree == plain,
#                                # zero re-trace, incl. a 2x4/8x1 mesh
#                                # subprocess case) + the spec_decode
#                                # tree-vs-linear benchmark at equal node
#                                # budget
#   scripts/ci.sh --paged-smoke  # additionally run the block-paged KV
#                                # shard: dense-vs-paged token-identical
#                                # equivalence (mixed widths + depth switch
#                                # + shared-prefix adoption, full-attn /
#                                # SWA / kv-quant, spec + tree) locally and
#                                # on a 2x4 CPU mesh subprocess, plus the
#                                # allocator/radix property tests
#   scripts/ci.sh --chaos-smoke  # additionally run the fault-tolerance
#                                # shard: chaos-trace harness (injected
#                                # executor failures at every launch
#                                # boundary -> bit-identical streams after
#                                # failover, dense + paged, incl. a 2x4 CPU
#                                # mesh subprocess) + snapshot/restore and
#                                # seed fault_tolerance primitive tests
#   scripts/ci.sh --obs-smoke    # additionally run the observability
#                                # shard: registry/trace-recorder tests
#                                # (percentiles vs numpy, Chrome trace
#                                # schema + chaos token accounting,
#                                # snapshot/restore metric carry, SLO
#                                # catch-up, engine phase spans in the
#                                # JAX profiler's trace)
#   scripts/ci.sh --fused-smoke  # additionally run the fused-superkernel
#                                # shard: bit-exact fused-vs-unfused
#                                # decode/verify/tree-verify equivalence +
#                                # zero-retrace tests, the fused serving
#                                # phase (token identity vs the per-op
#                                # path), and the kernel bench with a
#                                # fused <= unfused step-latency gate on
#                                # the CPU ref path
#   scripts/ci.sh --autoscale-smoke  # additionally run the online-
#                                # autoscaler shard: the adopt/retire
#                                # lifecycle tests (dense + paged + 2x4
#                                # mesh subprocess, bit-identity + zero
#                                # tick stalls), the MOGA property /
#                                # DSE-bugfix regression tests, and the
#                                # autoscale serving phase recorded into
#                                # BENCH_serving.json
set -uo pipefail
cd "$(dirname "$0")/.."

SEED_PASSED=124
SEED_FAILED=5
SEED_ERRORS=1
TIMEOUT="${CI_TIMEOUT:-1800}"
BENCH_SMOKE=0
MESH_SMOKE=0
SPEC_SMOKE=0
TREE_SMOKE=0
PAGED_SMOKE=0
CHAOS_SMOKE=0
FUSED_SMOKE=0
OBS_SMOKE=0
AUTOSCALE_SMOKE=0
for arg in "$@"; do
    case "$arg" in
        --bench-smoke) BENCH_SMOKE=1 ;;
        --mesh-smoke) MESH_SMOKE=1 ;;
        --spec-smoke) SPEC_SMOKE=1 ;;
        --tree-smoke) TREE_SMOKE=1 ;;
        --paged-smoke) PAGED_SMOKE=1 ;;
        --chaos-smoke) CHAOS_SMOKE=1 ;;
        --fused-smoke) FUSED_SMOKE=1 ;;
        --obs-smoke) OBS_SMOKE=1 ;;
        --autoscale-smoke) AUTOSCALE_SMOKE=1 ;;
        *) echo "ci.sh: unknown argument '$arg'" >&2; exit 2 ;;
    esac
done

if [ "$OBS_SMOKE" -eq 1 ]; then
    echo "CI: obs-smoke shard (observability layer)"
    OBS_TIMEOUT="${CI_OBS_TIMEOUT:-1200}"
    # registry primitives (exact percentiles vs numpy, Prometheus/JSON
    # export), Chrome trace schema + chaos-run token accounting,
    # snapshot/restore metric carry, SLO failover catch-up, engine phase
    # spans under the JAX profiler
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$OBS_TIMEOUT" \
        python -m pytest -q tests/test_observability.py; then
        echo "CI: FAIL (observability tests)"
        exit 1
    fi
    echo "CI: obs-smoke OK"
fi

if [ "$FUSED_SMOKE" -eq 1 ]; then
    echo "CI: fused-smoke shard (decode/verify superkernel)"
    FUSED_TIMEOUT="${CI_FUSED_TIMEOUT:-1200}"
    # bit-exact fused-vs-unfused equivalence (plain / SWA / kv-quant, dense
    # + paged, mixed widths), pallas-vs-ref kernel checks, zero-retrace
    # invariants, and the engine-level token-identity tests (incl. the 2x4
    # CPU mesh subprocess case)
    if ! FUSED_SMOKE=1 PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        timeout "$FUSED_TIMEOUT" \
        python -m pytest -q tests/test_fused_decode.py; then
        echo "CI: FAIL (fused superkernel tests)"
        exit 1
    fi
    # fused serving phase (token identity vs the per-op path, recorded into
    # benchmarks/results/BENCH_serving.json)
    if ! FUSED_SMOKE=1 PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        timeout "$FUSED_TIMEOUT" \
        python -c "from benchmarks import serve_continuous; serve_continuous.run(n_requests=6, phases=('fused',))"; then
        echo "CI: FAIL (serve_continuous fused bench-smoke)"
        exit 1
    fi
    # kernel bench (writes BENCH_kernels.json) + the latency gate: on the
    # CPU ref path fused and unfused lower to the same graph, so the fused
    # step must stay within noise (<= 1.25x) of the unfused step
    if ! FUSED_SMOKE=1 PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        timeout "$FUSED_TIMEOUT" python - <<'PY'
from benchmarks import kernel_bench
kernel_bench.run()
import json
with open(kernel_bench.BENCH_JSON) as f:
    sections = json.load(f)["sections"]
for kind in ("fused_decode", "fused_verify", "fused_tree_verify"):
    rec = sections[kind]
    assert rec["fused_us"] <= rec["unfused_us"] * 1.25, \
        f"{kind}: fused {rec['fused_us']}us > unfused {rec['unfused_us']}us"
    assert rec["attn_layer_primitives_pallas"] < rec["attn_layer_primitives_unfused"], \
        f"{kind}: superkernel did not shrink the attention layer graph"
print("fused latency gate OK")
PY
    then
        echo "CI: FAIL (kernel bench fused latency gate)"
        exit 1
    fi
    echo "CI: fused-smoke OK"
fi

if [ "$AUTOSCALE_SMOKE" -eq 1 ]; then
    echo "CI: autoscale-smoke shard (online NeuroForge autoscaler)"
    AUTOSCALE_TIMEOUT="${CI_AUTOSCALE_TIMEOUT:-1200}"
    # adopt/retire lifecycle under a traffic shift (dense + paged + 2x4
    # CPU mesh subprocess): background publish_aux adoption, cold-unit
    # retirement under the compile-table budget, bit-identical committed
    # streams, zero serving-tick stalls, snapshot/restore carry — plus the
    # MOGA property tests and the DSE bugfix regressions
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        timeout "$AUTOSCALE_TIMEOUT" \
        python -m pytest -q tests/test_autoscale.py tests/test_properties.py; then
        echo "CI: FAIL (autoscaler / MOGA tests)"
        exit 1
    fi
    # autoscale phase of the serving benchmark (frontier generations,
    # compile-table occupancy, tokens/s vs the static-policy baseline,
    # recorded into benchmarks/results/BENCH_serving.json)
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        timeout "$AUTOSCALE_TIMEOUT" \
        python -c "from benchmarks import serve_continuous; serve_continuous.run(n_requests=8, phases=('autoscale',))"; then
        echo "CI: FAIL (serve_continuous autoscale bench-smoke)"
        exit 1
    fi
    echo "CI: autoscale-smoke OK"
fi

if [ "$CHAOS_SMOKE" -eq 1 ]; then
    echo "CI: chaos-smoke shard (fault-tolerant serving)"
    CHAOS_TIMEOUT="${CI_CHAOS_TIMEOUT:-1200}"
    # chaos-trace harness (bit-identical streams under injected failures at
    # decode / verify / tree-verify / paged-decode / prefill boundaries,
    # dense + paged + mesh subprocess), ServingEngine.snapshot/restore
    # exactness, ExecutorSupervisor mechanics, and the seed
    # fault_tolerance.py primitives (TrainRunner restarts, StragglerMonitor
    # warmup, FailurePlan semantics)
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$CHAOS_TIMEOUT" \
        python -m pytest -q tests/test_chaos.py tests/test_fault_tolerance.py; then
        echo "CI: FAIL (fault-tolerance tests)"
        exit 1
    fi
    # failover phase of the serving benchmark (recovery latency + tokens/s
    # degradation recorded into benchmarks/results/BENCH_serving.json)
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$CHAOS_TIMEOUT" \
        python -c "from benchmarks import serve_continuous; serve_continuous.run(n_requests=6, phases=('failover',))"; then
        echo "CI: FAIL (serve_continuous failover bench-smoke)"
        exit 1
    fi
    echo "CI: chaos-smoke OK"
fi

if [ "$PAGED_SMOKE" -eq 1 ]; then
    echo "CI: paged-smoke shard (block-paged KV cache)"
    PAGED_TIMEOUT="${CI_PAGED_TIMEOUT:-1200}"
    # dense-vs-paged token identity (mixed widths + depth switch +
    # shared-prefix adoption; full-attn / SWA / kv-quant; linear-spec and
    # token-tree engines; incl. the 2x4 CPU mesh subprocess case) plus the
    # allocator/radix property tests and the paged engine invariants
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$PAGED_TIMEOUT" \
        python -m pytest -q tests/test_serving_paged.py \
        "tests/test_serving.py::test_engine_slot_invariants_under_random_traces" \
        "tests/test_serving.py::test_block_allocator_free_list_roundtrip" \
        "tests/test_serving.py::test_radix_insert_match_evict_deterministic" \
        "tests/test_serving.py::test_radix_allocator_properties"; then
        echo "CI: FAIL (block-paged KV tests)"
        exit 1
    fi
    echo "CI: paged-smoke OK"
fi

if [ "$TREE_SMOKE" -eq 1 ]; then
    echo "CI: tree-smoke shard (token-tree speculation)"
    TREE_TIMEOUT="${CI_TREE_TIMEOUT:-1200}"
    # the property harness includes the greedy-tree==plain + zero-re-trace
    # engine tests and the 2x4/8x1 mesh subprocess case
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$TREE_TIMEOUT" \
        python -m pytest -q tests/test_tree_speculative.py tests/test_sampling.py; then
        echo "CI: FAIL (token-tree tests)"
        exit 1
    fi
    # tree vs linear at equal node budget (asserts the tree wins)
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$TREE_TIMEOUT" \
        python -c "from benchmarks import spec_decode; spec_decode.run(n_requests=8, train_steps=8, ks=(2,), trees=((2,1),))"; then
        echo "CI: FAIL (spec_decode tree bench-smoke)"
        exit 1
    fi
    echo "CI: tree-smoke OK"
fi

if [ "$SPEC_SMOKE" -eq 1 ]; then
    echo "CI: spec-smoke shard (speculative decoding)"
    SPEC_TIMEOUT="${CI_SPEC_TIMEOUT:-900}"
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$SPEC_TIMEOUT" \
        python -m pytest -q tests/test_speculative.py; then
        echo "CI: FAIL (speculative tests)"
        exit 1
    fi
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$SPEC_TIMEOUT" \
        python -c "from benchmarks import spec_decode; spec_decode.run(n_requests=8, train_steps=8, ks=(2,))"; then
        echo "CI: FAIL (spec_decode bench-smoke)"
        exit 1
    fi
    echo "CI: spec-smoke OK"
fi

if [ "$MESH_SMOKE" -eq 1 ]; then
    echo "CI: mesh-smoke shard (8-device CPU host platform)"
    MESH_TIMEOUT="${CI_MESH_TIMEOUT:-900}"
    # the tests spawn their own 8-device subprocesses; the env var also
    # covers anything collected in-process
    if ! XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$MESH_TIMEOUT" \
        python -m pytest -q tests/test_serving_mesh.py; then
        echo "CI: FAIL (sharded-serving tests)"
        exit 1
    fi
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$MESH_TIMEOUT" \
        python -m benchmarks.serve_continuous --mesh; then
        echo "CI: FAIL (serve_continuous --mesh)"
        exit 1
    fi
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$MESH_TIMEOUT" \
        python -m benchmarks.width_morph --mesh; then
        echo "CI: FAIL (width_morph --mesh)"
        exit 1
    fi
    echo "CI: mesh-smoke OK"
fi

if [ "$BENCH_SMOKE" -eq 1 ]; then
    echo "CI: bench-smoke stage (tiny configs)"
    BENCH_TIMEOUT="${CI_BENCH_TIMEOUT:-900}"
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$BENCH_TIMEOUT" \
        python -c "from benchmarks import width_morph; width_morph.run(train_steps=1)"; then
        echo "CI: FAIL (width_morph bench-smoke)"
        exit 1
    fi
    if ! PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$BENCH_TIMEOUT" \
        python -c "from benchmarks import serve_continuous; serve_continuous.run(n_requests=6)"; then
        echo "CI: FAIL (serve_continuous bench-smoke)"
        exit 1
    fi
    echo "CI: bench-smoke OK"
fi

out=$(PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" timeout "$TIMEOUT" \
      python -m pytest -q 2>&1)
status=$?
echo "$out" | tail -25

if [ $status -eq 124 ]; then
    echo "CI: TIMEOUT after ${TIMEOUT}s"
    exit 124
fi

summary=$(echo "$out" | grep -E '[0-9]+ (passed|failed|error)' | tail -1)
passed=$(echo "$summary" | grep -oE '[0-9]+ passed' | grep -oE '[0-9]+' || echo 0)
failed=$(echo "$summary" | grep -oE '[0-9]+ failed' | grep -oE '[0-9]+' || echo 0)
errors=$(echo "$summary" | grep -oE '[0-9]+ error' | grep -oE '[0-9]+' || echo 0)
passed=${passed:-0}; failed=${failed:-0}; errors=${errors:-0}

echo ""
echo "CI: passed=$passed failed=$failed errors=$errors"
echo "CI: delta vs seed baseline ($SEED_PASSED passed / $SEED_FAILED failed / $SEED_ERRORS collection error):"
echo "CI:   passed $((passed - SEED_PASSED)) | failed $((failed - SEED_FAILED)) | errors $((errors - SEED_ERRORS))"

if [ "$failed" -gt 0 ] || [ "$errors" -gt 0 ]; then
    echo "CI: FAIL (failures or errors present)"
    exit 1
fi
if [ "$passed" -lt "$SEED_PASSED" ]; then
    echo "CI: FAIL (fewer passes than seed baseline)"
    exit 1
fi
echo "CI: OK"
