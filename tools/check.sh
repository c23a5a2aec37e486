#!/usr/bin/env bash
# CI gate: framework lint + tier-1 verify (ROADMAP.md).
#
#   bash tools/check.sh            # full gate
#   bash tools/check.sh --lint     # lint only (fast, no jax import)
#   bash tools/check.sh --kernels  # kernel parity gate only (interpret-mode
#                                  # matrix over every Pallas kernel in ops/)
#   bash tools/check.sh --serving  # serving runtime test family only
#                                  # (continuous batcher, multi-model server,
#                                  # end-to-end concurrency acceptance)
#   bash tools/check.sh --pipeline # host input-pipeline test family only
#                                  # (DataPipeline determinism matrix,
#                                  # starvation metric, sharded readers)
#   bash tools/check.sh --artifacts # AOT artifact family end-to-end
#                                  # (export -> wipe cache dir -> warm_start
#                                  # -> 0 fresh compiles via telemetry,
#                                  # corruption matrix, trainer resume)
#   bash tools/check.sh --quant    # low-precision family (compressed
#                                  # gradient collectives + error feedback,
#                                  # quantized training state, fp8 serving,
#                                  # collective-bytes locks)
#   bash tools/check.sh --resilience # serving-resilience + chaos family
#                                  # (deadlines, circuit breaker, supervised
#                                  # workers, training + serving chaos
#                                  # matrix, failure-policy retries)
#   bash tools/check.sh --fleet    # fleet observability family (process-
#                                  # tagged streams, heartbeats + straggler
#                                  # monitor, /healthz + /metrics endpoint,
#                                  # merged multi-process reports)
#   bash tools/check.sh --elastic  # elastic fleet family (per-host-sharded
#                                  # checkpoints + manifest verify/assembly,
#                                  # host-loss shrink + epoch-boundary
#                                  # rejoin e2e, coordinator arithmetic,
#                                  # fleet chaos seams)
#   bash tools/check.sh --perf     # performance observability family
#                                  # (MFU/roofline accounting, step-time
#                                  # decomposition, PerfMonitor + triggered
#                                  # capture)
#   bash tools/check.sh --concurrency # concurrency audit family (static
#                                  # lock-discipline/lock-order auditor over
#                                  # the threaded runtime + runtime lock
#                                  # sanitizer e2e)
#   bash tools/check.sh --trace    # causal tracing family (trace-context
#                                  # propagation, serving chaos continuity,
#                                  # critical-path epsilon, /trace endpoint,
#                                  # trace_export Chrome-trace JSON)
#   bash tools/check.sh --postmortem # flight recorder family (terminal
#                                  # chaos-seam dump matrix, real-SIGSEGV
#                                  # faulthandler artifact, bundle verify
#                                  # tamper/truncate, recorder-armed
#                                  # 1-compile canary, fleet merge)
set -u -o pipefail
cd "$(dirname "$0")/.."

echo "== lint_framework: bigdl_tpu/ tools/ =="
python tools/lint_framework.py bigdl_tpu tools || exit 1

echo "== obs_report selftest (golden telemetry fixture) =="
python tools/obs_report.py --selftest || exit 1

echo "== concurrency audit selftest (fixtures + repo-clean + acyclic lock graph) =="
python bigdl_tpu/analysis/concurrency.py --selftest || exit 1

echo "== trace_export selftest (golden span fixture -> Chrome-trace JSON) =="
python tools/trace_export.py --selftest || exit 1

echo "== postmortem selftest (golden bundle: verify/triage/fleet/tamper) =="
python tools/postmortem.py --selftest || exit 1

if [ "${1:-}" = "--lint" ]; then
    exit 0
fi

if [ "${1:-}" = "--concurrency" ]; then
    echo "== concurrency audit family (CPU) =="
    python bigdl_tpu/analysis/concurrency.py bigdl_tpu || exit 1
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_concurrency_audit.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "--trace" ]; then
    echo "== causal tracing family (CPU) =="
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_trace.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "--postmortem" ]; then
    echo "== flight recorder / postmortem family (CPU) =="
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_blackbox.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "--perf" ]; then
    echo "== perf observability family (CPU) =="
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_perf.py tests/test_obs.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "--serving" ]; then
    echo "== serving test family (CPU) =="
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_serving.py tests/test_serving_e2e.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "--pipeline" ]; then
    echo "== input pipeline test family (CPU) =="
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_input_pipeline.py tests/test_files_dataset.py \
        tests/test_tfrecord.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "--artifacts" ]; then
    echo "== AOT artifact family (CPU) =="
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_artifacts.py tests/test_artifacts_e2e.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "--resilience" ]; then
    echo "== serving-resilience + chaos family (CPU) =="
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_serving_resilience.py tests/test_chaos_matrix.py \
        tests/test_resilience.py -q -m 'not slow' \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "--fleet" ]; then
    echo "== fleet observability family (CPU) =="
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_fleet.py tests/test_obs.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "--elastic" ]; then
    echo "== elastic fleet family (CPU) =="
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_elastic.py tests/test_fleet.py -q -m 'not slow' \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "--quant" ]; then
    echo "== low-precision family (CPU) =="
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_low_precision.py tests/test_quantized.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "--kernels" ]; then
    echo "== kernel parity gate (CPU interpret mode) =="
    exec env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_kernel_parity.py tests/test_fused_kernels.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

echo "== tier-1 verify =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
