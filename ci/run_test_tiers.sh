#!/usr/bin/env bash
# Tiered test pipeline (the reference's docker-compose/Buildkite matrix
# analog, docker-compose.test.yml + .buildkite/gen-pipeline.sh):
#
#   ci/run_test_tiers.sh fast     # tier 1: single-process unit tests
#   ci/run_test_tiers.sh matrix   # tier 2: multi-process integration
#   ci/run_test_tiers.sh slow     # tier 3: elastic recovery + soaks
#   ci/run_test_tiers.sh all      # everything, tier by tier
#
# Tiers run SEQUENTIALLY and each tier is one pytest invocation: the
# multi-process tests contend for cores and flake when two pytest
# processes overlap (tests/conftest.py enforces per-test timeouts).
#
# The partition is validated by tests/test_ci_tiers.py (the golden-test
# spirit of the reference's test/single/test_buildkite.py): every
# tests/test_*.py file must belong to exactly one tier, so a new test
# file can never silently fall out of CI.
set -euo pipefail
cd "$(dirname "$0")/.."

# Hang forensics: a wedged test run must leave stack traces, not a bare
# `timeout -k` kill.  PYTHONFAULTHANDLER makes fatal signals dump all
# threads; tests/conftest.py additionally arms
# faulthandler.dump_traceback_later just under each tier's budget
# (HVD_TPU_CI_HANG_DUMP_S, seconds; 0 disables) so a silently-stuck
# suite prints where every thread is before the watchdog kills it.
export PYTHONFAULTHANDLER=1

# Launcher-spawned autotune workers (tests/test_autotune.py writes and
# execs autotune_worker.py scripts) can outlive an interrupted pytest:
# VERDICT found four alive hours after a run.  Reap any that survive
# this script, whatever the exit path.  (Pattern is user-wide: assumes
# one CI job per container/host, the normal CI topology.)
cleanup_orphans() {
  pkill -f 'python[0-9.]* .*autotune_worker\.py' 2>/dev/null || true
}
trap cleanup_orphans EXIT INT TERM

# Tier 1 — fast, single-process: model/op/unit layers (~5 min).
TIER_FAST=(
  test_basics.py test_bert.py
  # The flash kernels under the block-diffusion mask (ISSUE 39): forward and
  # the three gradients against the dense mask, the list of the live tiles
  # (exactly the tiles with an unmasked pair, n in 1, 2, 4), the tile rule,
  # the counter, the refusals, and the flagship's, BERT's and Laguna's
  # attention calls' pinned traces.
  test_block_diffusion_attention.py
  test_checkpoint_engine.py test_chips.py
  test_ci_tiers.py
  test_collectives.py test_data_pipeline.py test_debug_flight.py
  test_dispatch.py
  test_flash_attention.py
  # The flash kernels with a sliding window (ISSUE 33): forward and the
  # three gradients against the reference's mask, grids that walk the list
  # of the band's tiles only, the tile rule, the callers' window arguments.
  test_flash_attention_window.py
  # The flash backward as one pass over the score tiles (ISSUE 34): dQ, dK,
  # dV against the two kernels it replaced (bit for bit in fp32) and the
  # reference, the scratch's zeroing, the VMEM the call states, the length
  # it refuses, and the backward compiled for a described v5e.
  test_flash_backward_pass.py
  # The heads of a flash program (ISSUE 48): two heads of 64 (one of 128)
  # a program of the (B, S, H·D) rows against each head alone, bit for bit,
  # the two halves of a block not leaking, and no transpose of a heads'
  # array left in the families' lowered steps.
  test_flash_heads.py
  # What the layer checkpoint keeps, in the models (ROADMAP Sr; sections of
  # test_transformer.py and test_bert.py until PR 48): one forward kernel
  # call in the gradient's program, gradients the bare checkpoint's.
  test_flash_kept_in_models.py
  # The list the flash grids walk (ISSUE 43): the live tile pairs of the
  # three masks against the dense mask (counts, order, offsets, unequal
  # tiles), the grids and the counter of the cells' calls, and results bit
  # for bit those of a walk over every tile and of traced offsets.
  test_flash_walk.py
  test_fleet.py
  # The pause sentinel (ISSUE 36): garbage collections and the heartbeat in
  # the registry, the flight recorder, the log and a trace; arming under
  # init() / shutdown() and flight_disable.  With it the one file of
  # tests/benchmark_tests/ a tier holds (the others run under the driver's
  # `pytest tests/`): the reader that lays those marks against the device's
  # idle gaps, which is the sentinel's other half.
  test_host_pause.py
  benchmark_tests/test_benchmark_trace_host.py
  # Laguna's mix of windowed and full attention on the training path
  # (ISSUE 33): YaRN frequencies and the half-head rotation by hand, the
  # attention blocks, gate and dense MLP against the reference's equations,
  # the shares of heads and experts summing to the whole layer, leading
  # blocks, and what the new fields' defaults leave as it was.
  test_laguna_layers.py
  test_launch_flags.py
  test_metrics.py
  # Third mesh dimensions (ISSUE 16): MoE routing/capacity goldens, the
  # (dp, ep) workload vs its no-capacity oracle and the FLOPs-matched
  # dense baseline, 1F1B-vs-GPipe bit parity, the (2,2,2) -> (2,2,1)
  # 3-axis reshard drill, pipeline_bubble attribution, and MoE serving.
  test_moe_pipeline.py
  # Nemotron 3's hybrid on the training path (ISSUE 31): the chunked
  # state-space scan against the recurrence, the conv and gated norm, the
  # shares of heads and experts summing to the whole layer, the sigmoid
  # router and its held share against dense formulas, the balancer, every
  # dp layout against one device, the three refusals.
  test_nemotron_layers.py
  test_net_resilience.py
  # Fleet-scale observability plane (ISSUE 13): digest merge algebra
  # goldens, flat-vs-tree straggler verdict parity, host observer
  # exchange + crash tolerance, gateway timeline, new debug surfaces.
  test_observe_plane.py
  # OLMoE-1B-7B's pieces on the flagship's training path (ISSUE 26):
  # RoPE, QK-norm, the dropless MoE layer against a dense-mask formula,
  # router losses, every layout against one device.
  test_olmoe_layers.py
  # A pipeline of one stage is its stage (ISSUE 25): pipeline_apply at
  # pp = 1 against the tick loop and the parent's nested checkpoints,
  # and both flagship cells compiled for a described v5e (two forward
  # kernel instructions, no collective-permute, peak under 12.5 / 12.8 GiB).
  test_one_stage_pipeline.py
  test_optimizers.py
  test_overlap.py
  test_parallel.py
  # Perf-observatory drill: injected input slowdown must fire the drift
  # detector with data-component attribution; steady runs stay silent.
  test_perf_observatory.py
  test_probe_rendezvous.py
  test_quantization.py
  # The fused q/k/v projection as three products of the stored wqkv's
  # [q | k | v] reordering (ISSUE 51): both models' layers against the
  # head-by-head form of the stored weights, one member and two; no
  # activation of the traced layer interleaves q, k and v.
  test_qkv_slabs.py
  test_recovery.py
  # Flat-shard layout math goldens (ISSUE 14): 1-D + (dp, mp) nested
  # reshard arithmetic every durability tier leans on.
  test_reshard.py
  test_resnet.py test_response_cache.py
  # SDAR's block-diffusion training on the training path (ISSUE 39): the
  # doubled sequence against a plain block-causal forward block by block,
  # per-head QK-norm and the wrapped positions against the reference, the
  # eight expert shares summing to the whole layer, the published parameter
  # count, the host-side noise, the refusals.  With it the cell's own
  # benchmark tests (system against reference, the controls, the readers)
  # and its step compiled for a described v5e.
  test_sdar_layers.py
  # The held experts' rows summed into their tokens over the buffer's live
  # rows (ISSUE 40): the held path against the pair-space formulas, the
  # loop's edge cases, the three families' models, the traced step's shape.
  test_moe_token_sums.py
  # The Pallas kernel that adds those rows in VMEM (ISSUE 47), alone in the
  # interpreter: tile and chunk edges, a shared walk, exact weights.
  test_token_sum_kernel.py
  # q and k's position prologue as one Pallas pass (ISSUE 53): the kernel
  # alone in the interpreter against the jnp form, every form a
  # configuration gives it; and the step lowered for a TPU holding it under
  # hvd_attn_rope in all three passes, the jnp form elsewhere.
  test_qk_position_kernel.py
  test_qk_position_step.py
  benchmark_tests/test_benchmark_sdar.py
  benchmark_tests/test_benchmark_compile_v5e_sdar.py
  # LFM2's gated short convolution on the training path (ISSUE 41): the "C"
  # block against loops written out and the reference, its causality bit
  # for bit, the router's choice with and without the bias, the eight
  # expert shares summing to the whole layer, the published parameter
  # count, dQ's transposes in pieces at 32,768 queries, the touched paths
  # against the parent's formulas jaxpr for jaxpr.  With it the cell's own
  # benchmark tests (system against reference, the controls, the readers)
  # and its step compiled for a described v5e.
  test_lfm2_layers.py
  benchmark_tests/test_benchmark_lfm2.py
  benchmark_tests/test_benchmark_compile_v5e_lfm2.py
  # SmallThinker's layer on the training path (ISSUE 46): an expert block
  # whose router reads the stream as the block before it received it, ahead
  # of the attention, against equations written out and the reference; full
  # blocks without positions beside windowed ones that rotate; ReLU-gated
  # experts; the controls; the four expert shares summing to the whole
  # layer; the published parameter count; the refusals.  With it the cell's
  # own benchmark tests and its step compiled for a described v5e.
  test_smallthinker_layers.py
  benchmark_tests/test_benchmark_smallthinker.py
  benchmark_tests/test_benchmark_compile_v5e_smallthinker.py
  # Learned sparse attention on the training path (ISSUE 49): the exact
  # choice of a query's keys against a sort, ties and all; the flash
  # kernels of a selected call against reference_attention under the mask;
  # rotary positions from three streams; the indexer's leaves learning from
  # their loss alone; the refusals.  With it the Keye-VL cell's own
  # benchmark tests (system against reference, the controls, the eight
  # expert shares, the published parameter count, the traffic's positions,
  # the readers) and its step compiled for a described v5e.
  test_keye_vl_layers.py
  benchmark_tests/test_benchmark_keye_vl.py
  benchmark_tests/test_benchmark_compile_v5e_keye_vl.py
  test_timeline.py
  # Serving plane (ISSUE 15): admission-policy goldens, prefill/decode
  # parity vs the training-path logits, continuous-vs-static occupancy,
  # hot-swap bit-parity, overload shed, and the train→serve handoff
  # drill.
  test_serving.py
  # Production-scale serving (ISSUE 18): radix prefix cache refcount
  # lifecycle + bit-identity drills, chunked prefill, speculative
  # acceptance identity/exactness, policy aging + prefill-budget
  # goldens, and the KV-page migration codec + token-for-token handoff.
  test_serving_scale.py
  # Names inside the compiled training step (ISSUE 24): the five hvd_*
  # scopes and three flash-kernel names in both models' lowered step,
  # and bit-identical results with and without them.
  test_step_scopes.py
  # Request-scoped tracing + SLO error budgets (ISSUE 19): sampling
  # determinism, burn-rate goldens, burn-aware policy/autoscaler,
  # span coverage with tracing-on/off bit-identity, the migrated
  # stitched-trace drill, merge --trace, loop-liveness surface.
  # The mp rings of parallel/tensor_parallel.py (ISSUE 37): the four-chip
  # flagship step compiled for a described v5e:2x2 has a matmul between
  # every mp collective-permute's start and done, no synchronous gather or
  # scatter over mp, the dp all-reduce, and a peak under 14 GiB.
  test_described_tp_overlap_schedule.py
  test_tracing.py
  test_transformer.py
  # Closed-loop autotuning drill (ISSUE 12): injected comm regression →
  # drift → bounded re-tune → regression-gated rollback → resolution in
  # the report's tuning section, plus the tuning-memory store/warm-start
  # surface.
  test_tuning_loop.py
  test_utils_ops.py
  # Compiled-plane quantized + topology-scheduled collectives (ISSUE
  # 20): lowering purity (no host callbacks), N-rank sum-error analytic
  # bounds under shard_map, EF convergence parity vs fp32, stage-2/3
  # GSPMD parity quantized-vs-not + compression=none bit-identity,
  # checkpointed residual round-trip, hierarchical cross-byte goldens,
  # dispatch-table/pin schedule selection.
  test_xla_collectives.py
  # ZeRO-2/3 weight-update sharding (ISSUE 14): stage parity, the
  # forward-prefetch gather, the GSPMD NamedSharding plane, and the
  # world-4 -> world-2 / (dp, mp) mesh-change restore drill.
  test_zero_stages.py
)

# Tier 2 — multi-process matrix: native runtime, transports, device
# plane, framework front-ends, launcher (~20 min).
TIER_MATRIX=(
  test_adasum_native.py test_async_api.py test_autotune.py
  test_device_matrix.py
  test_eager_device_plane.py test_examples.py test_frontend_matrix.py
  test_fuzz_native.py test_hierarchical.py test_integrations.py
  test_mxnet_frontend.py test_native_matrix.py test_native_runtime.py
  test_runner.py test_shm_transport.py test_spark_estimators.py
  test_ssh_launch.py test_stall.py test_tf_custom_op.py
  test_tf_frontend.py test_torch_adasum.py test_torch_async_grouped.py
  test_torch_extras.py test_torch_frontend.py
)

# Tier 3 — elastic recovery + slow-marked soaks.
TIER_SLOW=(
  test_churn_soak.py
  test_elastic.py
  test_tf_elastic.py
)

# Per-tier stack-dump deadline: just under the tier's wall budget (the
# driver's tier-1 verify runs under `timeout -k 10 870`, so fast dumps
# at 850 s; the longer tiers get ceilings matched to their budgets).
hang_dump_s() {
  case "$1" in
    fast)   echo 850 ;;
    matrix) echo 1800 ;;
    *)      echo 3600 ;;
  esac
}

# Wall budget per tier (seconds) — the number the dump deadline shadows.
# The fast budget has been within 12% twice; print the margin in every
# run's log so drift toward the wall is visible per PR, not discovered
# by a timeout.
tier_budget_s() {
  case "$1" in
    fast)   echo 870 ;;
    matrix) echo 1860 ;;
    *)      echo 3660 ;;
  esac
}

# The budgets are sized for an idle machine; a loaded box stretches the
# whole suite uniformly, so the printed VERDICT scales by the same
# measured load factor the wall-clock tests use (tests/_loadprobe.py),
# disclosed once on stderr.  The raw idle-machine budget stays in the
# line so per-PR drift remains comparable across runs.
load_factor() {
  if [[ -z "${_LOAD_FACTOR:-}" ]]; then
    _LOAD_FACTOR=$(python - <<'EOF' 2>/dev/null || echo 1.0
import sys
sys.path.insert(0, "tests")
import _loadprobe
print(f"{_loadprobe.load_factor('ci_tiers'):.2f}")
EOF
)
    echo "ci_tiers: scaling tier budget verdicts by measured load" \
         "factor ${_LOAD_FACTOR}x" >&2
  fi
  echo "$_LOAD_FACTOR"
}

report_tier_time() {
  # Printed on success AND failure (EXIT path): wall seconds vs budget
  # with the consumed percentage, e.g. "tier fast: 812s / 870s (93%)".
  # The percentage is against the load-scaled budget; the idle budget
  # and the factor are both in the line so neither is hidden.
  local name="$1" start="$2" rc="$3"
  local wall=$(( SECONDS - start ))
  local budget; budget=$(tier_budget_s "$name")
  local factor; factor=$(load_factor)
  local scaled; scaled=$(awk -v b="$budget" -v f="$factor" \
                         'BEGIN { printf "%d", b * f }')
  local pct=$(( wall * 100 / scaled ))
  echo "=== tier ${name} wall time: ${wall}s / ${scaled}s budget" \
       "(${budget}s idle x ${factor} load, ${pct}% used, exit ${rc}) ==="
}

run_tier() {
  local name="$1"; shift
  local files=()
  for f in "$@"; do files+=("tests/$f"); done
  echo "=== tier: ${name} ($# files) ==="
  local start=$SECONDS rc=0
  HVD_TPU_CI_HANG_DUMP_S="${HVD_TPU_CI_HANG_DUMP_S:-$(hang_dump_s "$name")}" \
    python -m pytest "${files[@]}" -q || rc=$?
  report_tier_time "$name" "$start" "$rc"
  return $rc
}

case "${1:-all}" in
  fast)   run_tier fast "${TIER_FAST[@]}" ;;
  matrix) run_tier matrix "${TIER_MATRIX[@]}" ;;
  slow)   run_tier slow "${TIER_SLOW[@]}" ;;
  all)
    run_tier fast "${TIER_FAST[@]}"
    run_tier matrix "${TIER_MATRIX[@]}"
    run_tier slow "${TIER_SLOW[@]}"
    ;;
  list)
    # Machine-readable partition for tests/test_ci_tiers.py.
    printf '%s\n' "${TIER_FAST[@]}" | sed 's/^/fast /'
    printf '%s\n' "${TIER_MATRIX[@]}" | sed 's/^/matrix /'
    printf '%s\n' "${TIER_SLOW[@]}" | sed 's/^/slow /'
    ;;
  *)
    echo "usage: $0 {fast|matrix|slow|all|list}" >&2; exit 2 ;;
esac
