"""Environment-variable configuration knobs.

The reference concentrates all runtime tunables in ``HOROVOD_*`` env vars
(common.h:66-96, parsed in operations.cc:395-540 and utils/env_parser.cc).
We accept both the original ``HOROVOD_*`` names (drop-in compatibility) and
``HVD_TPU_*`` overrides; the TPU-specific name wins when both are set.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

# Knob names (reference: common.h:66-96).
FUSION_THRESHOLD = "FUSION_THRESHOLD"          # bytes
CYCLE_TIME = "CYCLE_TIME"                      # ms, background loop cadence
CACHE_CAPACITY = "CACHE_CAPACITY"              # response-cache entries
TIMELINE = "TIMELINE"                          # filename
TIMELINE_MARK_CYCLES = "TIMELINE_MARK_CYCLES"
AUTOTUNE = "AUTOTUNE"
AUTOTUNE_LOG = "AUTOTUNE_LOG"
AUTOTUNE_WARMUP_SAMPLES = "AUTOTUNE_WARMUP_SAMPLES"
AUTOTUNE_STEPS_PER_SAMPLE = "AUTOTUNE_STEPS_PER_SAMPLE"
AUTOTUNE_BAYES_OPT_MAX_SAMPLES = "AUTOTUNE_BAYES_OPT_MAX_SAMPLES"
AUTOTUNE_GAUSSIAN_PROCESS_NOISE = "AUTOTUNE_GAUSSIAN_PROCESS_NOISE"
# Closed-loop autotuning (the observatory feedback plane): persistent
# tuning memory keyed by (model fingerprint, world, topology) — the
# autotune analog of the response cache — plus drift-triggered bounded
# re-tune episodes with regression-gated rollback.  See
# docs/timeline_autotune.md ("Closing the loop").
AUTOTUNE_MEMORY = "AUTOTUNE_MEMORY"            # warm start + write-back
AUTOTUNE_MEMORY_DIR = "AUTOTUNE_MEMORY_DIR"    # local store (no gateway)
AUTOTUNE_RETUNE = "AUTOTUNE_RETUNE"            # drift-triggered re-tune
AUTOTUNE_RETUNE_WINDOWS = "AUTOTUNE_RETUNE_WINDOWS"  # episode budget
AUTOTUNE_ROLLBACK_PCT = "AUTOTUNE_ROLLBACK_PCT"  # regression gate (%)
LOG_LEVEL = "LOG_LEVEL"
LOG_HIDE_TIME = "LOG_HIDE_TIME"
STALL_CHECK_DISABLE = "STALL_CHECK_DISABLE"
STALL_CHECK_TIME_SECONDS = "STALL_CHECK_TIME_SECONDS"
STALL_SHUTDOWN_TIME_SECONDS = "STALL_SHUTDOWN_TIME_SECONDS"
HIERARCHICAL_ALLREDUCE = "HIERARCHICAL_ALLREDUCE"
HIERARCHICAL_ALLGATHER = "HIERARCHICAL_ALLGATHER"
# Topology-probed per-payload schedule dispatch (ops/dispatch.py): a
# short seeded probe at init() measures flat vs hierarchical per payload
# size and installs a per-(op kind, payload bucket) dispatch table the
# coordinator stamps into every response.  An EXPLICIT
# HVD_TPU_HIERARCHICAL_ALLREDUCE/_ALLGATHER pins that op kind to the
# given schedule for the whole payload range and bypasses its probe
# (the blind-global semantics those knobs had before the dispatch plane
# — kept as pins, deprecated as defaults).
SCHEDULE_PROBE = "SCHEDULE_PROBE"              # probe + dispatch on/off
SCHEDULE_PROBE_SEED = "SCHEDULE_PROBE_SEED"    # payload-content seed
SCHEDULE_PROBE_REPS = "SCHEDULE_PROBE_REPS"    # timed reps per arm
BATCH_D2D_MEMCOPIES = "BATCH_D2D_MEMCOPIES"
ELASTIC = "ELASTIC"
MESH_AXES = "MESH_AXES"                        # TPU-only: mesh axis spec
# Input pipeline (horovod_tpu/data/).
DATA_PREFETCH = "DATA_PREFETCH"                # background prefetch on/off
DATA_QUEUE_DEPTH = "DATA_QUEUE_DEPTH"          # prefetch queue depth
DATA_STALL_TIMEOUT_SECONDS = "DATA_STALL_TIMEOUT_SECONDS"  # 0 = warn only
# Quantized collective engine (horovod_tpu/ops/quantization.py).
COMPRESSION = "COMPRESSION"                    # none|fp16|bf16|int8|int4
QUANT_BLOCK = "QUANT_BLOCK"                    # elements per absmax scale
# Backward-overlap bucketed gradient scheduler (horovod_tpu/ops/overlap.py).
OVERLAP = "OVERLAP"                            # session default on/off
OVERLAP_BUCKET_BYTES = "OVERLAP_BUCKET_BYTES"  # bucket size; pins autotune
# GSPMD-native weight-update sharding (horovod_tpu/optimizers.py
# ZeroShardedOptimizer + ops/gspmd.py): 1 = optimizer state sharded
# (ZeRO-1), 2 = + gradient shards are the persistent objects (ZeRO-2),
# 3 = + parameters sharded with forward-prefetched per-bucket gathers
# (ZeRO-3).  ZERO_PREFETCH gates the per-bucket forward gather schedule
# (off = one monolithic gather before forward).
ZERO_STAGE = "ZERO_STAGE"                      # 1 | 2 | 3
ZERO_PREFETCH = "ZERO_PREFETCH"                # bucketed forward gathers
ZERO_QUANT_GATHER = "ZERO_QUANT_GATHER"        # quantized stage-3 gathers
# Metrics subsystem (horovod_tpu/metrics/).
METRICS_SYNC_STEPS = "METRICS_SYNC_STEPS"      # cross-rank cadence; 0 = off
METRICS_PORT = "METRICS_PORT"                  # Prometheus port; 0 = off
METRICS_STRAGGLER_FACTOR = "METRICS_STRAGGLER_FACTOR"
METRICS_STRAGGLER_MIN_SECONDS = "METRICS_STRAGGLER_MIN_SECONDS"
METRICS_STRAGGLER_PATIENCE = "METRICS_STRAGGLER_PATIENCE"
# Host-sharded (hierarchical) telemetry plane (metrics/digest.py +
# metrics/observer.py): intra-host digest merge at the per-host
# observer, one O(hosts) exchange per sync round, flat allgather kept
# as the small-world default.  TOPK bounds the per-host raw outlier
# evidence riding each digest.
METRICS_TREE = "METRICS_TREE"                  # hierarchical sync on/off
METRICS_TOPK = "METRICS_TOPK"                  # outlier evidence per host
METRICS_TREE_TIMEOUT_S = "METRICS_TREE_TIMEOUT_S"  # exchange deadline
METRICS_TREE_GRACE_S = "METRICS_TREE_GRACE_S"  # laggard-snapshot grace
METRICS_RETAIN_FILES = "METRICS_RETAIN_FILES"  # JSONL rotation retention
# Performance observatory (horovod_tpu/metrics/attribution.py +
# baseline.py): per-step time attribution, live MFU, drift detection.
ATTRIBUTION = "ATTRIBUTION"                    # per-step attribution on/off
ATTRIBUTION_JSONL = "ATTRIBUTION_JSONL"        # per-step JSONL sink path
PEAK_TFLOPS = "PEAK_TFLOPS"                    # calibrated chip peak; 0 = spec
PERF_DRIFT = "PERF_DRIFT"                      # drift detector on/off
PERF_DRIFT_WARMUP = "PERF_DRIFT_WARMUP"        # baseline steps before arming
PERF_DRIFT_THRESHOLD = "PERF_DRIFT_THRESHOLD"  # CUSUM trip level (sigmas)
PERF_DRIFT_MIN_PCT = "PERF_DRIFT_MIN_PCT"      # min % slowdown to fire
PERF_DRIFT_COOLDOWN = "PERF_DRIFT_COOLDOWN"    # steps muted after a fire
PERF_DRIFT_LOOKBACK_S = "PERF_DRIFT_LOOKBACK_S"  # event-correlation window
# Flight recorder / hang diagnosis (horovod_tpu/debug/).
FLIGHT_DISABLE = "FLIGHT_DISABLE"              # recorder off entirely
FLIGHT_CAPACITY = "FLIGHT_CAPACITY"            # ring-buffer events
FLIGHT_DIR = "FLIGHT_DIR"                      # dumps + hang reports
FLIGHT_PORT = "FLIGHT_PORT"                    # debug endpoint; 0 = ephemeral
FLIGHT_LAST_EVENTS = "FLIGHT_LAST_EVENTS"      # events quoted per rank
FLIGHT_ESCALATE = "FLIGHT_ESCALATE"            # stall -> hang report
# Peer-to-peer hot recovery (horovod_tpu/recovery/).
RECOVERY = "RECOVERY"                          # buddy replication + peer restore
RECOVERY_STRIDE = "RECOVERY_STRIDE"            # buddy ring shift; 0 = local size
ASYNC_COMMIT = "ASYNC_COMMIT"                  # background disk committer
CKPT_STREAMING = "CKPT_STREAMING"              # per-leaf streaming restore
# Deterministic fault injection (horovod_tpu/recovery/chaos.py).  The
# chaos layer is inert unless at least one CHAOS_* knob is set.
CHAOS_SEED = "CHAOS_SEED"                      # schedule seed
CHAOS_KILL_STEPS = "CHAOS_KILL_STEPS"          # "rank@step,..." kill schedule
CHAOS_COMMIT_CRASH = "CHAOS_COMMIT_CRASH"      # "<point>[@step]" crash point
CHAOS_SLOW_PEER_MS = "CHAOS_SLOW_PEER_MS"      # peer-serving latency injection
CHAOS_TORN_RANKS = "CHAOS_TORN_RANKS"          # corrupt these ranks' replicas
CHAOS_INPUT_DELAY_MS = "CHAOS_INPUT_DELAY_MS"  # input-pipeline slowdown drill
CHAOS_COMM_DELAY_MS = "CHAOS_COMM_DELAY_MS"    # comm-side slowdown drill
# Self-healing wire fabric (horovod_tpu/net/ + native/src/net.cc).  The
# native knobs are parsed in C (net.cc NetResilience/NetChaos); they are
# listed here so the knob table has one home and launch.py exports them.
NET_RESILIENCE = "NET_RESILIENCE"              # escalation ladder on/off
NET_PROBE_MS = "NET_PROBE_MS"                  # no-progress reconnect probe
NET_RECONNECT_S = "NET_RECONNECT_S"            # budget per reconnect
NET_OP_DEADLINE_S = "NET_OP_DEADLINE_S"        # per-transfer total budget
NET_MAX_RENEG = "NET_MAX_RENEG"                # ring re-formations cap
NET_RENEGOTIATE = "NET_RENEGOTIATE"            # rung 3 on/off
NET_HTTP_RETRIES = "NET_HTTP_RETRIES"          # attempts per HTTP request
NET_HTTP_BACKOFF_MS = "NET_HTTP_BACKOFF_MS"    # base of the jittered backoff
# Fleet service mode (horovod_tpu/fleet/): always-on multi-tenant job
# gateway multiplexing submitted jobs onto one device fleet.
FLEET_PORT = "FLEET_PORT"                      # gateway HTTP port
FLEET_ADDR = "FLEET_ADDR"                      # client default gateway addr
FLEET_SECRET = "FLEET_SECRET"                  # submission HMAC secret
FLEET_DIR = "FLEET_DIR"                        # durable job-queue directory
FLEET_TICK_S = "FLEET_TICK_S"                  # scheduler cadence
FLEET_QUOTA_SLOTS = "FLEET_QUOTA_SLOTS"        # per-tenant slots; 0 = unlimited
FLEET_PREEMPTION = "FLEET_PREEMPTION"          # priority preemption on/off
FLEET_PREEMPT_GRACE_S = "FLEET_PREEMPT_GRACE_S"  # commit wait before forcing
# Fleet timeline (fleet/observe.py): host observers push digests to the
# gateway's bounded ring store on a cadence; operators query per-job
# series over GET /fleet/observe/<job> without touching worker disks.
FLEET_OBSERVE_PUSH_S = "FLEET_OBSERVE_PUSH_S"  # push cadence; 0 = off
FLEET_OBSERVE_RETAIN = "FLEET_OBSERVE_RETAIN"  # ring samples per job
# Serving plane (horovod_tpu/serving/): continuous-batching inference
# services on the fleet fabric — decode-slot geometry, the bounded
# admission queue, checkpoint hot-swap polling, and queue/SLO-driven
# replica autoscaling.  See docs/serving.md.
SERVING_PORT = "SERVING_PORT"                  # request-plane HTTP port
SERVING_ADDR = "SERVING_ADDR"                  # client default replica addr
SERVING_SECRET = "SERVING_SECRET"              # request HMAC secret
SERVING_SLOTS = "SERVING_SLOTS"                # decode slots per replica
SERVING_PAGE_TOKENS = "SERVING_PAGE_TOKENS"    # tokens per KV page
SERVING_MAX_LEN = "SERVING_MAX_LEN"            # context cap; 0 = model seq_len
SERVING_MAX_NEW_TOKENS = "SERVING_MAX_NEW_TOKENS"  # default output cap
SERVING_QUEUE_CAP = "SERVING_QUEUE_CAP"        # admission queue bound
SERVING_SWAP_POLL_S = "SERVING_SWAP_POLL_S"    # checkpoint watch cadence
SERVING_AUTOSCALE = "SERVING_AUTOSCALE"        # replica autoscaler on/off
SERVING_TARGET_QUEUE = "SERVING_TARGET_QUEUE"  # queued reqs/replica target
SERVING_SLO_TTFT_S = "SERVING_SLO_TTFT_S"      # TTFT target; 0 = none
SERVING_SCALE_COOLDOWN_S = "SERVING_SCALE_COOLDOWN_S"  # resize hysteresis
# Production-scale serving (ISSUE 18): radix prefix cache, chunked
# prefill, speculative decoding, disaggregated prefill/decode.
SERVING_PREFIX_CACHE = "SERVING_PREFIX_CACHE"  # radix KV prefix cache on/off
SERVING_PREFILL_CHUNK = "SERVING_PREFILL_CHUNK"  # prefill tokens/iter; 0 = all
SERVING_AGING_S = "SERVING_AGING_S"            # page-reservation aging; 0 = off
SERVING_MIGRATE_BITS = "SERVING_MIGRATE_BITS"  # KV wire quant: 0 = fp32; 8 | 4
SPEC_K = "SPEC_K"                              # draft tokens/round; 0 = off
# Request-scoped tracing + per-tenant SLO error budgets (ISSUE 19):
# serving/tracing.py and serving/slo.py.  See docs/observability.md.
TRACE_SAMPLE = "TRACE_SAMPLE"                  # sampled request fraction [0,1]
TRACE_SEED = "TRACE_SEED"                      # trace-id derivation seed
SLO_TARGET = "SLO_TARGET"                      # attainment target [0.5,0.9999]
SLO_WINDOW_S = "SLO_WINDOW_S"                  # rolling budget window (s)
SLO_BURN_THRESHOLD = "SLO_BURN_THRESHOLD"      # burn rate that trips action
# Third mesh dimensions (parallel/moe.py, parallel/pipeline.py): MoE
# routing geometry and the pipeline schedule.  Single-sourced here —
# models read these through Config/the getters, never os.environ
# directly.  See docs/parallel.md for the knob table.
MOE_TOP_K = "MOE_TOP_K"                        # experts routed per token
MOE_CAPACITY_FACTOR = "MOE_CAPACITY_FACTOR"    # dispatch slots / even share
MOE_DISPATCH_BITS = "MOE_DISPATCH_BITS"        # 0 = fp32 wire; 8 | 4
MOE_DISPATCH_BLOCK = "MOE_DISPATCH_BLOCK"      # quant scale-block length
PP_SCHEDULE = "PP_SCHEDULE"                    # "gpipe" | "1f1b"
PP_MICROBATCHES = "PP_MICROBATCHES"            # microbatches per step
# Seeded wire chaos (both the native socket layer and the Python HTTP
# planes read these; inert unless set).
CHAOS_NET_SEED = "CHAOS_NET_SEED"              # wire-chaos schedule seed
CHAOS_NET_DROP_PCT = "CHAOS_NET_DROP_PCT"      # swallow a frame/request (%)
CHAOS_NET_RESET_PCT = "CHAOS_NET_RESET_PCT"    # connection reset (%)
CHAOS_NET_DELAY_MS = "CHAOS_NET_DELAY_MS"      # injected latency per frame
CHAOS_NET_TRUNCATE = "CHAOS_NET_TRUNCATE"      # truncate a frame/response (%)
CHAOS_NET_BLACKHOLE = "CHAOS_NET_BLACKHOLE"    # "a-b,..." dead rank pairs

_PREFIXES = ("HVD_TPU_", "HOROVOD_")


def get_env(name: str, default: Optional[str] = None) -> Optional[str]:
    """Read a knob, preferring HVD_TPU_* over HOROVOD_*."""
    for prefix in _PREFIXES:
        val = os.environ.get(prefix + name)
        if val is not None:
            return val
    return default


def get_bool(name: str, default: bool = False) -> bool:
    val = get_env(name)
    if val is None:
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


def get_int(name: str, default: int) -> int:
    val = get_env(name)
    if val is None:
        return default
    try:
        return int(val)
    except ValueError:
        return default


def get_float(name: str, default: float) -> float:
    val = get_env(name)
    if val is None:
        return default
    try:
        return float(val)
    except ValueError:
        return default


@dataclasses.dataclass
class Config:
    """Parsed runtime configuration.

    Defaults mirror the reference: 64 MB fusion buffer unless autotuning
    (operations.cc:448 sets 128 MB when tuning), 1 ms cycle time, response
    cache capacity 1024, stall warning at 60 s.
    """

    fusion_threshold_bytes: int = 64 * 1024 * 1024
    cycle_time_ms: float = 1.0
    cache_capacity: int = 1024
    timeline_filename: str = ""
    timeline_mark_cycles: bool = False
    autotune: bool = False
    autotune_log: str = ""
    # Reference autotune defaults (parameter_manager.h / launch.py flags).
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 0   # 0 = time-windowed sampling
    autotune_bayes_opt_max_samples: int = 20
    autotune_gaussian_process_noise: float = 0.8
    # Closed-loop autotuning: the tuning memory is on by default but
    # only engages once a model fingerprint is announced (TpuState or
    # autotune.announce_model); gateway jobs ride the fleet store, the
    # local dir is the gateway-less fallback.  A drift whose suspect is
    # a tunable subsystem triggers a bounded re-tune of
    # autotune_retune_windows sample windows; the re-tuned config rolls
    # back to the last-known-good entry when its score lands more than
    # autotune_rollback_pct percent below the pre-drift baseline.
    autotune_memory: bool = True
    autotune_memory_dir: str = "./autotune_memory"
    autotune_retune: bool = True
    autotune_retune_windows: int = 6
    autotune_rollback_pct: float = 5.0
    stall_check_disable: bool = False
    stall_warning_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    # Tri-state pins for the dispatch plane: None = the knob was not set
    # (the probe decides per payload), True/False = the operator
    # explicitly pinned the schedule — the probe is bypassed for that op
    # kind and the whole payload range uses the pinned choice.
    hierarchical_allreduce_pin: Optional[bool] = None
    hierarchical_allgather_pin: Optional[bool] = None
    # Topology probe: a few seeded payload sizes x {flat, hierarchical}
    # over the native collective path at init() (<1s at world <= 8; runs
    # only when the topology has a real hierarchy to choose, i.e.
    # 1 < local_size < world dividing evenly).
    schedule_probe: bool = True
    schedule_probe_seed: int = 0
    schedule_probe_reps: int = 2
    elastic: bool = False
    mesh_axes: str = ""
    # Input pipeline: prefetch on, double buffering, no hard stall
    # ceiling (the warning still fires at stall_warning_time_seconds).
    data_prefetch: bool = True
    data_queue_depth: int = 2
    data_stall_timeout_seconds: float = 0.0
    # Wire compression: the default format for the eager plane (every
    # allreduce/reducescatter without an explicit ``compression=``) and
    # the negotiated device plane's response-stream stamp.  Quantized
    # formats scale per ``quant_block`` elements (ops/quantization.py).
    compression: str = "none"
    quant_block: int = 256
    # Backward-overlap bucketed gradient scheduler: the session default
    # for optimizers called without an explicit ``overlap=`` argument
    # (bit-parity with the barrier schedule, so an env default is safe),
    # and the bucket size used when overlap is on.  Setting the bytes
    # knob explicitly PINS the autotuner's bucket-size dimension.
    overlap: bool = False
    overlap_bucket_bytes: int = 8 * 1024 * 1024
    # ZeRO weight-update sharding stage (ZeroShardedOptimizer default)
    # and the stage-3 forward-prefetch schedule (docs/zero.md).
    zero_stage: int = 1
    zero_prefetch: bool = True
    # Opt-in: put the stage-3 parameter gather itself on the quantized
    # wire (ops/overlap.gather_in_forward, ops/gspmd).  Off by default —
    # a gather has no error-feedback channel, so its loss (one bounded
    # qdq round trip per step; the sharded master stays fp32) lands on
    # the forward.  docs/compression.md prices the trade.
    zero_quant_gather: bool = False
    # Metrics: registry always records locally; cross-rank aggregation
    # and the scrape endpoint are opt-in (both default off).
    metrics_sync_steps: int = 0
    metrics_port: int = 0
    # Host-sharded telemetry plane: tree sync off by default (small
    # worlds lose nothing to the flat allgather; the launcher exports
    # the knob fleet-wide so every rank agrees).  topk bounds per-host
    # raw outlier evidence; retain_files prunes rotated JSONL sinks on
    # long-lived fleet workers.
    metrics_tree: bool = False
    metrics_topk: int = 4
    metrics_tree_timeout_s: float = 10.0
    metrics_tree_grace_s: float = 2.0
    metrics_retain_files: int = 3
    # Performance observatory: step_end() closes a per-step attribution
    # record (compute / exposed comm / hidden comm / input / checkpoint /
    # host gap) and feeds the EWMA/CUSUM drift detector; both default on
    # (the per-step cost is a handful of cached metric reads).
    # peak_tflops grades
    # hvd_mfu_ratio: 0 = the chip's spec-sheet peak by exact device kind
    # (metrics/attribution.PEAK_FLOPS_BY_KIND).
    attribution: bool = True
    attribution_jsonl: str = ""
    peak_tflops: float = 0.0
    perf_drift: bool = True
    perf_drift_warmup: int = 30
    perf_drift_threshold: float = 8.0
    perf_drift_min_pct: float = 10.0
    perf_drift_cooldown: int = 50
    perf_drift_lookback_s: float = 120.0
    # Flight recorder: always-on ring buffer; the stall →
    # hang-report escalation runs wherever the native controller does.
    flight_disable: bool = False
    flight_capacity: int = 4096
    flight_dir: str = "."
    flight_port: int = 0
    flight_last_events: int = 20
    flight_escalate: bool = True
    # Peer-to-peer hot recovery: buddy replication of committed ZeRO
    # shards + peer-first elastic restore (disk stays the correlated-
    # failure fallback).  Async commit overlaps the disk write with the
    # next training steps (single-controller only — the commit barrier
    # of a multi-controller save is a collective that cannot run on a
    # background thread).  Streaming restore reads one leaf at a time
    # so restore's transient memory is O(largest leaf), not O(state).
    recovery: bool = True
    recovery_stride: int = 0   # 0 = auto: the local world size
    async_commit: bool = False
    ckpt_streaming: bool = False
    # Self-healing wire fabric: graded failure escalation on every
    # cross-host channel (native TCP ring: framing + acks + reconnect-
    # and-resume + ring renegotiation; HTTP planes: per-attempt deadlines
    # with bounded jittered retries).  The native defaults live in
    # net.cc NetResilience() and MUST match these.
    # Fleet service mode: the job gateway's port, durable-queue home,
    # scheduler cadence, per-tenant slot quota (0 = unlimited), and the
    # checkpoint-mediated preemption knobs (preemption on/off + how long
    # the scheduler waits for the victim's next commit before shrinking
    # anyway).  See docs/fleet.md.
    fleet_port: int = 28642
    fleet_dir: str = "./fleet_state"
    fleet_tick_s: float = 0.5
    fleet_quota_slots: int = 0
    fleet_preemption: bool = True
    fleet_preempt_grace_s: float = 30.0
    fleet_observe_push_s: float = 0.0
    fleet_observe_retain: int = 512
    # Serving plane: decode-slot geometry (slots × pages × page tokens
    # is the replica's whole KV budget), the request plane's bounded
    # admission queue, the checkpoint-watch cadence of the hot-swap
    # path, and the queue-depth/SLO autoscaler (off by default — a
    # replica only resizes itself when asked to).  See docs/serving.md.
    serving_port: int = 28643
    serving_slots: int = 8
    serving_page_tokens: int = 16
    serving_max_len: int = 0          # 0 = the model's seq_len
    serving_max_new_tokens: int = 64
    serving_queue_cap: int = 64
    serving_swap_poll_s: float = 2.0
    serving_autoscale: bool = False
    serving_target_queue: float = 4.0
    serving_slo_ttft_s: float = 0.0
    serving_scale_cooldown_s: float = 10.0
    # Production-scale serving: the radix prefix cache rides every
    # admission by default (it only ever SAVES prefill work); chunked
    # prefill, reservation aging, and speculation are opt-in; the
    # KV-migration wire int8-quantizes by default (~3.9x smaller,
    # block-scaled — set 0 for the bit-exact fp32 wire).
    serving_prefix_cache: bool = True
    serving_prefill_chunk: int = 0    # prompt tokens/iteration; 0 = all
    serving_aging_s: float = 0.0      # page-reservation aging; 0 = off
    serving_migrate_bits: int = 8     # 0 = fp32 wire; 8 | 4
    spec_k: int = 0                   # draft tokens/round; 0 = off
    # Request-scoped tracing + SLO budgets: a 1% default sample rate
    # keeps the span stream within the flight recorder's <1% overhead
    # bar; the budget window and burn threshold follow SRE convention
    # (burn rate 1.0 = exactly spending the error budget).
    trace_sample: float = 0.01        # sampled request fraction [0, 1]
    trace_seed: int = 0               # trace-id derivation seed
    slo_target: float = 0.99          # per-tenant attainment target
    slo_window_s: float = 300.0       # rolling error-budget window (s)
    slo_burn_threshold: float = 1.0   # burn rate that trips scale/shed
    # MoE / pipeline geometry: experts routed per token, dispatch-
    # buffer headroom over the even share, the optional block-scaled
    # quantized dispatch wire (0 = fp32; 8/4 ride ops/quantization.py),
    # and the pipeline schedule + microbatch count.
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_dispatch_bits: int = 0
    moe_dispatch_block: int = 256
    pp_schedule: str = "gpipe"
    pp_microbatches: int = 1
    net_resilience: bool = True
    net_probe_ms: float = 10000.0
    net_reconnect_s: float = 10.0
    net_op_deadline_s: float = 60.0
    net_http_retries: int = 3        # attempts per HTTP request
    net_http_backoff_ms: float = 50.0

    @classmethod
    def from_env(cls) -> "Config":
        cfg = cls()
        cfg.fusion_threshold_bytes = get_int(
            FUSION_THRESHOLD, cfg.fusion_threshold_bytes)
        cfg.cycle_time_ms = get_float(CYCLE_TIME, cfg.cycle_time_ms)
        cfg.cache_capacity = get_int(CACHE_CAPACITY, cfg.cache_capacity)
        cfg.timeline_filename = get_env(TIMELINE, "") or ""
        cfg.timeline_mark_cycles = get_bool(TIMELINE_MARK_CYCLES)
        cfg.autotune = get_bool(AUTOTUNE)
        cfg.autotune_log = get_env(AUTOTUNE_LOG, "") or ""
        cfg.autotune_warmup_samples = get_int(
            AUTOTUNE_WARMUP_SAMPLES, cfg.autotune_warmup_samples)
        cfg.autotune_steps_per_sample = get_int(
            AUTOTUNE_STEPS_PER_SAMPLE, cfg.autotune_steps_per_sample)
        cfg.autotune_bayes_opt_max_samples = get_int(
            AUTOTUNE_BAYES_OPT_MAX_SAMPLES,
            cfg.autotune_bayes_opt_max_samples)
        cfg.autotune_gaussian_process_noise = get_float(
            AUTOTUNE_GAUSSIAN_PROCESS_NOISE,
            cfg.autotune_gaussian_process_noise)
        cfg.autotune_memory = get_bool(AUTOTUNE_MEMORY, cfg.autotune_memory)
        cfg.autotune_memory_dir = get_env(
            AUTOTUNE_MEMORY_DIR, cfg.autotune_memory_dir) \
            or cfg.autotune_memory_dir
        cfg.autotune_retune = get_bool(AUTOTUNE_RETUNE, cfg.autotune_retune)
        cfg.autotune_retune_windows = max(1, get_int(
            AUTOTUNE_RETUNE_WINDOWS, cfg.autotune_retune_windows))
        cfg.autotune_rollback_pct = max(0.0, get_float(
            AUTOTUNE_ROLLBACK_PCT, cfg.autotune_rollback_pct))
        cfg.stall_check_disable = get_bool(STALL_CHECK_DISABLE)
        cfg.stall_warning_time_seconds = get_float(
            STALL_CHECK_TIME_SECONDS, cfg.stall_warning_time_seconds)
        cfg.stall_shutdown_time_seconds = get_float(
            STALL_SHUTDOWN_TIME_SECONDS, cfg.stall_shutdown_time_seconds)
        cfg.hierarchical_allreduce = get_bool(HIERARCHICAL_ALLREDUCE)
        cfg.hierarchical_allgather = get_bool(HIERARCHICAL_ALLGATHER)
        # Presence (not value) of the legacy knobs is what pins: an
        # unset knob means "let the probe decide per payload".
        cfg.hierarchical_allreduce_pin = (
            None if get_env(HIERARCHICAL_ALLREDUCE) is None
            else cfg.hierarchical_allreduce)
        cfg.hierarchical_allgather_pin = (
            None if get_env(HIERARCHICAL_ALLGATHER) is None
            else cfg.hierarchical_allgather)
        cfg.schedule_probe = get_bool(SCHEDULE_PROBE, cfg.schedule_probe)
        cfg.schedule_probe_seed = get_int(SCHEDULE_PROBE_SEED,
                                          cfg.schedule_probe_seed)
        cfg.schedule_probe_reps = max(
            1, get_int(SCHEDULE_PROBE_REPS, cfg.schedule_probe_reps))
        cfg.elastic = get_bool(ELASTIC)
        cfg.mesh_axes = get_env(MESH_AXES, "") or ""
        cfg.data_prefetch = get_bool(DATA_PREFETCH, cfg.data_prefetch)
        cfg.data_queue_depth = max(
            1, get_int(DATA_QUEUE_DEPTH, cfg.data_queue_depth))
        cfg.data_stall_timeout_seconds = get_float(
            DATA_STALL_TIMEOUT_SECONDS, cfg.data_stall_timeout_seconds)
        comp = (get_env(COMPRESSION, cfg.compression) or "none")
        comp = comp.strip().lower()
        # A typo'd knob must not kill (or silently de-compress) a fleet:
        # normalize unknown names to none — by_name() does the same for
        # call-site strings — and keep the block even (int4 packs pairs).
        # (Name set mirrors ops/compression._BY_NAME; kept literal here
        # so config parsing never imports the jax-backed ops layer.)
        if comp not in ("none", "fp16", "bf16", "int8", "int4"):
            comp = "none"
        cfg.compression = comp
        cfg.quant_block = max(2, get_int(QUANT_BLOCK, cfg.quant_block))
        cfg.quant_block -= cfg.quant_block % 2
        cfg.overlap = get_bool(OVERLAP, cfg.overlap)
        # Floor of 1 KB: a zero/garbage bucket size would put every leaf
        # alone in a bucket — legal but never what anyone meant.
        cfg.overlap_bucket_bytes = max(
            1024, get_int(OVERLAP_BUCKET_BYTES, cfg.overlap_bucket_bytes))
        # Clamp to the defined stages: a typo'd knob must not silently
        # run unsharded (0) or invent a stage 4.
        cfg.zero_stage = min(3, max(1, get_int(ZERO_STAGE, cfg.zero_stage)))
        cfg.zero_prefetch = get_bool(ZERO_PREFETCH, cfg.zero_prefetch)
        cfg.zero_quant_gather = get_bool(ZERO_QUANT_GATHER,
                                         cfg.zero_quant_gather)
        cfg.metrics_sync_steps = max(
            0, get_int(METRICS_SYNC_STEPS, cfg.metrics_sync_steps))
        cfg.metrics_port = get_int(METRICS_PORT, cfg.metrics_port)
        cfg.metrics_tree = get_bool(METRICS_TREE, cfg.metrics_tree)
        # The other tree/retention knobs (METRICS_TOPK, the tree
        # timeouts, METRICS_RETAIN_FILES) are read at their use sites
        # with the dataclass defaults below — like the straggler knobs,
        # they are consumed by long-lived helpers, not by init(), so
        # parsing them into this snapshot would just be a second copy
        # of the clamp logic that nothing reads.
        cfg.attribution = get_bool(ATTRIBUTION, cfg.attribution)
        cfg.attribution_jsonl = get_env(
            ATTRIBUTION_JSONL, cfg.attribution_jsonl) or ""
        cfg.peak_tflops = max(0.0, get_float(PEAK_TFLOPS, cfg.peak_tflops))
        cfg.perf_drift = get_bool(PERF_DRIFT, cfg.perf_drift)
        cfg.perf_drift_warmup = max(
            1, get_int(PERF_DRIFT_WARMUP, cfg.perf_drift_warmup))
        cfg.perf_drift_threshold = max(0.5, get_float(
            PERF_DRIFT_THRESHOLD, cfg.perf_drift_threshold))
        cfg.perf_drift_min_pct = max(0.0, get_float(
            PERF_DRIFT_MIN_PCT, cfg.perf_drift_min_pct))
        cfg.perf_drift_cooldown = max(
            0, get_int(PERF_DRIFT_COOLDOWN, cfg.perf_drift_cooldown))
        cfg.perf_drift_lookback_s = max(1.0, get_float(
            PERF_DRIFT_LOOKBACK_S, cfg.perf_drift_lookback_s))
        cfg.flight_disable = get_bool(FLIGHT_DISABLE, cfg.flight_disable)
        cfg.flight_capacity = max(
            1, get_int(FLIGHT_CAPACITY, cfg.flight_capacity))
        cfg.flight_dir = get_env(FLIGHT_DIR, cfg.flight_dir) or "."
        cfg.flight_port = get_int(FLIGHT_PORT, cfg.flight_port)
        cfg.flight_last_events = max(
            1, get_int(FLIGHT_LAST_EVENTS, cfg.flight_last_events))
        cfg.flight_escalate = get_bool(FLIGHT_ESCALATE, cfg.flight_escalate)
        cfg.recovery = get_bool(RECOVERY, cfg.recovery)
        cfg.recovery_stride = max(
            0, get_int(RECOVERY_STRIDE, cfg.recovery_stride))
        cfg.async_commit = get_bool(ASYNC_COMMIT, cfg.async_commit)
        cfg.ckpt_streaming = get_bool(CKPT_STREAMING, cfg.ckpt_streaming)
        cfg.fleet_port = get_int(FLEET_PORT, cfg.fleet_port)
        cfg.fleet_dir = get_env(FLEET_DIR, cfg.fleet_dir) or cfg.fleet_dir
        cfg.fleet_tick_s = max(
            0.05, get_float(FLEET_TICK_S, cfg.fleet_tick_s))
        cfg.fleet_quota_slots = max(
            0, get_int(FLEET_QUOTA_SLOTS, cfg.fleet_quota_slots))
        cfg.fleet_preemption = get_bool(FLEET_PREEMPTION,
                                        cfg.fleet_preemption)
        cfg.fleet_preempt_grace_s = get_float(FLEET_PREEMPT_GRACE_S,
                                              cfg.fleet_preempt_grace_s)
        cfg.fleet_observe_push_s = max(0.0, get_float(
            FLEET_OBSERVE_PUSH_S, cfg.fleet_observe_push_s))
        cfg.fleet_observe_retain = max(1, get_int(
            FLEET_OBSERVE_RETAIN, cfg.fleet_observe_retain))
        cfg.serving_port = get_int(SERVING_PORT, cfg.serving_port)
        cfg.serving_slots = max(1, get_int(SERVING_SLOTS,
                                           cfg.serving_slots))
        cfg.serving_page_tokens = max(1, get_int(SERVING_PAGE_TOKENS,
                                                 cfg.serving_page_tokens))
        cfg.serving_max_len = max(0, get_int(SERVING_MAX_LEN,
                                             cfg.serving_max_len))
        cfg.serving_max_new_tokens = max(1, get_int(
            SERVING_MAX_NEW_TOKENS, cfg.serving_max_new_tokens))
        cfg.serving_queue_cap = max(1, get_int(SERVING_QUEUE_CAP,
                                               cfg.serving_queue_cap))
        cfg.serving_swap_poll_s = max(0.05, get_float(
            SERVING_SWAP_POLL_S, cfg.serving_swap_poll_s))
        cfg.serving_autoscale = get_bool(SERVING_AUTOSCALE,
                                         cfg.serving_autoscale)
        cfg.serving_target_queue = max(0.5, get_float(
            SERVING_TARGET_QUEUE, cfg.serving_target_queue))
        cfg.serving_slo_ttft_s = max(0.0, get_float(
            SERVING_SLO_TTFT_S, cfg.serving_slo_ttft_s))
        cfg.serving_scale_cooldown_s = max(0.0, get_float(
            SERVING_SCALE_COOLDOWN_S, cfg.serving_scale_cooldown_s))
        cfg.serving_prefix_cache = get_bool(SERVING_PREFIX_CACHE,
                                            cfg.serving_prefix_cache)
        cfg.serving_prefill_chunk = max(0, get_int(
            SERVING_PREFILL_CHUNK, cfg.serving_prefill_chunk))
        cfg.serving_aging_s = max(0.0, get_float(
            SERVING_AGING_S, cfg.serving_aging_s))
        mbits = get_int(SERVING_MIGRATE_BITS, cfg.serving_migrate_bits)
        cfg.serving_migrate_bits = mbits if mbits in (0, 4, 8) else 8
        cfg.spec_k = min(32, max(0, get_int(SPEC_K, cfg.spec_k)))
        cfg.trace_sample = min(1.0, max(0.0, get_float(
            TRACE_SAMPLE, cfg.trace_sample)))
        cfg.trace_seed = get_int(TRACE_SEED, cfg.trace_seed)
        cfg.slo_target = min(0.9999, max(0.5, get_float(
            SLO_TARGET, cfg.slo_target)))
        cfg.slo_window_s = max(1.0, get_float(
            SLO_WINDOW_S, cfg.slo_window_s))
        cfg.slo_burn_threshold = max(0.01, get_float(
            SLO_BURN_THRESHOLD, cfg.slo_burn_threshold))
        cfg.moe_top_k = max(1, get_int(MOE_TOP_K, cfg.moe_top_k))
        cfg.moe_capacity_factor = max(0.0, get_float(
            MOE_CAPACITY_FACTOR, cfg.moe_capacity_factor))
        bits = get_int(MOE_DISPATCH_BITS, cfg.moe_dispatch_bits)
        cfg.moe_dispatch_bits = bits if bits in (0, 4, 8) else 0
        cfg.moe_dispatch_block = max(1, get_int(
            MOE_DISPATCH_BLOCK, cfg.moe_dispatch_block))
        sched = (get_env(PP_SCHEDULE, cfg.pp_schedule) or
                 cfg.pp_schedule).strip().lower()
        cfg.pp_schedule = sched if sched in ("gpipe", "1f1b") \
            else cfg.pp_schedule
        cfg.pp_microbatches = max(1, get_int(
            PP_MICROBATCHES, cfg.pp_microbatches))
        cfg.net_resilience = get_bool(NET_RESILIENCE, cfg.net_resilience)
        cfg.net_probe_ms = get_float(NET_PROBE_MS, cfg.net_probe_ms)
        cfg.net_reconnect_s = get_float(NET_RECONNECT_S,
                                        cfg.net_reconnect_s)
        cfg.net_op_deadline_s = get_float(NET_OP_DEADLINE_S,
                                          cfg.net_op_deadline_s)
        cfg.net_http_retries = max(
            1, get_int(NET_HTTP_RETRIES, cfg.net_http_retries))
        cfg.net_http_backoff_ms = get_float(NET_HTTP_BACKOFF_MS,
                                            cfg.net_http_backoff_ms)
        if cfg.autotune and get_env(FUSION_THRESHOLD) is None:
            cfg.fusion_threshold_bytes = 128 * 1024 * 1024
        return cfg
