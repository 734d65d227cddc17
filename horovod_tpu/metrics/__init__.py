"""``hvd.metrics`` — unified runtime telemetry, cross-rank aggregation
and straggler health.

One queryable surface over what used to be five ad-hoc telemetry
pockets: the native timeline's op brackets, the profiler's private
data-wait stats, checkpoint/autotune free-text logs, and per-rank
elastic events.  Layers:

* :mod:`.registry` — thread-safe Counters / Gauges / fixed-bucket
  Histograms; every subsystem records here
  (``hvd.metrics.registry()``).
* :mod:`.aggregate` — ``step_end()`` per training step; on the
  ``HVD_TPU_METRICS_SYNC_STEPS`` cadence, allgathers compact per-rank
  snapshots over the existing collective path so every rank (rank 0
  included) holds a fleet view.  Off the hot path by default (cadence
  0).
* :mod:`.health` — straggler detection over the aggregated step-time /
  data-wait distributions: warnings, timeline markers, and a
  ``blacklist_hint()`` the elastic driver can consume.
* :mod:`.exporters` — Prometheus text-format at ``/metrics`` (served
  from the rendezvous HTTP scaffold; auto-started by ``init()`` when
  ``HVD_TPU_METRICS_PORT`` is set) and a rotating JSONL sink.
* :mod:`.attribution` — the performance observatory's interpretation
  layer: every ``step_end`` decomposes the step's wall time into
  compute / exposed comm / hidden comm / input / checkpoint / host gap
  (``hvd_step_attribution_seconds{component}``) and grades live MFU
  (``set_step_flops`` → ``hvd_mfu_ratio`` vs ``HVD_TPU_PEAK_TFLOPS``).
* :mod:`.baseline` — EWMA/CUSUM drift detection over step time and
  component shares; a sustained regression emits a ``perf.drift``
  flight event and a suspect-naming regression report
  (``debug/regression.py``).  See ``docs/observability.md``.

Instrumented out of the box: eager collectives (ops/bytes/latency per
kind), the negotiated device plane (fusion batch size, response-
signature cache hit rate, staged bytes), the native controller (op
completions, last fused-names count), the input pipeline (data-wait
spans, stall warnings), the checkpoint engine (save/restore durations
and bytes), the autotuner (samples, applied parameters), and the
elastic layer (commits, restores, syncs, resets; driver-side rounds,
failures, blacklists).

See ``docs/metrics.md`` for the schema and a scrape example.
"""

from .registry import (
    Counter, Gauge, Histogram, MetricsRegistry,
    DEFAULT_BYTE_BUCKETS, DEFAULT_TIME_BUCKETS,
    enabled, registry, set_enabled,
)
from .aggregate import (
    Aggregator, aggregator, fleet_digest, fleet_snapshot, step_end,
    sync,
)
from .digest import (
    QuantileSketch, digest_mfu, digest_shares, digest_step_quantiles,
    merge_all, merge_digests, snapshot_digest,
)
from .health import (
    RankHealth, StragglerDetector, blacklist_hint, detector,
    straggler_report,
)
from .exporters import (
    JsonlSink, MetricsServer, render_prometheus, serve, stop_serving,
)
# NB: the engine accessor `attribution()` is deliberately NOT
# re-exported here — binding it onto the package would shadow the
# `metrics.attribution` SUBMODULE (`import horovod_tpu.metrics.
# attribution as am` would silently hand back the function).  Reach the
# accessor via the submodule: `from horovod_tpu.metrics.attribution
# import attribution`.
from .attribution import (
    COMPONENTS, WALL_COMPONENTS, StepAttribution, compute_span,
    last_attribution, note_pipeline_bubble, peak_flops, set_step_flops,
)
from .baseline import (
    DriftDetector, DriftEvent, drift_detector, last_drift_event,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "DEFAULT_BYTE_BUCKETS", "DEFAULT_TIME_BUCKETS",
    "enabled", "registry", "set_enabled",
    "Aggregator", "aggregator", "fleet_digest", "fleet_snapshot",
    "step_end", "sync",
    "QuantileSketch", "digest_mfu", "digest_shares",
    "digest_step_quantiles", "merge_all", "merge_digests",
    "snapshot_digest",
    "RankHealth", "StragglerDetector", "blacklist_hint", "detector",
    "straggler_report",
    "JsonlSink", "MetricsServer", "render_prometheus", "serve",
    "stop_serving",
    "COMPONENTS", "WALL_COMPONENTS", "StepAttribution", "compute_span",
    "last_attribution", "note_pipeline_bubble", "peak_flops",
    "set_step_flops",
    "DriftDetector", "DriftEvent", "drift_detector", "last_drift_event",
]
