"""Per-step time attribution + live MFU — the interpretation layer.

The stack *emits* ~70 metric families across nine subsystems; this
module *interprets* them per training step.  ``hvd.metrics.step_end()``
closes a :class:`StepRecord` that decomposes the step's wall time into
where it went:

* ``input`` — blocking input-pipeline wait (``hvd_data_wait_*``, the
  spans ``utils/profiler.data_wait`` and the prefetch consumer record).
* ``comm_exposed`` — wire time the step actually *paid*: synchronous
  eager collectives (``hvd_collective_latency_seconds``) plus the
  overlap queue's measured submit+blocked seconds.  Overlap-managed
  wire time is counted ONCE, via the queue's direct measurement: its
  sync-fallback ops also land in the latency histogram, so exactly
  that share (``hvd_overlap_fallback_latency_seconds_total``, priced
  at the submit site) is subtracted from the histogram delta — the
  native/device async submits never enter the histogram and genuine
  non-overlap latency is never erased.
* ``comm_hidden`` — wire time the backward-overlap scheduler hid
  behind compute (the union-minus-exposed residue of
  ``EagerBucketQueue.finish``, the same measurement behind
  ``hvd_overlap_comm_hidden_ratio``).  Informational: hidden comm is
  *not* part of the wall-time decomposition (it overlapped compute).
* ``checkpoint`` — blocking save/restore/commit seconds
  (``hvd_checkpoint_blocking_seconds_total`` — the async committer's
  background flushes are excluded at the source,
  ``checkpoint/engine.background_io``).
* ``compute`` — the device-step span when the loop brackets it with
  :func:`compute_span` (or reports it via :func:`note_compute`);
  otherwise the residual after the measured components.
* ``host`` — the unattributed host gap: wall time none of the above
  explains.  Non-zero only when compute is *measured* — with residual
  compute the gap is indistinguishable from compute by construction.

Exported as ``hvd_step_attribution_seconds{component}`` (last step)
and ``hvd_step_attribution_seconds_total{component}`` (cumulative),
plus an optional per-step JSONL trail (``HVD_TPU_ATTRIBUTION_JSONL``).

**Live MFU**: :func:`set_step_flops` declares the model FLOPs one step
executes per chip (helpers: ``models/resnet.train_flops_per_image``,
``models/bert.train_flops_per_seq``,
``models/transformer.train_flops_per_seq``); every ``step_end`` then grades
``hvd_mfu_ratio = flops / (step_time * peak)`` against
:func:`peak_flops` — ``HVD_TPU_PEAK_TFLOPS`` when set, else the detected
chip's spec-sheet peak (``PEAK_FLOPS_BY_KIND``, keyed by exact
``device_kind``).

Budget: one ``close_step`` is ~a dozen cached-child reads and float
arithmetic.  Disable with ``HVD_TPU_ATTRIBUTION=0`` or :func:`set_enabled`.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional

from ..core import config as _config
from .registry import registry as _registry

# The decomposition components, in the order reports print them.
# "comm_hidden" is informational (overlapped wire time, not wall time);
# the others partition the step's wall clock.  WALL_COMPONENTS is
# the single home — the drift detector (baseline.py) and the straggler
# cause attribution (health.py) import it, so a future component is
# considered everywhere or nowhere.  "pipeline_bubble" is the schedule
# fill/drain idle share of a pipeline-parallel step (reported by
# parallel/pipeline.note_bubble via hvd_pipeline_bubble_seconds_total);
# it is carved OUT of the measured compute span — the device is live
# but idling, and a bubble that grows with a geometry change should
# drift as its own component, not hide inside compute.
COMPONENTS = ("compute", "comm_exposed", "comm_hidden", "input",
              "checkpoint", "pipeline_bubble", "host")
WALL_COMPONENTS = ("compute", "comm_exposed", "input", "checkpoint",
                   "pipeline_bubble", "host")

_enabled: Optional[bool] = None


def enabled() -> bool:
    global _enabled
    if _enabled is None:
        _enabled = _config.get_bool("ATTRIBUTION",
                                    _config.Config.attribution)
    return _enabled


def set_enabled(flag: Optional[bool]) -> None:
    """Toggle attribution (None = re-read the env knob)."""
    global _enabled
    _enabled = None if flag is None else bool(flag)


# ---------------------------------------------------------------------------
# chip peak resolution (HVD_TPU_PEAK_TFLOPS -> table by device_kind -> None)
# ---------------------------------------------------------------------------

# Per-chip peak bf16 FLOP/s keyed by the exact ``device_kind`` jax reports
# (the spellings of jax._src.mesh_utils) — the table ``hvd_mfu_ratio`` is
# graded against.  Source: Google Cloud TPU documentation,
# the "System architecture" page of each generation.  A kind that is not
# here has no peak: no substring guesses a neighbouring generation's.
PEAK_FLOPS_BY_KIND = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}

_peak: Optional[float] = None
_peak_known = False


def peak_flops() -> Optional[float]:
    """The FLOP/s ceiling ``hvd_mfu_ratio`` grades against.

    ``HVD_TPU_PEAK_TFLOPS`` (TFLOP/s) wins when set.  Otherwise the
    detected chip's entry in ``PEAK_FLOPS_BY_KIND``; None off-TPU and
    for a TPU kind the table does not list (MFU is then not computed).
    Cached after the first
    resolution — this runs on every ``close_step``, and an env read per
    step is measurable at the <1% budget; :func:`reset_peak_cache`
    re-reads the knob."""
    global _peak, _peak_known
    if _peak_known:
        return _peak
    tf = _config.get_float("PEAK_TFLOPS", _config.Config.peak_tflops)
    if tf > 0:
        _peak = tf * 1e12
    else:
        import jax
        d = jax.devices()[0]
        _peak = (PEAK_FLOPS_BY_KIND.get(d.device_kind)
                 if d.platform == "tpu" else None)
    _peak_known = True
    return _peak


def reset_peak_cache() -> None:
    global _peak, _peak_known
    _peak = None
    _peak_known = False


# ---------------------------------------------------------------------------
# the attribution engine
# ---------------------------------------------------------------------------

def _family_read(reg, name: str, histogram: bool = False):
    """(sum, resets-generation) of a family's children — read-only,
    never creates the family.  The generation lets close_step tell a
    mid-step counter reset (epoch-boundary reset_data_wait_stats, a
    registry reset) from a genuine zero delta.

    Reads the slots directly instead of the locked properties: this
    runs every step_end across six families, GIL-atomic attribute reads
    are safe for a monitoring consumer, and the child locks are pure
    overhead here."""
    total, gen = 0.0, 0
    for child in reg.children_of(name):
        total += child._sum if histogram else child._value
        gen += getattr(child, "_resets", 0)
    return total, gen


class StepAttribution:
    """Window-marked delta reader over the subsystem counters.

    One instance per process (:func:`attribution`); separate instances
    exist only in tests.  ``close_step`` is called by
    ``Aggregator.step_end`` with the step's wall time; everything else
    is bookkeeping for the cross-rank snapshot (windowed component sums
    ride the aggregation wire so stragglers are attributed *by
    component*, metrics/health.py)."""

    def __init__(self, reg=None):
        self._reg = reg or _registry()
        self._lock = threading.Lock()
        self._marks: Optional[Dict[str, float]] = None
        self._compute_total = 0.0          # compute_span accumulations
        self._flops_per_step = 0.0
        self._last: Optional[dict] = None
        # Windowed (since last advance_window) sums for the aggregation
        # snapshot; "steps"/"flops"/"wall" ride along so consumers can
        # form per-step means and MFU over the SAME step set.
        self._win: Dict[str, float] = {}
        self._win_steps = 0
        self._win_flops = 0.0
        self._win_wall = 0.0
        self._sink = None
        self._sink_failed = False
        self._gauges: Dict[str, object] = {}
        self._totals: Dict[str, object] = {}
        self._mfu_gauge = None
        self._flops_gauge = None

    # -- inputs ------------------------------------------------------------

    def set_step_flops(self, flops: float) -> None:
        """Declare the model FLOPs ONE training step executes on this
        chip (batch x per-element FLOPs).  Sticky until changed."""
        with self._lock:
            self._flops_per_step = max(0.0, float(flops))

    def note_compute(self, seconds: float) -> None:
        """Report measured device-compute seconds (the alternative to
        :func:`compute_span` for loops that already time the step)."""
        if seconds > 0:
            with self._lock:
                self._compute_total += float(seconds)

    def note_pipeline_bubble(self, seconds: float) -> None:
        """Credit measured pipeline-bubble seconds (schedule fill/drain
        idle inside the compute span) to the source counter the
        decomposition reads.  Callers: ``parallel/pipeline.note_bubble``
        with ``bubble_fraction(...) * span``."""
        if seconds > 0:
            self._reg.counter(
                "hvd_pipeline_bubble_seconds_total",
                "Pipeline-schedule bubble (fill/drain idle) seconds"
            ).inc(float(seconds))

    @contextlib.contextmanager
    def compute_span(self):
        """Bracket the device-blocking part of the step — the call that
        dispatches and waits on the training computation.  With the span
        present, ``compute`` is measured and ``host`` becomes a real
        unattributed gap instead of zero."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.note_compute(time.perf_counter() - t0)

    # -- source reads ------------------------------------------------------

    def _read_sources(self) -> Dict[str, float]:
        reg = self._reg
        with self._lock:
            compute = self._compute_total
        out, gen = {"compute": compute}, 0
        for key, fam, hist in (
                ("input", "hvd_data_wait_seconds_total", False),
                ("comm_lat", "hvd_collective_latency_seconds", True),
                ("ovl_exposed",
                 "hvd_overlap_comm_exposed_seconds_total", False),
                ("ovl_fallback",
                 "hvd_overlap_fallback_latency_seconds_total", False),
                ("ovl_hidden",
                 "hvd_overlap_comm_hidden_seconds_total", False),
                ("checkpoint",
                 "hvd_checkpoint_blocking_seconds_total", False),
                ("pipeline_bubble",
                 "hvd_pipeline_bubble_seconds_total", False)):
            out[key], g = _family_read(reg, fam, histogram=hist)
            gen += g
        out["_gen"] = gen
        return out

    # -- the per-step close ------------------------------------------------

    def close_step(self, step: int, dur_s: float,
                   sync_exports: bool = True) -> Optional[dict]:
        """Decompose one step of ``dur_s`` wall seconds; update gauges,
        window sums and the JSONL trail; return the record."""
        if dur_s is None or dur_s <= 0:
            return None
        cur = self._read_sources()
        with self._lock:
            marks, self._marks = self._marks, cur
        if marks is None:
            # First close: no window to diff yet — anchor and move on.
            return None
        if cur.get("_gen", 0) != marks.get("_gen", 0) or any(
                cur[k] < marks.get(k, 0.0) for k in cur if k != "_gen"):
            # A source counter was reset inside this step (epoch-
            # boundary reset_data_wait_stats(), a registry reset): the
            # window straddles the discontinuity and any decomposition
            # would misattribute the vanished seconds to compute — skip
            # this one record, freshly anchored, rather than lie.
            return None
        d = {k: max(cur[k] - marks.get(k, 0.0), 0.0)
             for k in cur if k != "_gen"}

        ovl_exposed = d["ovl_exposed"]
        # Overlap's sync-fallback submits land in the latency histogram
        # too; its native/device async submits do NOT.  Subtract exactly
        # the fallback share (measured at the submit site,
        # ops/collective.overlap_submit_scope) so overlap-managed wire
        # time counts once without erasing genuine non-overlap latency.
        comm_exposed = max(d["comm_lat"] - d["ovl_fallback"], 0.0) \
            + ovl_exposed
        comm_hidden = d["ovl_hidden"]
        input_s = d["input"]
        ckpt_s = d["checkpoint"]
        compute_meas = d["compute"]
        # The bubble is reported as a share of the pipeline span, which
        # lives INSIDE the compute span — split it out so schedule idle
        # and useful compute drift independently.  Clamp to the measured
        # compute when both are present (a bubble cannot exceed the span
        # it was carved from).
        bubble_s = d["pipeline_bubble"]
        if compute_meas > 0.0:
            bubble_s = min(bubble_s, compute_meas)

        attributed = input_s + ckpt_s + comm_exposed + bubble_s
        if compute_meas > 0.0:
            compute_s = compute_meas - bubble_s
            host_s = dur_s - attributed - compute_s
        else:
            compute_s = max(dur_s - attributed, 0.0)
            host_s = 0.0
        if host_s < 0.0 or attributed + compute_s > dur_s:
            # Over-attribution (e.g. a background thread's seconds
            # leaking into a blocking counter, or timer skew) — on the
            # measured-compute path host goes negative, on the residual
            # path compute clamps to 0 with the rest still exceeding
            # the step: either way, normalize the wall components onto
            # the step so shares stay sane.
            total = attributed + compute_s
            if total > 0:
                scale = dur_s / total
                input_s *= scale
                ckpt_s *= scale
                comm_exposed *= scale
                bubble_s *= scale
                compute_s *= scale
            host_s = 0.0

        comps = {"compute": compute_s, "comm_exposed": comm_exposed,
                 "comm_hidden": comm_hidden, "input": input_s,
                 "checkpoint": ckpt_s, "pipeline_bubble": bubble_s,
                 "host": host_s}
        shares = {k: (comps[k] / dur_s) for k in WALL_COMPONENTS}

        with self._lock:
            flops = self._flops_per_step
            self._win_steps += 1
            self._win_flops += flops
            self._win_wall += dur_s
            for k, v in comps.items():
                self._win[k] = self._win.get(k, 0.0) + v
        peak = peak_flops() if flops > 0 else None
        mfu = (flops / (dur_s * peak)) if peak else None

        record = {"step": int(step), "dur_s": dur_s,
                  "components": comps, "shares": shares,
                  "flops": flops, "mfu": mfu}
        with self._lock:
            self._last = record
        if sync_exports:
            self._export(record)
        return record

    def _export(self, record: dict) -> None:
        reg = self._reg
        if not self._gauges:
            for k in COMPONENTS:
                self._gauges[k] = reg.gauge(
                    "hvd_step_attribution_seconds",
                    "Last step's wall-time decomposition (comm_hidden "
                    "is informational overlapped wire time, not wall)",
                    component=k)
                self._totals[k] = reg.counter(
                    "hvd_step_attribution_seconds_total",
                    "Cumulative attributed seconds by component",
                    component=k)
            self._mfu_gauge = reg.gauge(
                "hvd_mfu_ratio",
                "Model FLOPs utilization of the last step "
                "(set_step_flops / peak_flops; see HVD_TPU_PEAK_TFLOPS)")
            self._flops_gauge = reg.gauge(
                "hvd_step_model_flops",
                "Declared model FLOPs per step (set_step_flops)")
        for k, v in record["components"].items():
            self._gauges[k].set(v)
            self._totals[k].inc(max(v, 0.0))
        if record["flops"] > 0:
            self._flops_gauge.set(record["flops"])
        if record["mfu"] is not None:
            self._mfu_gauge.set(record["mfu"])
        self._write_jsonl(record)

    def _write_jsonl(self, record: dict) -> None:
        # The path knob is read ONCE, at the first close (an env read
        # per step is measurable at the <1% budget); :meth:`reset`
        # clears the latch, so a knob set later takes effect at the
        # next engine reset.
        if self._sink is None and not self._sink_failed:
            path = _config.get_env("ATTRIBUTION_JSONL", "") or ""
            if not path:
                self._sink_failed = True
                return
            try:
                from .exporters import JsonlSink
                self._sink = JsonlSink(path)
            except Exception:  # noqa: BLE001 — telemetry never kills
                self._sink_failed = True
                return
        if self._sink is not None:
            try:
                self._sink.write(record)
            except Exception:  # noqa: BLE001
                self._sink_failed = True
                self._sink = None

    # -- read side / windows ----------------------------------------------

    def last_record(self) -> Optional[dict]:
        with self._lock:
            return dict(self._last) if self._last is not None else None

    def window_components(self) -> Dict[str, float]:
        """Component seconds accumulated since the last
        :meth:`advance_window` — the cross-rank snapshot payload."""
        with self._lock:
            out = dict(self._win)
            out["steps"] = float(self._win_steps)
            out["flops"] = self._win_flops
            out["wall"] = self._win_wall
            return out

    def window_shares(self) -> Optional[Dict[str, float]]:
        """Normalized wall-component shares of the CURRENT window —
        component seconds divided by the window's wall seconds, the
        multi-step view of a single record's ``shares``.  This is the
        structured signal the autotuner consumes (autotune.py): one
        sample window spans many steps, so the tuner wants the window
        mean, not whichever step happened to close last.  None before
        any record landed in the window."""
        with self._lock:
            wall = self._win_wall
            if wall <= 0.0:
                return None
            return {k: self._win.get(k, 0.0) / wall
                    for k in WALL_COMPONENTS}

    def advance_window(self) -> None:
        with self._lock:
            self._win = {}
            self._win_steps = 0
            self._win_flops = 0.0
            self._win_wall = 0.0

    def reanchor(self) -> None:
        """Re-anchor the delta marks at the counters' CURRENT values and
        open a fresh window — the elastic-reset hook: restore-time
        checkpoint/comm seconds spent *between* training runs must not
        be attributed to the first post-reset step."""
        cur = self._read_sources()
        with self._lock:
            self._marks = cur
            self._win = {}
            self._win_steps = 0
            self._win_flops = 0.0
            self._win_wall = 0.0

    def reset(self) -> None:
        with self._lock:
            self._marks = None
            self._compute_total = 0.0
            self._flops_per_step = 0.0
            self._last = None
            self._win = {}
            self._win_steps = 0
            self._win_flops = 0.0
            self._win_wall = 0.0
            # Re-read the JSONL knob at the next close: a path set (or
            # fixed) after the first step should not stay latched off.
            self._sink = None
            self._sink_failed = False


_attribution: Optional[StepAttribution] = None
_attribution_lock = threading.Lock()


def attribution() -> StepAttribution:
    """The process-global attribution engine."""
    global _attribution
    with _attribution_lock:
        if _attribution is None:
            _attribution = StepAttribution()
        return _attribution


# Module-level conveniences (the ``hvd.metrics`` surface).

def set_step_flops(flops: float) -> None:
    """``hvd.metrics.set_step_flops(batch * flops_per_element)`` — the
    live-MFU input.  Model helpers compute the per-element figure:
    ``models.resnet.train_flops_per_image``,
    ``models.bert.train_flops_per_seq``,
    ``models.transformer.train_flops_per_seq``."""
    attribution().set_step_flops(flops)


def compute_span():
    """``with hvd.metrics.compute_span(): loss = train_step(batch)`` —
    marks the device-blocking span so the ``host`` gap is measurable."""
    return attribution().compute_span()


def last_attribution() -> Optional[dict]:
    """The most recent step's attribution record (None before the
    second ``step_end``)."""
    return attribution().last_record()


def note_pipeline_bubble(seconds: float) -> None:
    """Credit measured pipeline-bubble seconds to the ``pipeline_bubble``
    wall component (see ``parallel/pipeline.note_bubble``, which computes
    ``bubble_fraction(n_stages, n_micro) * span``)."""
    attribution().note_pipeline_bubble(seconds)


def window_shares() -> Optional[dict]:
    """Normalized wall-component shares of the current attribution
    window (None before any record) — the autotuner's per-window
    signal."""
    return attribution().window_shares()
