"""Performance observatory tests (ISSUE 10): per-step attribution,
live MFU, drift detection + regression diagnosis, step_end idempotency,
and exporter-vs-registration concurrency.

The acceptance drill lives here too: an injected input-pipeline
slowdown (``HVD_TPU_CHAOS_INPUT_DELAY_MS`` through the real data
iterator) must produce a drift event and a regression report
attributing the regression to the *data* component within a bounded
number of steps, while the identical steady run produces none.
"""

import json
import os
import threading
import time

import pytest

from horovod_tpu import metrics
from horovod_tpu.metrics.aggregate import Aggregator
from horovod_tpu.metrics.attribution import (
    StepAttribution, attribution, peak_flops, reset_peak_cache,
    set_enabled as set_attr_enabled,
)
from horovod_tpu.metrics.baseline import (
    DriftDetector, drift_detector, reset_drift_detector,
    set_drift_enabled,
)
from horovod_tpu.metrics.exporters import render_prometheus
from horovod_tpu.metrics.registry import MetricsRegistry
from horovod_tpu.debug import regression


@pytest.fixture(autouse=True)
def _fresh_observatory():
    """The attribution engine, drift detector and peak cache are
    process-global; every test starts (and leaves) them clean.

    The GLOBAL metrics registry is zeroed too: earlier tests (data
    pipeline, debug drills) leave large accumulated values in the
    source counters attribution window-diffs, and a window delta
    computed as ``(big + 0.05) - big`` loses low bits to float
    cancellation — the snapshot test's ``input >= 0.05`` assert then
    fails in hand-picked subset orders while passing in the full
    alphabetical run.  reset() keeps families/children (no bucket-
    choice conflicts) and bumps the resets generation, which the
    post-reset reanchor absorbs — so every test here sees exact,
    order-independent deltas."""
    metrics.registry().reset()
    attribution().reset()
    reset_drift_detector()
    reset_peak_cache()
    set_attr_enabled(None)
    set_drift_enabled(None)
    regression.reset()
    yield
    attribution().reset()
    reset_drift_detector()
    reset_peak_cache()
    set_attr_enabled(None)
    set_drift_enabled(None)
    regression.reset()


# ---------------------------------------------------------------------------
# attribution decomposition
# ---------------------------------------------------------------------------

def _sources(reg):
    """The subsystem counters close_step diffs, as writable children."""
    return {
        "input": reg.counter("hvd_data_wait_seconds_total", "t"),
        "lat": reg.histogram("hvd_collective_latency_seconds", "t",
                             buckets=(0.01, 1.0), kind="allreduce"),
        "exposed": reg.counter("hvd_overlap_comm_exposed_seconds_total",
                               "t"),
        "fallback": reg.counter(
            "hvd_overlap_fallback_latency_seconds_total", "t"),
        "hidden": reg.counter("hvd_overlap_comm_hidden_seconds_total",
                              "t"),
        "ckpt": reg.counter("hvd_checkpoint_blocking_seconds_total", "t"),
    }


def test_close_step_decomposes_wall_time_with_residual_compute():
    reg = MetricsRegistry()
    src = _sources(reg)
    eng = StepAttribution(reg)
    assert eng.close_step(0, 0.1) is None  # first close only anchors
    src["input"].inc(0.02)
    src["lat"].observe(0.01)
    src["ckpt"].inc(0.03)
    rec = eng.close_step(1, 0.1)
    comps = rec["components"]
    assert comps["input"] == pytest.approx(0.02)
    assert comps["comm_exposed"] == pytest.approx(0.01)
    assert comps["checkpoint"] == pytest.approx(0.03)
    # Compute is the residual; host gap indistinguishable → 0.
    assert comps["compute"] == pytest.approx(0.04)
    assert comps["host"] == 0.0
    assert sum(rec["shares"].values()) == pytest.approx(1.0)
    # Exported: last-step gauge + cumulative counter per component.
    flat = reg.scalars()
    assert flat["hvd_step_attribution_seconds{component=input}"] == \
        pytest.approx(0.02)
    assert flat["hvd_step_attribution_seconds_total{component=compute}"] \
        == pytest.approx(0.04)


def test_close_step_measured_compute_exposes_host_gap():
    reg = MetricsRegistry()
    src = _sources(reg)
    eng = StepAttribution(reg)
    eng.close_step(0, 0.1)
    src["input"].inc(0.01)
    eng.note_compute(0.06)
    rec = eng.close_step(1, 0.1)
    assert rec["components"]["compute"] == pytest.approx(0.06)
    # dur - input - compute: an unattributed host gap, now visible.
    assert rec["components"]["host"] == pytest.approx(0.03)


def test_overlap_exposed_seconds_not_double_counted():
    reg = MetricsRegistry()
    src = _sources(reg)
    eng = StepAttribution(reg)
    eng.close_step(0, 0.1)
    # The overlap queue's sync-fallback ops land in BOTH the latency
    # histogram and the exposed counter; the fallback counter (priced
    # at the submit site) says how much doubled, and the union counts
    # once.
    src["lat"].observe(0.02)
    src["exposed"].inc(0.02)
    src["fallback"].inc(0.02)
    src["hidden"].inc(0.05)
    rec = eng.close_step(1, 0.1)
    assert rec["components"]["comm_exposed"] == pytest.approx(0.02)
    # Hidden comm is informational — not part of the wall partition.
    assert rec["components"]["comm_hidden"] == pytest.approx(0.05)
    wall = sum(v for k, v in rec["components"].items()
               if k != "comm_hidden")
    assert wall == pytest.approx(0.1)


def test_native_overlap_does_not_erase_sync_latency():
    """On the native controller, overlap submits are async and never
    enter the latency histogram — subtracting the full exposed total
    would erase genuine synchronous-collective latency.  Only the
    measured fallback share is subtracted."""
    reg = MetricsRegistry()
    src = _sources(reg)
    eng = StepAttribution(reg)
    eng.close_step(0, 0.1)
    src["lat"].observe(0.010)     # a plain sync allreduce the step paid
    src["exposed"].inc(0.008)     # native overlap exposure (no fallback)
    rec = eng.close_step(1, 0.1)
    assert rec["components"]["comm_exposed"] == pytest.approx(0.018)


def test_close_step_skips_step_spanning_counter_reset():
    """A mid-step source reset (epoch-boundary reset_data_wait_stats,
    a registry reset) makes the window unusable — the record is
    skipped, freshly anchored, instead of misattributing the vanished
    seconds to compute."""
    reg = MetricsRegistry()
    src = _sources(reg)
    eng = StepAttribution(reg)
    eng.close_step(0, 0.1)
    src["input"].inc(0.02)
    assert eng.close_step(1, 0.1) is not None
    src["input"].inc(0.05)
    src["input"].reset()
    assert eng.close_step(2, 0.1) is None
    src["input"].inc(0.01)
    rec = eng.close_step(3, 0.1)
    assert rec["components"]["input"] == pytest.approx(0.01)


def test_over_attribution_normalizes_onto_step():
    reg = MetricsRegistry()
    src = _sources(reg)
    eng = StepAttribution(reg)
    eng.close_step(0, 0.1)
    # Timer skew: counters claim more than the step's wall time.
    src["input"].inc(0.09)
    src["ckpt"].inc(0.06)
    rec = eng.close_step(1, 0.1)
    wall = sum(v for k, v in rec["components"].items()
               if k != "comm_hidden")
    assert wall == pytest.approx(0.1)
    # Proportions preserved: input got 60% of the attributed time.
    assert rec["components"]["input"] == pytest.approx(0.06)
    assert rec["components"]["checkpoint"] == pytest.approx(0.04)


def test_window_components_accumulate_and_reanchor_drops_gap():
    reg = MetricsRegistry()
    src = _sources(reg)
    eng = StepAttribution(reg)
    eng.close_step(0, 0.1)
    src["input"].inc(0.02)
    eng.close_step(1, 0.1)
    src["input"].inc(0.04)
    eng.close_step(2, 0.1)
    win = eng.window_components()
    assert win["steps"] == 2
    assert win["input"] == pytest.approx(0.06)
    eng.advance_window()
    assert eng.window_components()["steps"] == 0
    # Restore work BETWEEN runs must not hit the next step's record.
    src["ckpt"].inc(5.0)
    eng.reanchor()
    src["input"].inc(0.01)
    rec = eng.close_step(3, 0.1)
    assert rec["components"]["checkpoint"] == 0.0
    assert rec["components"]["input"] == pytest.approx(0.01)


def test_mfu_graded_against_calibrated_peak(monkeypatch):
    monkeypatch.setenv("HVD_TPU_PEAK_TFLOPS", "100")
    reset_peak_cache()
    assert peak_flops() == pytest.approx(100e12)
    reg = MetricsRegistry()
    eng = StepAttribution(reg)
    eng.set_step_flops(5e12)
    eng.close_step(0, 0.1)
    rec = eng.close_step(1, 0.1)
    # 5 TFLOP in 0.1 s = 50 TFLOP/s on a 100 TFLOP/s peak.
    assert rec["mfu"] == pytest.approx(0.5)
    flat = reg.scalars()
    assert flat["hvd_mfu_ratio"] == pytest.approx(0.5)
    assert flat["hvd_step_model_flops"] == pytest.approx(5e12)


def test_mfu_absent_without_peak_or_flops():
    reset_peak_cache()  # CPU backend, no env override → no ceiling
    reg = MetricsRegistry()
    eng = StepAttribution(reg)
    eng.set_step_flops(5e12)
    eng.close_step(0, 0.1)
    assert eng.close_step(1, 0.1)["mfu"] is None


def test_peak_table_is_keyed_by_exact_device_kind(monkeypatch):
    """The chip reports ``TPU v5 lite``; a kind the table does not list has
    no peak — no substring grades it at a neighbouring generation's."""
    import types

    import jax
    from horovod_tpu.metrics.attribution import PEAK_FLOPS_BY_KIND

    def peak_on(kind, platform="tpu"):
        monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(
            platform=platform, device_kind=kind)])
        reset_peak_cache()
        return peak_flops()

    assert peak_on("TPU v5 lite") == PEAK_FLOPS_BY_KIND["TPU v5 lite"] \
        == pytest.approx(197e12)
    assert peak_on("TPU v5p") == pytest.approx(459e12)
    assert peak_on("TPU v5") is None          # was graded as a v5p
    assert peak_on("TPU v7x") is None
    assert peak_on("TPU v5 lite", platform="cpu") is None
    reset_peak_cache()


def test_models_flops_helpers_feed_set_step_flops():
    from horovod_tpu.models import bert, resnet, transformer
    r = resnet.train_flops_per_image(resnet.ResNetConfig(depth=50))
    assert r == pytest.approx(3 * 4.09e9)
    b = bert.train_flops_per_seq(bert.BertConfig())
    cfg = bert.BertConfig()
    d, ff, L, s, v = (cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.seq_len,
                      cfg.vocab_size)
    assert b == pytest.approx(3 * (s * L * (8 * d * d + 4 * d * ff)
                                   + L * 4 * s * s * d
                                   + s * (2 * d * d + 2 * d * v)))
    # Gathered head: fewer predicted positions → strictly fewer FLOPs.
    assert bert.train_flops_per_seq(cfg, n_pred=80) < b
    t = transformer.train_flops_per_seq(transformer.TransformerConfig())
    assert t > 0


def test_attribution_jsonl_trail(tmp_path, monkeypatch):
    path = tmp_path / "attr.jsonl"
    monkeypatch.setenv("HVD_TPU_ATTRIBUTION_JSONL", str(path))
    reg = MetricsRegistry()
    src = _sources(reg)
    eng = StepAttribution(reg)
    eng.close_step(0, 0.1)
    src["input"].inc(0.02)
    eng.close_step(1, 0.1)
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert lines[-1]["step"] == 1
    assert lines[-1]["components"]["input"] == pytest.approx(0.02)


# ---------------------------------------------------------------------------
# step_end idempotency (satellite: reentrancy/double-fire audit)
# ---------------------------------------------------------------------------

def test_step_end_idempotent_per_explicit_step_index():
    agg = Aggregator()
    agg.step_end(0.01, step=1)
    agg.step_end(0.01, step=2)
    # The elastic-commit hook double-fires the user loop's index.
    agg.step_end(0.01, step=2)
    agg.step_end(0.01, step=2)
    agg.step_end(0.01, step=3)
    snap = agg.local_snapshot()
    assert snap["step"] == 3
    assert snap["step_count"] == 3
    assert snap["step_time_sum"] == pytest.approx(0.03)


def test_step_end_duplicate_close_does_not_shrink_derived_interval():
    agg = Aggregator()
    agg.step_end(step=1)
    time.sleep(0.03)
    agg.step_end(step=2)
    agg.step_end(step=2)  # duplicate: must not re-mark the wall clock
    time.sleep(0.03)
    agg.step_end(step=3)
    snap = agg.local_snapshot()
    assert snap["step"] == 3
    assert snap["step_count"] == 2
    # Both derived intervals cover their full sleeps — a duplicate that
    # re-anchored the timestamp would have halved one of them.
    assert snap["step_time_sum"] >= 0.05


def test_step_end_lagging_duplicate_absorbed():
    """A hook closing an OLDER index after the loop moved on (the
    elastic-commit double-fire processed one iteration late) must not
    count a phantom near-zero step."""
    agg = Aggregator()
    agg.step_end(0.01, step=1)
    agg.step_end(0.01, step=2)
    agg.step_end(0.01, step=1)  # lagging duplicate
    snap = agg.local_snapshot()
    assert snap["step"] == 2
    assert snap["step_count"] == 2


def test_attribution_jsonl_knob_rereads_after_reset(tmp_path,
                                                    monkeypatch):
    """An unset path at the first step must not latch the sink off
    forever — reset() re-reads the knob."""
    reg = MetricsRegistry()
    src = _sources(reg)
    eng = StepAttribution(reg)
    eng.close_step(0, 0.1)
    src["input"].inc(0.01)
    eng.close_step(1, 0.1)          # no knob: sink latched off
    path = tmp_path / "attr.jsonl"
    monkeypatch.setenv("HVD_TPU_ATTRIBUTION_JSONL", str(path))
    eng.close_step(2, 0.1)
    assert not path.exists()        # still latched (by design, cached)
    eng.reset()
    eng.close_step(0, 0.1)
    src["input"].inc(0.01)
    eng.close_step(1, 0.1)
    assert path.exists()            # reset re-read the knob


def test_step_end_reset_clears_idempotency_latch():
    agg = Aggregator()
    agg.step_end(0.01, step=7)
    agg.reset()
    # Post-restart loops may replay the same index; after a reset it
    # must count again.
    agg.step_end(0.01, step=7)
    assert agg.local_snapshot()["step"] == 1


def test_module_level_step_end_passes_step_through():
    agg = metrics.aggregator()
    before = agg.local_snapshot()["step"]
    metrics.step_end(0.01, step=990001)
    metrics.step_end(0.01, step=990001)
    assert agg.local_snapshot()["step"] == before + 1


# ---------------------------------------------------------------------------
# exporter vs concurrent registration (satellite: registry mutation)
# ---------------------------------------------------------------------------

def test_export_scrape_races_instrument_creation():
    """Exporters iterate a collect() snapshot under the registry lock;
    before that, a scrape concurrent with child creation raised
    ``dictionary changed size during iteration``."""
    reg = MetricsRegistry()
    reg.counter("seed_total", "seed").inc()
    stop = threading.Event()
    errors = []

    def create():
        i = 0
        while not stop.is_set():
            reg.counter("churn_total", "c", worker=str(i % 97)).inc()
            reg.histogram("churn_seconds", "c", buckets=(0.1, 1.0),
                          worker=str(i % 89)).observe(0.05)
            i += 1

    def scrape():
        try:
            while not stop.is_set():
                render_prometheus(reg)
                reg.snapshot()
                reg.scalars()
        except Exception as e:  # noqa: BLE001 — the assertion target
            errors.append(e)

    threads = [threading.Thread(target=create) for _ in range(2)] + \
              [threading.Thread(target=scrape) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors
    # And the final exposition is well-formed for every family.
    text = render_prometheus(reg)
    assert "# TYPE churn_total counter" in text
    assert "# TYPE churn_seconds histogram" in text


def test_registry_reset_concurrent_with_creation():
    reg = MetricsRegistry()
    stop = threading.Event()
    errors = []

    def create():
        i = 0
        while not stop.is_set():
            reg.counter("r_total", "c", k=str(i % 53)).inc()
            i += 1

    def reset():
        try:
            while not stop.is_set():
                reg.reset()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=create),
               threading.Thread(target=reset)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors


# ---------------------------------------------------------------------------
# drift detection
# ---------------------------------------------------------------------------

def _steady_then_shift(det, n_steady, n_shift, base_s, shift_s,
                       base_shares=None, shift_shares=None):
    events = []
    step = 0
    for _ in range(n_steady):
        step += 1
        ev = det.update(step, base_s, shares=base_shares)
        if ev:
            events.append(ev)
    for _ in range(n_shift):
        step += 1
        ev = det.update(step, shift_s, shares=shift_shares)
        if ev:
            events.append(ev)
    return events


def test_drift_steady_run_never_fires():
    det = DriftDetector(warmup=20, threshold=8.0, min_pct=10.0,
                        cooldown=10, emit_report=False)
    # 2% sinusoid-ish jitter around 10 ms: realistic steady noise.
    for i in range(300):
        det.update(i, 0.010 * (1.0 + 0.02 * ((i % 7) - 3) / 3.0))
    assert det.events() == []


def test_drift_fires_on_sustained_slowdown_and_names_component():
    det = DriftDetector(warmup=20, threshold=8.0, min_pct=10.0,
                        cooldown=30, emit_report=False)
    base = {"compute": 0.8, "comm_exposed": 0.1, "input": 0.1,
            "checkpoint": 0.0, "host": 0.0}
    slow = {"compute": 0.4, "comm_exposed": 0.05, "input": 0.55,
            "checkpoint": 0.0, "host": 0.0}
    events = _steady_then_shift(det, 40, 25, 0.010, 0.020,
                                base_shares=base, shift_shares=slow)
    assert len(events) == 1  # re-baseline: one report per regression
    ev = events[0]
    assert ev.component == "input"
    # Fires FAST: the min_pct ratio guard clears as soon as the fast
    # EWMA moves 10% — well before it converges to the full 2x.
    assert ev.ratio >= 1.1
    assert ev.baseline_s == pytest.approx(0.010, rel=0.05)
    # Onset is where the CUSUM climb began — at/after the injection.
    assert 38 <= ev.onset_step <= 45


def test_drift_min_pct_guard_blocks_microsecond_jitter():
    det = DriftDetector(warmup=20, threshold=6.0, min_pct=10.0,
                        cooldown=10, emit_report=False)
    # Deterministic baseline then a sustained but tiny (+4%) shift:
    # variance collapse would trip a pure-CUSUM detector here.
    events = _steady_then_shift(det, 40, 60, 0.010, 0.0104)
    assert events == []


def test_drift_rebaselines_and_can_fire_again():
    det = DriftDetector(warmup=15, threshold=6.0, min_pct=10.0,
                        cooldown=5, emit_report=False)
    ev1 = _steady_then_shift(det, 30, 20, 0.010, 0.015)
    assert len(ev1) == 1
    # After the cooldown the 15 ms level IS the baseline; a second
    # regression on top of it is a new event.
    events = []
    for i in range(40):
        ev = det.update(100 + i, 0.015)
        if ev:
            events.append(ev)
    for i in range(20):
        ev = det.update(200 + i, 0.024)
        if ev:
            events.append(ev)
    assert len(events) == 1
    assert events[0].baseline_s == pytest.approx(0.015, rel=0.1)


def test_drift_emits_flight_event_and_counter(monkeypatch, tmp_path):
    monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path))
    from horovod_tpu.debug import flight
    det = DriftDetector(warmup=15, threshold=6.0, min_pct=10.0,
                        cooldown=5, emit_report=True)
    _steady_then_shift(det, 30, 20, 0.010, 0.020)
    kinds = [e["kind"] for e in flight.snapshot()]
    assert "perf.drift" in kinds
    ev = det.last_event()
    assert ev is not None and ev.report_path
    assert os.path.exists(ev.report_path)
    flat = metrics.registry().scalars()
    key = f"hvd_perf_drift_total{{component={ev.component}}}"
    assert flat.get(key, 0) >= 1


def test_drift_active_gauge_clears_with_zero_cooldown(monkeypatch,
                                                      tmp_path):
    """cooldown=0 has no countdown to clear the active gauge — a fire
    must not leave the dashboard showing a perpetual drift."""
    monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path))
    det = DriftDetector(warmup=15, threshold=6.0, min_pct=10.0,
                        cooldown=0, emit_report=False)
    _steady_then_shift(det, 30, 20, 0.010, 0.020)
    assert det.events()
    assert metrics.registry().scalars().get(
        "hvd_perf_drift_active", 0.0) == 0.0


def test_drift_reset_mid_cooldown_clears_active_gauge(monkeypatch,
                                                      tmp_path):
    """A reset during the cooldown (teardown, tooling) zeroes the
    countdown — the only other clearing path — so reset itself must
    clear the gauge."""
    monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path))
    det = DriftDetector(warmup=15, threshold=6.0, min_pct=10.0,
                        cooldown=500, emit_report=False)
    _steady_then_shift(det, 30, 10, 0.010, 0.020)
    assert det.events()
    assert metrics.registry().scalars().get(
        "hvd_perf_drift_active") == 1.0
    det.reset()
    assert metrics.registry().scalars().get(
        "hvd_perf_drift_active") == 0.0


# ---------------------------------------------------------------------------
# regression diagnosis
# ---------------------------------------------------------------------------

class _FakeDrift:
    def __init__(self, component, onset_mono, step=100):
        self.step = step
        self.onset_step = step - 3
        self.onset_wall = time.time()
        self.onset_mono = onset_mono
        self.ratio = 2.0
        self.component = component
        self.baseline_s = 0.01
        self.current_s = 0.02
        self.share_delta = 0.3

    def as_dict(self):
        return {"step": self.step, "component": self.component}


def test_regression_report_prefers_component_consistent_suspect():
    now = time.monotonic()
    events = [
        {"kind": "autotune.decision", "name": None, "t_mono": now - 5.0},
        {"kind": "data.chaos_delay", "name": "it", "t_mono": now - 2.0},
    ]
    rep = regression.build_regression_report(
        _FakeDrift("input", now), write=False, events=events)
    assert rep["suspect"]["subsystem"] == "data"
    # Same window, comm drift: the tuner outranks the data event.
    rep2 = regression.build_regression_report(
        _FakeDrift("comm_exposed", now), write=False, events=events)
    assert rep2["suspect"]["subsystem"] == "autotune"
    assert "autotune.decision" in rep2["verdict"]


def test_regression_report_ignores_events_after_onset_slack():
    now = time.monotonic()
    events = [
        {"kind": "fleet.preempt", "name": None, "t_mono": now + 30.0},
    ]
    rep = regression.build_regression_report(
        _FakeDrift("input", now), write=False, events=events)
    assert rep["suspect"] is None
    assert "no flight-recorded subsystem event" in rep["verdict"]


def test_classify_prefix_fallback_covers_unlisted_kinds():
    """Subsystems grow new event kinds; the namespace prefix keeps them
    in the causal window (exact entries still win; op-stream chatter
    and the diagnoser's own perf.* events stay out)."""
    assert regression._classify("checkpoint.extract.begin") == "checkpoint"
    assert regression._classify("recovery.restore.miss") == "recovery"
    assert regression._classify("elastic.commit") == "elastic_commit"
    assert regression._classify("perf.drift") is None
    assert regression._classify("collective.enqueue") is None


def test_regression_report_verdict_states_causal_direction():
    """A suspect inside the after-onset slack must not be described as
    'before onset'."""
    now = time.monotonic()
    rep = regression.build_regression_report(
        _FakeDrift("input", now), write=False,
        events=[{"kind": "data.chaos_delay", "name": None,
                 "t_mono": now + 0.8}])
    assert rep["suspect"]["vs_onset_s"] == pytest.approx(0.8)
    assert "after onset" in rep["verdict"]
    assert "before onset" not in rep["verdict"]
    rep2 = regression.build_regression_report(
        _FakeDrift("input", now), write=False,
        events=[{"kind": "data.chaos_delay", "name": None,
                 "t_mono": now - 2.0}])
    assert "before onset" in rep2["verdict"]


def test_regression_report_keeps_discrete_event_under_chatter_flood():
    """80 post-onset data.wait chatter events must not evict the
    pre-onset discrete causal event from the quoted context."""
    now = time.monotonic()
    events = [{"kind": "autotune.decision", "name": None,
               "t_mono": now - 3.0}]
    events += [{"kind": "data.wait", "name": None,
                "t_mono": now + 0.001 * i} for i in range(80)]
    rep = regression.build_regression_report(
        _FakeDrift("input", now), write=False, events=events)
    kinds = [e["kind"] for e in rep["events"]]
    assert "autotune.decision" in kinds
    assert kinds.count("data.wait") <= 20


def test_attribution_submodule_not_shadowed_by_package_export():
    """`import horovod_tpu.metrics.attribution as am` must bind the
    MODULE — re-exporting the accessor function onto the package would
    shadow it."""
    import horovod_tpu.metrics
    import horovod_tpu.metrics.attribution as am
    assert hasattr(am, "enabled") and callable(am.attribution)
    assert getattr(horovod_tpu.metrics, "attribution") is am


def test_regression_report_written_atomically(tmp_path, monkeypatch):
    monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path))
    now = time.monotonic()
    rep = regression.build_regression_report(
        _FakeDrift("checkpoint", now, step=42), write=True,
        events=[{"kind": "checkpoint.save.commit", "name": None,
                 "t_mono": now - 0.5}])
    path = tmp_path / "perf_regression_step42.json"
    assert path.exists()
    on_disk = json.loads(path.read_text())
    assert on_disk["component"] == "checkpoint"
    assert on_disk["suspect"]["subsystem"] == "checkpoint"
    assert regression.last_report()["path"] == rep["path"] == str(path)


# ---------------------------------------------------------------------------
# the acceptance drill: injected input slowdown → data-attributed drift
# ---------------------------------------------------------------------------

def _drill_loop(agg, iterator, n):
    # InlineIterator brackets its own next() in a data_wait span — the
    # exact production shape, no extra instrumentation here.
    step = agg.local_snapshot()["step"]
    it = iter(iterator)
    for _ in range(n):
        next(it)
        time.sleep(0.004)  # the "compute" half of the step
        step += 1
        agg.step_end(step=step)


def _drill_env(monkeypatch, tmp_path):
    monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("HVD_TPU_PERF_DRIFT_WARMUP", "10")
    monkeypatch.setenv("HVD_TPU_PERF_DRIFT_THRESHOLD", "6")
    monkeypatch.setenv("HVD_TPU_PERF_DRIFT_MIN_PCT", "50")
    monkeypatch.setenv("HVD_TPU_PERF_DRIFT_COOLDOWN", "100")
    reset_drift_detector()


def test_drift_drill_input_slowdown_attributed_to_data(
        monkeypatch, tmp_path):
    from horovod_tpu.data.prefetch import InlineIterator
    _drill_env(monkeypatch, tmp_path)
    agg = Aggregator()
    steady = InlineIterator(iter(range(10_000)))
    _drill_loop(agg, steady, 20)  # baseline: ~4 ms steps, no input wait
    assert drift_detector().events() == []

    # The injection: every batch now pays 30 ms in the input path.
    monkeypatch.setenv("HVD_TPU_CHAOS_INPUT_DELAY_MS", "30")
    slowed = InlineIterator(iter(range(10_000)))
    _drill_loop(agg, slowed, 25)

    events = drift_detector().events()
    assert len(events) == 1, "injected slowdown must fire exactly once"
    ev = events[0]
    assert ev.component == "input"
    assert ev.ratio > 1.5
    # Fired within the injected window — not tens of steps later.
    assert ev.step <= 20 + 25
    rep = regression.last_report()
    assert rep is not None
    assert rep["component"] == "input"
    # The chaos injection is flight-recorded at iterator construction;
    # the diagnoser names the data subsystem as the suspect.
    assert rep["suspect"]["subsystem"] == "data"
    assert rep["suspect"]["kind"] == "data.chaos_delay"
    assert os.path.exists(rep["path"])


def test_drift_drill_steady_run_is_silent(monkeypatch, tmp_path):
    from horovod_tpu.data.prefetch import InlineIterator
    _drill_env(monkeypatch, tmp_path)
    agg = Aggregator()
    it = InlineIterator(iter(range(10_000)))
    _drill_loop(agg, it, 45)  # same length as the injected drill
    assert drift_detector().events() == []
    assert regression.last_report() is None
    assert not list(tmp_path.glob("perf_regression_*.json"))


# ---------------------------------------------------------------------------
# aggregation: component sums ride the wire, stragglers attributed
# ---------------------------------------------------------------------------

def test_snapshot_carries_attribution_window(monkeypatch):
    agg = Aggregator()
    # The GLOBAL registry: only touch the counter the data plane owns —
    # re-registering the latency histogram here would conflict with the
    # collective plane's bucket choice when those tests ran first.
    wait = metrics.registry().counter("hvd_data_wait_seconds_total",
                                      "Input-wait seconds")
    attribution().reanchor()
    agg.step_end(0.1, step=1)  # anchor
    wait.inc(0.05)
    agg.step_end(0.1, step=2)
    snap = agg.local_snapshot()
    assert "attr" in snap
    assert snap["attr"]["steps"] >= 1
    assert snap["attr"]["input"] >= 0.05
    # The window's own wall sum — what fleet MFU divides flops by, so
    # anchor/skipped steps (timed but producing no record) can't bias
    # MFU low.
    assert snap["attr"]["wall"] >= 0.1


def test_elastic_run_reanchors_after_sync_restore_work():
    """The elastic run() loop re-anchors the attribution marks AFTER
    state.sync(): restore work done between runs (checkpoint reads,
    broadcasts) must never be charged to the first post-sync step."""
    from horovod_tpu.elastic import state as es

    ckpt = metrics.registry().counter(
        "hvd_checkpoint_blocking_seconds_total",
        "Save/restore wall seconds paid on the calling thread")

    class _S(es.State):
        def sync(self):
            ckpt.inc(5.0)  # "restore work" done between runs

        def save(self):
            pass

        def restore(self):
            pass

        def reset(self):
            pass

    eng = attribution()
    eng.reanchor()  # marks taken BEFORE the round (pre-sync values)

    @es.run
    def train(state):
        return "done"

    assert train(_S()) == "done"
    rec = eng.close_step(1, 0.1)
    assert rec is not None
    assert rec["components"]["checkpoint"] == pytest.approx(0.0)


def test_straggler_cause_uses_component_attribution():
    from horovod_tpu.metrics.health import StragglerDetector
    det = StragglerDetector(factor=1.5, min_seconds=0.001, patience=2)

    def entry(rank, mean, ckpt_mean):
        n = 10
        return {
            "rank": rank, "step_time_sum": mean * n, "step_count": n,
            "data_wait_sum": 0.0, "data_wait_count": n,
            "attr": {"steps": float(n), "compute": 0.01 * n,
                     "comm_exposed": 0.001 * n, "input": 0.001 * n,
                     "checkpoint": ckpt_mean * n, "host": 0.0},
        }

    per_rank = [entry(0, 0.012, 0.0), entry(1, 0.012, 0.0),
                entry(2, 0.012, 0.0), entry(3, 0.030, 0.018)]
    out = det.score_ranks(per_rank)
    flagged = [h for h in out if h.flagged]
    assert [h.rank for h in flagged] == [3]
    # Not just "slower": the checkpoint component explains the excess.
    assert flagged[0].cause == "checkpoint"


def test_straggler_cause_falls_back_without_attr():
    from horovod_tpu.metrics.health import StragglerDetector
    det = StragglerDetector(factor=1.5, min_seconds=0.001, patience=2)
    per_rank = [
        {"rank": 0, "step_time_sum": 0.1, "step_count": 10,
         "data_wait_sum": 0.0},
        {"rank": 1, "step_time_sum": 0.1, "step_count": 10,
         "data_wait_sum": 0.0},
        {"rank": 2, "step_time_sum": 0.3, "step_count": 10,
         "data_wait_sum": 0.18},
    ]
    out = det.score_ranks(per_rank)
    assert out[2].flagged and out[2].cause == "input"


def test_fleet_mfu_gauges_from_gathered_snapshots(monkeypatch):
    monkeypatch.setenv("HVD_TPU_PEAK_TFLOPS", "100")
    reset_peak_cache()
    reg = metrics.registry()
    gathered = [
        {"rank": 0, "step_time_sum": 1.0,
         "attr": {"steps": 10.0, "flops": 50e12}},
        {"rank": 1, "step_time_sum": 1.0,
         "attr": {"steps": 10.0, "flops": 30e12}},
    ]
    Aggregator._fleet_mfu_gauges(gathered, reg)
    flat = reg.scalars()
    assert flat["hvd_mfu_fleet_min"] == pytest.approx(0.3)
    assert flat["hvd_mfu_fleet_mean"] == pytest.approx(0.4)


# ---------------------------------------------------------------------------
# causal event stream completeness (satellite: flight events)
# ---------------------------------------------------------------------------

def test_autotune_decision_emits_flight_event():
    from horovod_tpu import autotune
    from horovod_tpu.debug import flight
    pm = autotune.ParameterManager(apply_fn=lambda *p: None)
    pm._apply(pm._current)
    kinds = [e["kind"] for e in flight.snapshot()]
    assert "autotune.decision" in kinds
    ev = [e for e in flight.snapshot()
          if e["kind"] == "autotune.decision"][-1]
    assert "fusion_bytes" in ev and "cycle_ms" in ev


def test_native_ladder_activity_emits_net_recovery(monkeypatch):
    from horovod_tpu.debug import flight
    from horovod_tpu.net import native as net_native

    class _Ctl:
        def __init__(self):
            self.c = {"retries": 0, "reconnects": 0, "renegotiations": 0,
                      "resets_avoided": 0, "chaos_injected": 0,
                      "recovering_now": 0, "last_recovery_age_ms": -1}

        def net_counters(self):
            return dict(self.c)

    from horovod_tpu.core.state import global_state
    ctl = _Ctl()
    monkeypatch.setattr(global_state, "controller", ctl, raising=False)
    net_native.reset_sync_state()
    net_native.sync_native_metrics()  # baseline: no deltas, no events
    before = [e for e in flight.snapshot() if e["kind"] == "net.recovery"]
    ctl.c["retries"] = 3
    ctl.c["resets_avoided"] = 1
    net_native.sync_native_metrics()
    after = [e for e in flight.snapshot() if e["kind"] == "net.recovery"]
    assert len(after) == len(before) + 1
    assert after[-1]["retries"] == 3
    assert after[-1]["resets_avoided"] == 1
    net_native.reset_sync_state()


def test_drift_vocabulary_covers_emitted_event_kinds():
    """Every causal event the correlation table classifies must map to
    a subsystem the component table can prefer — the diagnoser's
    vocabulary stays closed under its own preferences."""
    subs = set(regression.EVENT_SUBSYSTEM.values())
    preferred = set()
    for v in regression.COMPONENT_SUBSYSTEMS.values():
        preferred.update(v)
    assert preferred <= subs
    for kind in ("autotune.decision", "fleet.preempt", "net.recovery",
                 "elastic.resize", "data.chaos_delay"):
        assert kind in regression.EVENT_SUBSYSTEM
