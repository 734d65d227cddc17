"""The pause sentinel (horovod_tpu/debug/pause.py): garbage collections and a
heartbeat on the profiler's clock, in the metrics registry and, when the
process stops for long, in the flight recorder and on the log.

Steady beside a busy machine: every assertion is a lower limit or a count of
what must be there, never that something was on time."""

import gc
import glob
import logging
import os
import sys
import threading
import time

import pytest

import horovod_tpu as hvd
from horovod_tpu.debug import flight, pause
from horovod_tpu.metrics.registry import registry


def pause_events(name):
    return [e for e in flight.snapshot()
            if e["kind"] == "pause" and e["name"] == name]


def hooks():
    return [cb for cb in gc.callbacks
            if isinstance(getattr(cb, "__self__", None), pause.PauseSentinel)]


def sentinel_threads():
    return [t for t in threading.enumerate() if t.name == pause.THREAD_NAME]


def scalar(name):
    return registry().scalars().get(name, 0.0)


@pytest.fixture
def many_objects():
    """Three million tracked objects, made with the collector off so that
    making them costs no collection."""
    gc.disable()
    try:
        junk = [[] for _ in range(3_000_000)]
    finally:
        gc.enable()
    yield junk
    del junk[:]


@pytest.fixture
def warnings_seen():
    """The program's log (it does not propagate to the root logger)."""
    seen = []

    class Keep(logging.Handler):
        def emit(self, record):
            seen.append(record.getMessage())

    from horovod_tpu.utils.logging import get_logger
    handler = Keep(level=logging.WARNING)
    get_logger().addHandler(handler)
    yield seen
    get_logger().removeHandler(handler)


def hold_the_lock(seconds=0.7, interval=0.5):
    """A pure-Python loop under a long switch interval: no other Python
    thread gets the interpreter lock for ``interval`` seconds."""
    old = sys.getswitchinterval()
    sys.setswitchinterval(interval)
    try:
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            n += 1
    finally:
        sys.setswitchinterval(old)
    return n


def wait_for(condition, seconds=10.0):
    deadline = time.monotonic() + seconds
    while not condition() and time.monotonic() < deadline:
        time.sleep(0.02)
    return condition()


# -- garbage collection ---------------------------------------------------------

def test_a_full_collection_moves_the_counters_and_is_a_flight_event(
        many_objects):
    hvd.init()
    before = {g: scalar(f"hvd_gc_collections_total{{generation={g}}}")
              for g in range(3)}
    seconds = scalar("hvd_gc_pause_seconds_total{generation=2}")
    n_events = len(pause_events("gc"))
    t0 = time.perf_counter()
    gc.collect()
    took = time.perf_counter() - t0
    assert scalar("hvd_gc_collections_total{generation=2}") >= before[2] + 1
    spent = scalar("hvd_gc_pause_seconds_total{generation=2}") - seconds
    assert 0.0 < spent and took > 0.0
    assert scalar("hvd_gc_pause_seconds_max") >= spent * 0.99
    # Three million objects keep the collector longer than GC_EVENT_S on
    # any machine this suite runs on; the event says which and how long.
    assert spent >= pause.GC_EVENT_S
    events = pause_events("gc")[n_events:]
    assert events, "a collection of %.3f s left no flight event" % spent
    assert events[-1]["generation"] == 2
    assert events[-1]["seconds"] >= pause.GC_EVENT_S
    assert events[-1]["collected"] >= 0


def test_a_young_collection_counts_under_its_own_generation():
    hvd.init()
    before = scalar("hvd_gc_collections_total{generation=0}")
    gc.collect(0)
    gc.collect(0)
    assert scalar("hvd_gc_collections_total{generation=0}") >= before + 2


def read_host_events(trace_dir):
    """[(name, start_ns, end_ns)] of every non-device plane of the trace."""
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert found, "no trace was written"
    out = []
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events)
    return out


def test_under_a_trace_a_collection_is_a_span_and_the_heartbeat_marks(
        tmp_path, many_objects):
    import jax
    hvd.init()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        gc.collect()
        time.sleep(5 * pause.PERIOD_S)
    finally:
        jax.profiler.stop_trace()
    events = read_host_events(str(tmp_path))
    spans = [e for e in events if e[0] == pause.GC_SPAN + "2"]
    assert spans, sorted({e[0] for e in events})[:40]
    assert max(e - s for _, s, e in spans) >= pause.GC_EVENT_S * 1e9
    assert sum(1 for e in events if e[0] == pause.TICK) >= 2


# -- the heartbeat -----------------------------------------------------------------

def test_the_lock_held_half_a_second_is_a_late_wake_and_one_line(
        warnings_seen):
    hvd.init()
    n_events = len(pause_events("host"))
    hold_the_lock()
    assert wait_for(lambda: len(pause_events("host")) > n_events)
    assert scalar("hvd_host_pause_seconds_max") >= 0.3
    assert scalar("hvd_host_pause_seconds_total") >= 0.3
    event = max(pause_events("host")[n_events:], key=lambda e: e["seconds"])
    assert event["seconds"] >= 0.3
    assert {"MainThread", pause.THREAD_NAME} <= set(event["threads"])
    assert {"gc_seconds", "cpu_seconds", "majflt", "nivcsw",
            "inblock"} <= set(event)
    # The loop that held the lock burned a core the while.
    assert event["cpu_seconds"] >= 0.2
    assert wait_for(lambda: any("host pause" in m for m in warnings_seen))
    line = next(m for m in warnings_seen if "host pause" in m)
    assert "MainThread" in line and pause.THREAD_NAME in line
    assert "garbage collection" in line and "major faults" in line
    # Where the main thread is as the heartbeat wakes: here still in the
    # loop that held the lock, or already waiting for the event.
    assert "the main thread is now at " in line
    assert ".py:" in event["main_at"] and " in " in event["main_at"]


def test_the_line_says_how_much_of_a_late_wake_a_collection_covers(
        warnings_seen):
    s = pause.PauseSentinel()
    s._gc_recent.extend([(10.0, 10.4), (11.0, 11.1), (20.0, 21.0)])
    assert s.gc_seconds_between(10.2, 11.05) == pytest.approx(0.25)
    assert s.gc_seconds_between(12.0, 13.0) == 0.0
    n_events = len(pause_events("host"))
    s._say(0.9, s.gc_seconds_between(10.2, 11.05), [3, 41, 7, 0.125])
    event = pause_events("host")[n_events:][0]
    assert event["seconds"] == 0.9
    assert event["gc_seconds"] == pytest.approx(0.25)
    assert (event["majflt"], event["nivcsw"], event["inblock"]) == (3, 41, 7)
    assert event["cpu_seconds"] == 0.125
    assert any("0.900 s late" in m and "0.250 s of it inside a garbage "
               "collection" in m and "the process used 0.125 s of CPU, 3 "
               "major faults, 41 involuntary context switches, 7 block "
               "reads" in m for m in warnings_seen)


def test_the_constants_are_the_configuration():
    assert pause.PERIOD_S == 0.020
    assert pause.GC_EVENT_S == 0.100 and pause.LATE_EVENT_S == 0.250
    assert pause.THREAD_NAME == "hvd-tpu-host-sentinel"
    assert (pause.TICK, pause.GC_SPAN) == ("hvd.tick", "hvd.gc.gen")
    # No knob: nothing of it in the documented configuration.
    from horovod_tpu.core.config import Config
    assert not [f for f in vars(Config()) if "pause" in f or "sentinel" in f]


# -- arming --------------------------------------------------------------------------

def test_init_and_shutdown_twice_leave_no_thread_and_one_hook():
    assert not hooks() and not sentinel_threads()
    for _ in range(2):
        hvd.init()
        hvd.init()                      # idempotent: still one of each
        assert len(hooks()) == 1 and len(sentinel_threads()) == 1
        assert sentinel_threads()[0].daemon
        hvd.shutdown()
        assert not hooks()
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("hvd-tpu-")]


def test_flight_disable_arms_nothing(monkeypatch):
    monkeypatch.setenv("HVD_TPU_FLIGHT_DISABLE", "1")
    hvd.init()
    assert not hooks() and not sentinel_threads()
    hvd.shutdown()
    assert not hooks() and not sentinel_threads()


def test_disarmed_the_hook_and_the_thread_are_gone_and_nothing_counts():
    s = pause.PauseSentinel()
    s.arm()
    s.arm()
    assert gc.callbacks.count(s._on_gc) == 1 and s.armed
    s.disarm()
    s.disarm()
    assert s._on_gc not in gc.callbacks and not s.armed
    before = scalar("hvd_gc_collections_total{generation=2}")
    gc.collect()
    assert scalar("hvd_gc_collections_total{generation=2}") == before
