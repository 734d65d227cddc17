"""The pause sentinel: the program notices when its own process stops.

A training loop's host is off the critical path while steps are queued on
the device, until the interpreter stops for longer than what is queued: a
full garbage collection over the objects a trace-and-compile left behind,
another thread holding the interpreter lock in a long call, or the whole
process stopped (CPU quota, paging, a ``fork`` of a large process).  The
device then sits idle and every clock that runs on the main thread (a
step time, a host span around ``float(loss)``) says only *that* it waited.
This module says why, in the three places an operator already looks:

* **the profiler's clock** — a span ``hvd.gc.gen<generation>`` around every
  collection and a mark ``hvd.tick`` every ``PERIOD_S`` from a thread of
  its own (``utils/profiler.host_span``: in whatever trace is being taken,
  beside the device planes; a flag test when none is);
* **the metrics registry** — ``hvd_gc_pause_seconds_total{generation}``,
  ``hvd_gc_collections_total{generation}``, ``hvd_gc_pause_seconds_max``;
  ``hvd_host_pause_seconds_total``, ``hvd_host_pause_seconds_max`` (how
  late the heartbeat woke: it needs the interpreter lock to run, so it is
  late exactly when the main thread could not have run either);
* **the flight recorder and the log** — a collection of ``GC_EVENT_S`` or
  more is one flight event (``kind="pause"``, ``name="gc"``); a wake
  ``LATE_EVENT_S`` or more late is one (``name="host"``) and one warning
  line: how late, how much of it a collection covers, what
  ``getrusage`` counted meanwhile (the process's CPU seconds, major
  faults, involuntary context switches, block reads), which Python threads
  live and where the main thread is now.

Armed by ``hvd.init()`` where it arms the flight recorder (so
``flight_disable`` arms nothing), disarmed by ``hvd.shutdown()``.  No knob:
the constants below are the whole configuration.  Between collections the
hook costs nothing; the thread wakes 50 times a second for a few
microseconds.
"""

from __future__ import annotations

import atexit
import collections
import gc
import resource
import sys
import threading
import time
from typing import Optional

from ..utils import logging as log
from ..utils.profiler import host_span
from . import flight as _flight

PERIOD_S = 0.020          # the heartbeat; benchmark/trace/host.py reads it
GC_EVENT_S = 0.100        # a collection this long is a flight event
LATE_EVENT_S = 0.250      # a wake this late is a flight event and a warning
THREAD_NAME = "hvd-tpu-host-sentinel"
TICK = "hvd.tick"
GC_SPAN = "hvd.gc.gen"    # + the generation
GENERATIONS = 3
# Collections kept for "how much of a late wake a collection covers":
# (start, end) on ``time.monotonic``, the long ones only.
_GC_KEPT_S = 0.001
_GC_KEEP = 64


def _usage() -> tuple:
    """Of the whole process: major faults, involuntary context switches,
    block reads, CPU seconds (user + system, every thread's)."""
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_majflt, r.ru_nivcsw, r.ru_inblock, r.ru_utime + r.ru_stime


def _main_thread_at() -> str:
    """``file:line in function`` of the main thread's innermost frame: after
    a stop it is most often still in the call that held the lock, or in
    the wait that the stop interrupted."""
    frame = sys._current_frames().get(threading.main_thread().ident)
    if frame is None:
        return "(no frame)"
    code = frame.f_code
    return f"{code.co_filename}:{frame.f_lineno} in {code.co_name}"


class PauseSentinel:
    """One ``gc.callbacks`` hook and one heartbeat thread.  One instance a
    process (:func:`arm`); tests make their own."""

    def __init__(self):
        from ..metrics.registry import registry
        reg = registry()
        self.gc_seconds = [reg.counter(
            "hvd_gc_pause_seconds_total",
            "Seconds the interpreter spent in garbage collections",
            generation=str(g)) for g in range(GENERATIONS)]
        self.gc_count = [reg.counter(
            "hvd_gc_collections_total", "Garbage collections",
            generation=str(g)) for g in range(GENERATIONS)]
        self.gc_max = reg.gauge(
            "hvd_gc_pause_seconds_max", "Longest garbage collection")
        self.late_total = reg.counter(
            "hvd_host_pause_seconds_total",
            "Seconds the heartbeat woke late, of wakes a period or more "
            "late")
        self.late_max = reg.gauge(
            "hvd_host_pause_seconds_max", "Latest wake of the heartbeat")
        self._gc_names = [f"{GC_SPAN}{g}" for g in range(GENERATIONS)]
        self._gc_open = None           # (span or None, monotonic at start)
        self._gc_recent = collections.deque(maxlen=_GC_KEEP)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- garbage collection ---------------------------------------------------
    def _on_gc(self, phase: str, info: dict) -> None:
        # Runs on whichever thread's allocation set the collection off, both
        # phases on that thread, never nested.
        if phase == "start":
            span = host_span(self._gc_names[info["generation"]])
            if span is not None:
                span.__enter__()
            self._gc_open = (span, time.monotonic())
            return
        if self._gc_open is None:      # armed in the middle of a collection
            return
        (span, t0), self._gc_open = self._gc_open, None
        t1 = time.monotonic()
        if span is not None:
            span.__exit__(None, None, None)
        seconds, generation = t1 - t0, info["generation"]
        self.gc_seconds[generation].inc(seconds)
        self.gc_count[generation].inc()
        if seconds > self.gc_max.value:
            self.gc_max.set(seconds)
        if seconds >= _GC_KEPT_S:
            self._gc_recent.append((t0, t1))
        if seconds >= GC_EVENT_S:
            _flight.record("pause", "gc", generation=generation,
                           seconds=seconds, collected=info["collected"])

    def gc_seconds_between(self, lo: float, hi: float) -> float:
        """Seconds of [lo, hi] (``time.monotonic``) inside the kept
        collections, which never overlap each other."""
        return sum(max(0.0, min(t1, hi) - max(t0, lo))
                   for t0, t1 in list(self._gc_recent))

    # -- the heartbeat ----------------------------------------------------------
    def _beat(self) -> None:
        usage = _usage()
        while True:
            asleep = time.monotonic()
            if self._stop.wait(PERIOD_S):
                return
            now = time.monotonic()
            span = host_span(TICK)
            if span is not None:
                with span:
                    pass
            late = now - asleep - PERIOD_S
            was, usage = usage, _usage()
            if late > self.late_max.value:
                self.late_max.set(late)
            if late >= PERIOD_S:
                self.late_total.inc(late)
            if late >= LATE_EVENT_S:
                self._say(late, self.gc_seconds_between(asleep + PERIOD_S,
                                                        now),
                          [b - a for a, b in zip(was, usage)])

    def _say(self, late: float, in_gc: float, used: list) -> None:
        majflt, nivcsw, inblock, cpu = used
        threads = sorted(t.name for t in threading.enumerate())
        main_at = _main_thread_at()
        _flight.record("pause", "host", seconds=late, gc_seconds=in_gc,
                       cpu_seconds=cpu, majflt=majflt, nivcsw=nivcsw,
                       inblock=inblock, threads=threads, main_at=main_at)
        log.warning(
            "host pause: the heartbeat woke %.3f s late (no Python thread "
            "of this process ran meanwhile); %.3f s of it inside a garbage "
            "collection; since the last beat the process used %.3f s of "
            "CPU, %d major faults, %d involuntary context switches, %d "
            "block reads; live threads %s; the main thread is now at %s",
            late, in_gc, cpu, majflt, nivcsw, inblock, threads, main_at)

    # -- arming -------------------------------------------------------------------
    @property
    def armed(self) -> bool:
        return self._thread is not None

    def arm(self) -> None:
        if self.armed:
            return
        gc.callbacks.append(self._on_gc)
        self._stop.clear()
        self._thread = threading.Thread(target=self._beat, name=THREAD_NAME,
                                        daemon=True)
        self._thread.start()

    def disarm(self) -> None:
        if not self.armed:
            return
        self._stop.set()
        self._thread.join(timeout=5.0)   # it wakes within a period
        self._thread = None
        gc.callbacks.remove(self._on_gc)
        self._gc_open = None


_sentinel: Optional[PauseSentinel] = None
_lock = threading.Lock()


def arm() -> PauseSentinel:
    """Arm the process's sentinel (idempotent: one hook, one thread)."""
    global _sentinel
    with _lock:
        if _sentinel is None:
            _sentinel = PauseSentinel()
            # A program that never calls hvd.shutdown(): stop the heartbeat
            # before the interpreter's finalization freezes daemon threads
            # wherever they are.
            atexit.register(disarm)
        _sentinel.arm()
        return _sentinel


def disarm() -> None:
    with _lock:
        if _sentinel is not None:
            _sentinel.disarm()
