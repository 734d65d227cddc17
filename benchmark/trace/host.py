"""What the host was doing when the device went idle.

``reduce.py`` puts an idle gap down to the ``bench.*`` span that covers most
of it, and those spans are all on one thread, all *around* calls into the
program: "under ``bench.wait_loss``" says that the main thread waited, not
why.  The program's pause sentinel (``horovod_tpu/debug/pause.py``, armed by
``hvd.init()``) writes two more names into the same trace, on the same clock:

* ``hvd.gc.gen<n>`` — a span around every garbage collection;
* ``hvd.tick`` — a mark every ``PERIOD_NS`` from a thread of its own, which
  needs the interpreter lock to run: consecutive marks further apart than a
  period are a stretch in which no Python thread of the process ran (a
  collection, another thread holding the lock in a long call, the whole
  process stopped).

This module reads every host thread's events through
``reduce.read_planes`` and gives three numbers:

* the share of the traced interval inside ``hvd.gc.*`` spans;
* the widest spacing of consecutive marks, less the period;
* the longest part of a device's idle gap that neither a collection nor a
  late mark covers.

The interval of the first two is the one the ``bench.*`` spans cover, first
start to last end, so they need no device plane and serve any runner.  A
trace without ``hvd.*`` marks (this repository before the sentinel, or
``flight_disable``) gives nothing: the readers return None and do not raise.
In a traced run the five longest idle gaps over a millisecond go to the log,
each with its ``bench.*`` span, its ``hvd.*`` spans and the events of the
*other* host threads (the runtime's own) that overlap it most.
"""

from __future__ import annotations

import functools
import glob
import os

from benchmark import loader
from benchmark.trace import reduce as R
from benchmark.trace import scopes as S

TICK = "hvd.tick"
GC_PREFIX = "hvd.gc."
PROGRAM_PREFIX = "hvd."
PERIOD_NS = 20_000_000      # horovod_tpu/debug/pause.py PERIOD_S
# A mark counts as late, for what it explains of an idle gap, when it came a
# whole period or more after it was due (one mark at least is missing): the
# interpreter's 5 ms switch interval and the scheduler's jitter stay under it.
LATE_NS = PERIOD_NS
TABLE_GAP_NS = 1_000_000
TABLE_ROWS = 5
TABLE_EVENTS = 3


# -- the host's side of the planes --------------------------------------------

def host_threads(planes: dict) -> dict:
    """{(plane, line): [(name, start, end)]} of every plane that is not a
    device's: a line of a host plane is a thread."""
    return {(pname, lname): events
            for pname, lines in planes.items()
            if not pname.startswith("/device:")
            for lname, events in lines.items() if events}


def late_intervals(ticks: list) -> list:
    """[(due, came)] for the marks that came ``LATE_NS`` or more after they
    were due, ``ticks`` being the marks' times in order: the stretch in
    which the heartbeat's thread could not run."""
    return [(a + PERIOD_NS, b) for a, b in zip(ticks, ticks[1:])
            if b - (a + PERIOD_NS) >= LATE_NS]


def reduce_host(planes: dict) -> dict | None:
    """The host's numbers over the interval the ``bench.*`` spans cover, or
    None where the trace holds no such span or no mark of the sentinel's."""
    threads = host_threads(planes)
    events = [ev for evs in threads.values() for ev in evs]
    bench = [ev for ev in events if ev[0].startswith(R.HOST_SPAN_PREFIX)]
    ticks = sorted(s for n, s, _ in events if n == TICK)
    gc_spans = [(s, e) for n, s, e in events if n.startswith(GC_PREFIX)]
    if not bench or not (ticks or gc_spans):
        return None
    lo, hi = min(s for _, s, _ in bench), max(e for _, _, e in bench)
    gc, late = R.union(gc_spans), late_intervals(ticks)
    # Every spacing that reaches into the interval, whole: a stop that began
    # before the first bench span and ended inside it is this interval's.
    spacings = [b - a for a, b in zip(ticks, ticks[1:]) if b > lo and a < hi]
    return {
        "window": (lo, hi), "threads": threads,
        "n_ticks": sum(lo <= t <= hi for t in ticks),
        "n_gc": sum(1 for s, e in gc_spans if e > lo and s < hi),
        "gc_ns": R.total(R.clip(gc, lo, hi)),
        "pause_ns": max([0] + [sp - PERIOD_NS for sp in spacings]),
        "late": late, "covered": R.union(gc + late),
    }


def unexplained_ns(gap, host: dict) -> int:
    """The part of an idle gap that neither a collection nor a late mark
    covers."""
    return (gap[1] - gap[0]) - R.total(R.clip(host["covered"], *gap))


# -- the table ----------------------------------------------------------------

def overlap(ev, gap) -> int:
    return min(ev[2], gap[1]) - max(ev[1], gap[0])


def gap_row(gap, host: dict) -> str:
    """One idle gap for the log: its length and what is left unexplained,
    the ``bench.*`` span over most of it, the ``hvd.*`` spans inside it, and
    the events of the threads that hold neither that overlap it most."""
    ms = lambda ns: f"{ns / 1e6:.3f} ms"                      # noqa: E731
    spans, program, others = [], [], []
    for (_plane, line), events in host["threads"].items():
        for ev in events:
            inside = overlap(ev, gap)
            if inside <= 0 or ev[0] == TICK:
                continue
            if ev[0].startswith(R.HOST_SPAN_PREFIX):
                spans.append(ev)
            elif ev[0].startswith(PROGRAM_PREFIX):
                program.append((ev[0], inside))
            else:
                others.append((inside, line, ev[0]))
    program.sort(key=lambda no: -no[1])
    late = R.total(R.clip(host["late"], *gap))
    said = [f"idle {ms(gap[1] - gap[0])} (unexplained "
            f"{ms(unexplained_ns(gap, host))}) under "
            f"{R.attribute_gap(gap, spans)}",
            "hvd spans: " + (", ".join(f"{n} {ms(o)}" for n, o in program)
                             or "none")
            + (f", late marks cover {ms(late)}" if late else
               ", marks on time")]
    others.sort(reverse=True)
    said.append("other host threads: " + (
        ", ".join(f"{line}: {name[:60]} {ms(o)}"
                  for o, line, name in others[:TABLE_EVENTS])
        or "no host thread has an event inside it"))
    return "; ".join(said)


def say_table(host: dict, gaps: list) -> None:
    long = sorted((g for g in gaps if g[1] - g[0] > TABLE_GAP_NS),
                  key=lambda g: g[0] - g[1])[:TABLE_ROWS]
    lo, hi = host["window"]
    S.say(f"host while the device was idle ({(hi - lo) / 1e6:.1f} ms under "
          f"bench.* spans: {host['n_ticks']} {TICK} marks, {host['n_gc']} "
          f"collections of {host['gc_ns'] / 1e6:.3f} ms, widest spacing of "
          f"marks {host['pause_ns'] / 1e6:.3f} ms over the period): "
          + (f"the {len(long)} longest idle gap(s) over "
             f"{TABLE_GAP_NS / 1e6:g} ms" if long else
             f"no idle gap over {TABLE_GAP_NS / 1e6:g} ms among "
             f"{len(gaps)} (longest "
             f"{max([g[1] - g[0] for g in gaps] or [0]) / 1e6:.3f} ms)"))
    for gap in long:
        S.say("  " + gap_row(gap, host))


# -- this process's trace -------------------------------------------------------

def traces_of_this_process(trace_dir) -> list:
    """Every ``*.xplane.pb`` under ``trace_dir`` that is not older than
    this process, newest first (``scopes.newest_trace``'s rule, all of
    them: another process's run may have written a newer one beside
    ours)."""
    found = [p for p in glob.glob(os.path.join(str(trace_dir), "**",
                                               "*.xplane.pb"),
                                  recursive=True)
             if os.path.getmtime(p) >= S.process_start() - 1.0]
    return sorted(found, key=os.path.getmtime, reverse=True)


@functools.lru_cache(maxsize=1)
def _reduced(spans: tuple, gaps: tuple) -> dict | None:
    """The host's numbers for the trace whose ``bench.*`` spans are
    ``spans`` (the runner's reduction's), once for the three readers; the
    table goes to the log as it is first read."""
    trace_dir = loader.load_code("runners", "train").TRACE_DIR
    for path in traces_of_this_process(trace_dir):
        try:
            planes = R.read_planes(path)
        except Exception as e:          # a file another process is writing
            S.say(f"{path} cannot be read ({e!r})")
            continue
        if tuple(R.host_spans(planes)) != spans:
            continue
        host = reduce_host(planes)
        if host is None:
            S.say(f"{path} holds no {TICK} mark and no {GC_PREFIX}* span: "
                  "the program has no pause sentinel, or it is not armed")
            return None
        say_table(host, list(gaps))
        return host
    S.say(f"no trace of this process under {trace_dir} holds the spans "
          "the runner reduced")
    return None


def reduced(layers) -> tuple:
    """(the host's numbers or None, every device's idle gaps) of the
    traced run the runner reduced; (None, []) for an untraced run."""
    trace = layers["trace"]
    if not trace or not trace.get("host_spans"):
        return None, []
    gaps = [tuple(g) for d in trace["devices"].values()
            for g in d["idle_gaps"]]
    return _reduced(tuple(tuple(s) for s in trace["host_spans"]),
                    tuple(gaps)), gaps


# -- the three readers -------------------------------------------------------------

def gc_share(layers):
    host, _gaps = reduced(layers)
    if host is None:
        return None
    lo, hi = host["window"]
    return 100.0 * host["gc_ns"] / (hi - lo) if hi > lo else None


def pause_ms_max(layers):
    host, _gaps = reduced(layers)
    return None if host is None else host["pause_ns"] / 1e6


def idle_unexplained_ms_max(layers):
    host, gaps = reduced(layers)
    if host is None or not layers["trace"]["devices"]:
        return None
    return max([0] + [unexplained_ns(g, host) for g in gaps]) / 1e6
