"""From a profiler trace (``.xplane.pb``) to numbers.

The one place where device busy time, idle gaps, time by operation and
collective time are computed, so that every PR computes them the same way.
Read with nothing but ``jax.profiler.ProfileData``.  All times are integer
nanoseconds on the trace's own clock, which device planes and host threads
share.

A TPU device plane (``/device:TPU:<n>``) carries a line of executed
programs (``XLA Modules``), the profiler's own line of whole steps
(``Steps``), a line of the operations inside the programs (``XLA Ops``),
where a control-flow operation (``while``, ``conditional``, a call) spans
the operations of its body, and a line of asynchronous operations in
flight (``Async XLA Ops``).  Time by name is *self* time: an event's
duration less the part its children cover, so a loop is not counted twice.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)\Z")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEPS_LINE = "Steps"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
HOST_SPAN_PREFIX = "bench."


# -- intervals: lists of (start, end), integer ns -----------------------------

def union(intervals):
    """Sorted, disjoint intervals covering the same points; empty and
    inverted intervals are dropped."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(disjoint) -> int:
    return sum(e - s for s, e in disjoint)


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The part of union ``a`` that union ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(disjoint, lo: int, hi: int):
    """The idle intervals of [lo, hi] that ``disjoint`` leaves."""
    return subtract([(lo, hi)], clip(disjoint, lo, hi)) if hi > lo else []


def self_times(events):
    """[(name, self_ns)] for events (name, start, end) of one line: the
    duration of each less what the events nested inside it cover.  An event
    that only partly overlaps its predecessor is cut to the part after it."""
    out, stack = [], []          # stack of [name, end, self_ns]

    def pop():
        name, _end, self_ns = stack.pop()
        out.append((name, self_ns))

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            pop()
        if stack:
            e = min(e, stack[-1][1])
            stack[-1][2] -= max(0, e - s)
        if e > s:
            stack.append([name, e, e - s])
    while stack:
        pop()
    return out


# -- names ---------------------------------------------------------------------
#
# On a TPU plane an operation's event carries the HLO instruction's whole
# text as its name: ``%fusion.12 = bf16[...]{...} fusion(...), kind=...``.

_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
CONTROL_FLOW = ("while", "conditional", "call")


def instruction(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of an event name; a name that is not HLO
    text is its own instruction with the opcode ``""``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%"), ""
    m = _OPCODE.search(rest)
    return head.lstrip("%"), m.group(1) if m else ""


def kind_of(text: str) -> str:
    """``collective``, ``kernel`` (a Pallas kernel: a custom call whose
    target is ``tpu_custom_call``), ``control`` (an operation that only
    contains others) or ``compute``."""
    name, opcode = instruction(text)
    if (opcode or name).startswith(COLLECTIVES):
        return "collective"
    if opcode == "custom-call" and KERNEL_TARGET in text:
        return "kernel"
    if opcode in CONTROL_FLOW:
        return "control"
    return "compute"


def short_name(text: str) -> str:
    """``fusion.12 fusion``; a kernel is ``<instruction> tpu_custom_call``."""
    name, opcode = instruction(text)
    if kind_of(text) == "kernel":
        opcode = "tpu_custom_call"
    return f"{name} {opcode}".strip()


# -- reading ---------------------------------------------------------------------

def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_planes(path: str):
    """{plane name: {line name: [(event name, start, end)]}}."""
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, round(ev.start_ns),
                 round(ev.start_ns + ev.duration_ns))
                for ev in line.events)
    return planes


# -- reduction -------------------------------------------------------------------

def whole_programs(lines: dict):
    """The programs that ran from start to end inside the trace.  A trace
    begins and ends in the middle of a step, and the profiler's ``Steps``
    line holds the whole ones; without it, every event of ``XLA Modules``."""
    whole = lines.get(STEPS_LINE) or lines.get(MODULES_LINE) or []
    return sorted(whole, key=lambda ev: ev[1])


def reduce_device(lines: dict, window=None) -> dict | None:
    """One device plane's numbers inside ``window`` (default: from the start
    of the first whole program to the end of the last, or the extent of
    ``XLA Ops`` where the plane shows no program).

    Busy is the union of the operations that do work: an operation that only
    contains others (a ``while``) is not work, and the gaps between the
    operations of its body are idle time.  Collective time is the union of
    the collective operations on ``XLA Ops``, which every device's plane
    has: synchronous ones whole, asynchronous ones for their ``-start`` and
    ``-done``; the exposed part is what no compute operation or kernel on
    the same device overlaps.  The time asynchronous collectives are in
    flight (``Async XLA Ops``) is kept apart: the profiler writes that line
    for one device only."""
    ops = lines.get(OPS_LINE, [])
    if not ops:
        return None
    programs = whole_programs(lines)
    if window is None:
        if programs:
            window = (programs[0][1], programs[-1][2])
        else:
            window = (min(s for _, s, _ in ops), max(e for _, _, e in ops))
    lo, hi = window

    def cut(events):
        return [(n, max(s, lo), min(e, hi)) for n, s, e in events
                if min(e, hi) > max(s, lo)]

    ops = cut(ops)
    kinds = {n: kind_of(n) for n in {n for n, _, _ in ops}}
    by_name: dict[str, int] = {}
    self_ns = {"collective": 0, "kernel": 0, "control": 0, "compute": 0}
    for name, ns in self_times(ops):
        short = short_name(name)
        by_name[short] = by_name.get(short, 0) + ns
        self_ns[kinds[name]] += ns
    work = union((s, e) for n, s, e in ops if kinds[n] != "control")
    coll = union((s, e) for n, s, e in ops if kinds[n] == "collective")
    in_flight = union((s, e) for n, s, e in cut(lines.get(ASYNC_LINE, []))
                      if kind_of(n) == "collective")
    compute = union((s, e) for n, s, e in ops
                    if kinds[n] in ("compute", "kernel"))
    return {
        "window_ns": hi - lo, "busy_ns": total(work),
        "n_programs": sum(1 for _, s, e in programs if s >= lo and e <= hi),
        "by_name_ns": by_name, "self_ns": self_ns,
        "collective_ns": total(coll),
        "collective_async_ns": total(in_flight),
        "collective_exposed_ns": total(subtract(coll, compute)),
        "idle_gaps": gaps(work, lo, hi),
    }


def host_spans(planes: dict):
    """[(name, start, end)] of the benchmark's own host spans."""
    out = []
    for pname, lines in planes.items():
        if DEVICE_PLANE.match(pname):
            continue
        for events in lines.values():
            out.extend(ev for ev in events
                       if ev[0].startswith(HOST_SPAN_PREFIX))
    return sorted(out, key=lambda ev: ev[1])


def attribute_gap(gap, spans) -> str:
    """The host span that covers most of an idle gap, or ``(no span)``."""
    best, best_ns = "(no span)", 0
    for name, s, e in spans:
        ns = min(e, gap[1]) - max(s, gap[0])
        if ns > best_ns:
            best, best_ns = name, ns
    return best


def reduce_trace(path: str, devices=None) -> dict:
    """Every TPU plane's reduction (only the device ids in ``devices``, if
    given), keyed by device id, and the host spans."""
    planes = read_planes(path)
    out = {}
    for pname, lines in planes.items():
        m = DEVICE_PLANE.match(pname)
        if not m or (devices is not None and int(m.group(1)) not in devices):
            continue
        red = reduce_device(lines)
        if red is not None:
            out[int(m.group(1))] = red
    return {"devices": out, "host_spans": host_spans(planes)}


def breakdown(reduced: dict, n_ops: int = 10, n_gaps: int = 5) -> dict:
    """The contract's ``breakdown``: the operations with most self time on
    the busiest device, and the longest idle gaps of any device by the host
    span open at the time, in seconds."""
    devs = reduced["devices"]
    if not devs:
        return {}
    busiest = max(devs.values(), key=lambda d: d["busy_ns"])
    ops = sorted(busiest["by_name_ns"].items(), key=lambda kv: -kv[1])
    all_gaps = sorted((g for d in devs.values() for g in d["idle_gaps"]),
                      key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[n, ns / 1e9] for n, ns in ops[:n_ops]],
        "idle_gaps": [[attribute_gap(g, reduced["host_spans"]),
                       (g[1] - g[0]) / 1e9] for g in all_gaps[:n_gaps]],
    }


def per_step_ms(device: dict, ns: int):
    """``ns`` of one device's reduction in milliseconds a whole step, or
    None where the window holds no whole step."""
    if not device["n_programs"]:
        return None
    return ns / device["n_programs"] / 1e6


def over_devices(reduced, better: str, value):
    """``value(device)`` on the worst device: the lowest where higher is
    better, else the highest.  None where there is no trace, no device
    plane, or no device gives a value."""
    if not reduced or not reduced["devices"]:
        return None
    values = [v for v in map(value, reduced["devices"].values())
              if v is not None]
    if not values:
        return None
    return min(values) if better == "higher" else max(values)
