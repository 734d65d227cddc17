"""Device time by the names the program gives its own work.

``reduce.py`` reads a trace through ``jax.profiler.ProfileData``, which shows
an event's HLO text and nothing of the event's *metadata*.  The metadata is
where the profiler keeps JAX's ``op_name`` (as the stat ``tf_op``): the path
of ``jit`` / ``jvp`` / ``transpose`` / ``jax.named_scope`` names an operation
was traced under, e.g.

    jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/hvd_attn/dot_general

This module reads the same ``.xplane.pb`` as the protobuf it is (``XSpace``,
tsl/profiler/protobuf/xplane.proto; a reader of the wire format, so nothing is
imported into the process that holds the chip) and sorts the self time of
every ``XLA Ops`` event three ways:

* kernel: the instruction's name starts with one of ``KERNELS``, the
  ``name=`` the program gives its ``pl.pallas_call``s;
* phase: ``transpose`` among the path's tokens is the backward pass
  (recompute under remat lands there too), else ``jvp`` the forward pass,
  else ``hvd_optimizer`` the optimizer, else ``other``;
* block: the one of ``BLOCKS`` (``horovod_tpu.utils.profiler.STEP_SCOPES``)
  among the tokens, else ``none``.

A scope can be a path component or sit inside the parentheses of
``jvp(...)`` / ``transpose(...)``, so a path is split on ``/``, ``(`` and
``)`` and a token is tested for membership, never a prefix matched.  Window,
whole steps, self time and kinds are ``reduce.py``'s own, by import, so the
totals here are its totals.  A program that has none of the names (this
repository before them) gives no kernel and no block: those readers return
nothing.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import time
from collections import Counter

from benchmark import loader
from benchmark.trace import reduce as R

KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
BLOCKS = ("hvd_embed", "hvd_attn", "hvd_mlp", "hvd_head", "hvd_optimizer")
PHASES = ("fwd", "bwd", "optimizer", "other")
KINDS = ("kernel", "collective", "compute")
LINES = (R.OPS_LINE, R.STEPS_LINE, R.MODULES_LINE)
_SPLIT = re.compile(r"[/()]")


def say(msg: str) -> None:
    print(f"benchmark: {msg}", flush=True)


# -- names ---------------------------------------------------------------------

def tokens(op_name: str) -> frozenset:
    return frozenset(t for t in _SPLIT.split(op_name) if t)


def phase_of(toks) -> str:
    if "transpose" in toks:
        return "bwd"
    if "jvp" in toks:
        return "fwd"
    return "optimizer" if "hvd_optimizer" in toks else "other"


def block_of(toks) -> str:
    return next((b for b in BLOCKS if b in toks), "none")


def kernel_of(text: str) -> str | None:
    """Which of ``KERNELS`` an event's HLO text is, or None: a Pallas
    kernel whose instruction is named after one of them."""
    if R.kind_of(text) != "kernel":
        return None
    name, _opcode = R.instruction(text)
    return next((k for k in KERNELS if name.startswith(k)), None)


# -- the wire format -----------------------------------------------------------------
#
# xplane.proto, the fields read here (all others are skipped):
#   XSpace          planes=1
#   XPlane          name=2 lines=3 event_metadata=4 stat_metadata=5  (maps:
#                   entries of key=1 value=2)
#   XLine           name=2 timestamp_ns=3 events=4
#   XEvent          metadata_id=1 offset_ps=2 duration_ps=3
#   XEventMetadata  id=1 name=2 stats=5
#   XStatMetadata   id=1 name=2
#   XStat           metadata_id=1 str_value=5 ref_value=7

def _varint(buf, i: int):
    b = buf[i]
    i += 1
    if b < 0x80:
        return b, i
    value, shift = b & 0x7F, 7
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} at byte {i}")
            value, i = buf[i:i + size], i + size
            if i > n:
                raise ValueError("a field runs past its message")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    return next((v for f, v in fields(entry) if f == 2), b"")


def _stat_names(plane_fields) -> dict:
    names = {}
    for f, v in plane_fields:
        if f == 5:
            meta = dict(fields(_map_value(v)))
            names[meta.get(1, 0)] = _text(meta.get(2, b""))
    return names


def _event_metadata(plane_fields, stat_names: dict) -> dict:
    """{metadata id: (HLO text, op_name or "")}."""
    out = {}
    for f, v in plane_fields:
        if f != 4:
            continue
        mid, name, op_name = 0, "", ""
        for mf, mv in fields(_map_value(v)):
            if mf == 1:
                mid = mv
            elif mf == 2:
                name = _text(mv)
            elif mf == 5:
                stat = dict(fields(mv))
                if stat_names.get(stat.get(1)) == "tf_op":
                    op_name = (_text(stat[5]) if 5 in stat
                               else stat_names.get(stat.get(7), ""))
        out[mid] = (name, op_name)
    return out


def _line_events(line) -> tuple[str, list]:
    """(line name, [(metadata id, start, end)]) in the integer nanoseconds
    ``reduce.read_planes`` gets from ``ProfileData``, which cuts an offset
    and a duration to whole nanoseconds each: a start of ``timestamp_ns +
    offset_ps // 1000`` and an end of that plus ``duration_ps // 1000``."""
    name, t0, events = "", 0, []
    for f, v in fields(line):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0 = v
        elif f == 4:
            events.append(v)
    if name not in LINES:
        return name, []
    out = []
    for ev in events:
        mid = offset = duration = 0
        for f, v in fields(ev):
            if f == 1:
                mid = v
            elif f == 2:
                offset = v
            elif f == 3:
                duration = v
        start = t0 + offset // 1000
        out.append((mid, start, start + duration // 1000))
    return name, out


def read_device_planes(path: str) -> dict:
    """{device id: {"lines": {line name: [(metadata id, start, end)]},
    "meta": {metadata id: (HLO text, op_name)}}} of the TPU planes."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in fields(space):
        if f != 1:
            continue
        plane_fields = list(fields(plane))
        m = R.DEVICE_PLANE.match(
            next((_text(v) for pf, v in plane_fields if pf == 2), ""))
        if not m:
            continue
        lines = {}
        for pf, v in plane_fields:
            if pf == 3:
                name, events = _line_events(v)
                if events:
                    lines.setdefault(name, []).extend(events)
        out[int(m.group(1))] = {
            "lines": lines,
            "meta": _event_metadata(plane_fields, _stat_names(plane_fields))}
    return out


# -- classification --------------------------------------------------------------

def classify_device(lines: dict, meta: dict) -> dict | None:
    """One device plane's self time inside ``reduce_device``'s window, by
    kernel, phase and block.  ``kernel_ns`` and ``block_ns`` hold only the
    names that occur, ``phase_ns`` is empty where no operation carries an
    ``op_name``; an operation that only contains others is not work and is
    kept apart (``control_ns``), as ``reduce.py`` keeps it out of busy
    time."""
    ops = lines.get(R.OPS_LINE, [])
    if not ops:
        return None
    programs = R.whole_programs(lines)
    if programs:
        lo, hi = programs[0][1], programs[-1][2]
    else:
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    ops = [(m, max(s, lo), min(e, hi)) for m, s, e in ops
           if min(e, hi) > max(s, lo)]

    @functools.cache
    def classes(mid):
        text, op_name = meta.get(mid, ("", ""))
        toks = tokens(op_name)
        return R.kind_of(text), kernel_of(text), phase_of(toks), block_of(toks)

    out = {"window_ns": hi - lo,
           "n_programs": sum(1 for _, s, e in programs if s >= lo and e <= hi),
           "kernel_ns": Counter(), "phase_ns": Counter(),
           "block_ns": Counter(), "table_ns": Counter(),
           "kernel_events": Counter(
               k for k in (classes(m)[1] for m, _, _ in ops) if k),
           "control_ns": 0, "work_ns": 0}
    named = any(meta.get(m, ("", ""))[1] for m, _, _ in ops)
    for mid, ns in R.self_times(ops):
        kind, kernel, phase, block = classes(mid)
        if kind == "control":
            out["control_ns"] += ns
            continue
        out["work_ns"] += ns
        out["table_ns"][block, phase, kind] += ns
        if kernel:
            out["kernel_ns"][kernel] += ns
        if named:
            out["phase_ns"][phase] += ns
        if block != "none":
            out["block_ns"][block] += ns
    return out


def classify_trace(path: str, devices=None) -> dict:
    """``{"devices": {id: classify_device(...)}}``, the shape
    ``reduce.over_devices`` takes."""
    out = {}
    for dev, plane in read_device_planes(path).items():
        if devices is None or dev in devices:
            d = classify_device(plane["lines"], plane["meta"])
            if d is not None:
                out[dev] = d
    return {"devices": out}


def table(device: dict) -> list[str]:
    """Device ms a step by block x phase x kind, for the log."""
    steps = device["n_programs"] or 1
    rows = sorted({(b, p) for b, p, _ in device["table_ns"]},
                  key=lambda bp: ((BLOCKS + ("none",)).index(bp[0]),
                                  PHASES.index(bp[1])))
    out = [f"{'block':<14}{'phase':<10}"
           + "".join(f"{k:>12}" for k in KINDS)]
    for b, p in rows:
        out.append(f"{b:<14}{p:<10}" + "".join(
            f"{device['table_ns'].get((b, p, k), 0) / steps / 1e6:12.3f}"
            for k in KINDS))
    return out


# -- this process's trace ---------------------------------------------------------

@functools.cache
def process_start() -> float:
    """When this process started, seconds since the epoch (Linux)."""
    with open("/proc/self/stat", encoding="ascii") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/stat", encoding="ascii") as f:
        boot = next(int(ln.split()[1]) for ln in f if ln.startswith("btime"))
    return boot + ticks / os.sysconf("SC_CLK_TCK")


def newest_trace(trace_dir, not_before: float) -> str | None:
    """The newest ``*.xplane.pb`` anywhere under ``trace_dir``, or None
    where there is none or it was written before ``not_before``: a trace an
    earlier process left is not this run's."""
    found = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        return None
    newest = max(found, key=os.path.getmtime)
    return newest if os.path.getmtime(newest) >= not_before else None


@functools.lru_cache(maxsize=1)
def _classified(path: str, mtime_ns: int, devices: frozenset):
    """``classify_trace`` once for the seven readers that share it; the
    table goes to the log as it is first read."""
    t0 = time.perf_counter()
    out = classify_trace(path, devices)
    if out["devices"]:
        worst = max(out["devices"].values(), key=lambda d: d["work_ns"])
        say("device ms a step by the program's names, busiest device "
            f"({worst['n_programs']} whole steps; operations that only "
            f"contain others {worst['control_ns'] / 1e6:.3f} ms in all):")
        for row in table(worst):
            say("  " + row)
        say("kernel events a step: " + ", ".join(
            f"{k} {n / (worst['n_programs'] or 1):g}"
            for k, n in sorted(worst["kernel_events"].items())))
    say(f"read {path} by scope in {time.perf_counter() - t0:.2f} s")
    return out


def classified(layers) -> dict | None:
    """The traced run's classification, read once a process: the newest
    trace under the runner's ``TRACE_DIR`` (``layers`` carries no path), for
    the devices ``reduce.py`` reduced.  None for an untraced run, a trace
    with no TPU plane, no trace of this process, or one whose windows are
    not those ``reduce.py`` found."""
    reduced = layers["trace"]
    if not reduced or not reduced["devices"]:
        return None
    trace_dir = loader.load_code("runners", "train").TRACE_DIR
    path = newest_trace(trace_dir, process_start() - 1.0)
    if path is None:
        say(f"no trace of this process under {trace_dir}")
        return None
    out = _classified(path, os.stat(path).st_mtime_ns,
                      frozenset(reduced["devices"]))
    same = out["devices"].keys() == reduced["devices"].keys() and all(
        (d["window_ns"], d["n_programs"])
        == (reduced["devices"][i]["window_ns"],
            reduced["devices"][i]["n_programs"])
        for i, d in out["devices"].items())
    if not same:
        say(f"{path} is not the trace the runner reduced")
        return None
    return out


def ms_per_step(layers, metric, ns_of):
    """``ns_of(device)`` on the worst device in milliseconds a whole step;
    None where ``ns_of`` gives None (the name does not occur)."""
    def value(d):
        ns = ns_of(d)
        return None if ns is None else R.per_step_ms(d, ns)
    return R.over_devices(classified(layers), metric["better"], value)
