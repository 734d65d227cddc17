"""Device time of a step by everything its trace says of an operation: the
program's names, the pass, the kind and XLA's own category.

``scopes.py`` reads one stat of an ``XLA Ops`` event's metadata, ``tf_op``
(JAX's ``op_name``), and the six family readers beside it each look for a
few names of their own.  The metadata says more, and JAX writes more into
the path, and this module reads both for every operation at once:

* ``hlo_category``, the profiler's own sorting of the HLO instruction:
  ``convolution fusion`` and ``convolution`` are XLA's matmuls (``MATMUL``),
  ``data formatting`` its copies, transposes and bitcasts (``COPY``), beside
  ``loop fusion``, ``non-fusion elementwise``, ``dynamic-update-slice``,
  ``custom-call`` ...  Whether a ``fusion.N`` is a matmul or a copy is read,
  not guessed from its name;
* ``rematted_computation``, the token JAX puts into the ``op_name`` of
  every operation a ``jax.checkpoint`` recomputes in the backward pass
  (``jax/_src/ad_checkpoint.py``), as it puts ``jvp`` and ``transpose``: the
  pass ``recompute``, which ``scopes.phase_of`` counts as ``bwd``;
* every ``hvd_*`` token of the path, as a set: an operation is filed under
  all the names it carries, so a name can be asked for with or without the
  others (``hvd_layers`` alone is the layer scan's own work);
* ``flops`` and ``bytes_accessed``, XLA's own count for the instruction,
  and ``source``, the program's file and line it was traced at, for the
  log's tables only: no metric is made of them.

The wire reader, the window of whole steps, the self-time rule and the kinds
are ``scopes.py``'s and ``reduce.py``'s, by import, so the totals here are
theirs to the nanosecond.  One call, ``ms_per_step``, answers for a metric's
file; a later metric is three lines.  A trace without a name, a category or
any ``op_name`` gives that reader nothing: it returns None and does not
raise.  A fusion carries the ``op_name`` of its root instruction alone:
where XLA fuses a recomputed operation behind a backward one, the whole
fusion is the backward's.
"""

from __future__ import annotations

import functools
import os
import time
from collections import Counter

from benchmark import loader
from benchmark.trace import reduce as R
from benchmark.trace import scopes as S

RECOMPUTE = "rematted_computation"
PASSES = ("fwd", "recompute", "bwd", "optimizer", "other")
MATMUL = ("convolution fusion", "convolution")
COPY = ("data formatting",)
# The names inside ``hvd_attn`` that say what an operation there is for;
# compute under ``hvd_attn`` with none of them that is no matmul either is
# the block's remainder nobody has named yet.
ATTN_PARTS = ("hvd_attn_rope", "hvd_attn_qknorm", "hvd_attn_gate",
              "hvd_attn_kv_repeat", "hvd_attn_delta", "hvd_attn_index",
              "hvd_attn_index_loss")
_PREFIX = "hvd_"
_STATS = ("tf_op", "hlo_category", "flops", "bytes_accessed", "source")
_NO_META = ("", "", None, 0, 0, "")
N_OPS = 16                     # the longest operations the log lists


def pass_of(toks) -> str:
    return "recompute" if RECOMPUTE in toks else S.phase_of(toks)


def names_of(toks) -> frozenset:
    return frozenset(t for t in toks if t.startswith(_PREFIX))


# -- the wire format: XEventMetadata's stats ---------------------------------------
#
#   XStat  metadata_id=1 uint64_value=3 int64_value=4 str_value=5 ref_value=7

def _event_metadata(plane_fields, stat_names: dict) -> dict:
    """{metadata id: (HLO text, op_name, category or None, flops, bytes,
    source)}."""
    out = {}
    for f, v in plane_fields:
        if f != 4:
            continue
        mid, text, found = 0, "", {}
        for mf, mv in S.fields(S._map_value(v)):
            if mf == 1:
                mid = mv
            elif mf == 2:
                text = S._text(mv)
            elif mf == 5:
                stat = dict(S.fields(mv))
                name = stat_names.get(stat.get(1))
                if name not in _STATS:
                    continue
                if 5 in stat:
                    found[name] = S._text(stat[5])
                elif 7 in stat:
                    found[name] = stat_names.get(stat[7], "")
                else:
                    found[name] = stat.get(3, stat.get(4, 0))
        out[mid] = (text, found.get("tf_op", ""), found.get("hlo_category"),
                    found.get("flops", 0), found.get("bytes_accessed", 0),
                    found.get("source", ""))
    return out


def read_device_planes(path: str) -> dict:
    """``scopes.read_device_planes`` with the wider metadata."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in S.fields(space):
        if f != 1:
            continue
        plane_fields = list(S.fields(plane))
        m = R.DEVICE_PLANE.match(
            next((S._text(v) for pf, v in plane_fields if pf == 2), ""))
        if not m:
            continue
        lines = {}
        for pf, v in plane_fields:
            if pf == 3:
                name, events = S._line_events(v)
                if events:
                    lines.setdefault(name, []).extend(events)
        out[int(m.group(1))] = {
            "lines": lines,
            "meta": _event_metadata(plane_fields,
                                    S._stat_names(plane_fields))}
    return out


# -- classification --------------------------------------------------------------

def classify_device(lines: dict, meta: dict) -> dict:
    """One device plane's self time inside ``scopes.classify_device``'s
    window: ``part_ns`` by (names, pass, kind, category) of every operation
    that does work, ``work_ns`` their sum, ``flops`` and ``bytes`` what XLA
    counts for the same events by category, ``ops`` the ``N_OPS``
    instructions with most self time that are no Pallas kernel: (ns, events,
    short name, part, flops and bytes an event, source)."""
    ops = lines.get(R.OPS_LINE, [])
    out = {"part_ns": Counter(), "flops": Counter(), "bytes": Counter(),
           "work_ns": 0, "ops": []}
    if not ops:
        return out
    programs = R.whole_programs(lines)
    if programs:
        lo, hi = programs[0][1], programs[-1][2]
    else:
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    ops = [(m, max(s, lo), min(e, hi)) for m, s, e in ops
           if min(e, hi) > max(s, lo)]

    @functools.cache
    def part(mid):
        text, op_name, category, flops, byts, _source = meta.get(
            mid, _NO_META)
        kind = R.kind_of(text)
        if kind == "control":
            return None
        toks = S.tokens(op_name)
        return (names_of(toks), pass_of(toks), kind, category), flops, byts

    by_op, events = Counter(), Counter()
    for mid, ns in R.self_times(ops):
        found = part(mid)
        if found is None:
            continue
        key, flops, byts = found
        out["part_ns"][key] += ns
        out["work_ns"] += ns
        out["flops"][key[3]] += flops
        out["bytes"][key[3]] += byts
        if key[2] != "kernel":
            by_op[mid] += ns
            events[mid] += 1
    out["ops"] = [
        (ns, events[mid], R.short_name(meta[mid][0]), part(mid)[0],
         meta[mid][3], meta[mid][4], meta[mid][5])
        for mid, ns in by_op.most_common(N_OPS) if mid in meta]
    return out


def select(device: dict, names=(), without_names=False, passes=None,
           kinds=None, categories=None, without_categories=None):
    """The ns of ``device``'s parts that carry every one of ``names`` (and,
    ``without_names``, no other ``hvd_*`` name), of a pass in ``passes``, a
    kind in ``kinds``, a category in ``categories`` and not in
    ``without_categories`` (None: any); None where no part matches.  A part
    whose trace gave no category matches neither test of categories."""
    names = frozenset(names)
    by_category = categories is not None or without_categories is not None
    found = [ns for (have, p, k, c), ns in device["part_ns"].items()
             if names <= have and not (without_names and have != names)
             and (passes is None or p in passes)
             and (kinds is None or k in kinds)
             and not (by_category and c is None)
             and (categories is None or c in categories)
             and (without_categories is None or c not in without_categories)]
    return sum(found) if found else None


def label_of(names) -> str:
    return "+".join(sorted(n[len(_PREFIX):] for n in names)) or "(none)"


def tables(device: dict) -> list[str]:
    """Device ms a step by category x pass (with XLA's own GFLOP and GB a
    step for the row) and by names x pass, the attention block's unnamed
    remainder, and the longest operations that are no Pallas kernel with
    what the trace says of each, for the log."""
    steps = device["n_programs"] or 1
    parts = device["part_ns"]

    def ms(ns):
        return f"{ns / steps / 1e6:11.3f}"

    def grid(title, width, key_of, label, extra=None):
        rows = Counter()
        for key, ns in parts.items():
            rows[key_of(key), key[1]] += ns
        order = sorted({r for r, _ in rows},
                       key=lambda r: -sum(rows[r, p] for p in PASSES))
        out = [f"{title:<{width}}" + "".join(f"{p:>11}" for p in PASSES)
               + f"{'all':>11}" + (extra[0] if extra else "")]
        for r in order:
            out.append(f"{label(r):<{width}}" + "".join(
                ms(rows[r, p]) for p in PASSES)
                + ms(sum(rows[r, p] for p in PASSES))
                + (extra[1](r) if extra else ""))
        out.append(f"{'all':<{width}}" + "".join(
            ms(sum(rows[r, p] for r in order)) for p in PASSES)
            + ms(sum(rows.values())))
        return out

    def cost(category):
        return (f"{device['flops'][category] / steps / 1e9:12.1f}"
                f"{device['bytes'][category] / steps / 1e9:10.2f}")

    out = grid("category", 28, lambda k: k[3],
               lambda c: c if c is not None else "(none in the trace)",
               (f"{'XLA GFLOP':>12}{'XLA GB':>10}", cost))
    out += grid("names", 52, lambda k: k[0], label_of)
    by_pass = Counter()
    for (have, p, k, c), ns in parts.items():
        if ("hvd_attn" in have and k == "compute" and c is not None
                and c not in MATMUL and not have & set(ATTN_PARTS)):
            by_pass[p] += ns
    if by_pass:
        out.append(
            "hvd_attn, compute that is no matmul and under none of "
            f"{', '.join(n[len(_PREFIX):] for n in ATTN_PARTS)}: "
            + ", ".join(f"{p} {by_pass[p] / steps / 1e6:.3f}"
                        for p in PASSES if by_pass[p])
            + f", all {sum(by_pass.values()) / steps / 1e6:.3f}")
    if device.get("ops"):
        out.append(f"{'ms':>9}{'calls':>6}  operation, category, pass, names, "
                   "XLA GFLOP and GB a call, source")
    for ns, n, name, (have, which, _k, category), flops, byts, source in (
            device.get("ops", ())):
        out.append(
            f"{ns / steps / 1e6:9.3f}{n / steps:6g}  {name}, {category}, "
            f"{which}, {label_of(have)}, {flops / 1e9:.1f}, {byts / 1e9:.3f}, "
            + (source.rsplit("/", 1)[-1] or "-"))
    return out


# -- this process's trace ---------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _classified(path: str, mtime_ns: int, steps: tuple) -> dict:
    """``classify_device`` of the planes in ``steps`` ((device id, whole
    steps, ``scopes.py``'s work ns) triples), once for the readers that
    share it; the tables go to the log as it is first read."""
    t0 = time.perf_counter()
    planes = read_device_planes(path)
    devices = {dev: {**classify_device(planes[dev]["lines"],
                                       planes[dev]["meta"]),
                     "n_programs": n}
               for dev, n, _work in steps if dev in planes}
    if devices:
        worst = max(devices.values(), key=lambda d: d["work_ns"])
        theirs = {dev: work for dev, _n, work in steps}
        S.say("device ms a step by XLA's category, the pass and the "
              f"program's names, busiest device ({worst['n_programs']} whole "
              "steps; the totals are scopes.py's "
              + ("to the nanosecond" if all(
                  d["work_ns"] == theirs[dev] for dev, d in devices.items())
                 else f"NOT: {theirs} there") + "):")
        for row in tables(worst):
            S.say("  " + row)
    S.say(f"read {path} by part in {time.perf_counter() - t0:.2f} s")
    return {"devices": devices}


def classified(layers) -> dict | None:
    """``{"devices": {id: classify_device(...) + n_programs}}``, the shape
    ``reduce.over_devices`` takes, for the traced run ``scopes.classified``
    read: the same file, devices and whole steps.  None where that gave
    nothing."""
    base = S.classified(layers)
    if base is None:
        return None
    trace_dir = loader.load_code("runners", "train").TRACE_DIR
    path = S.newest_trace(trace_dir, S.process_start() - 1.0)
    if path is None:
        return None
    return _classified(path, os.stat(path).st_mtime_ns, tuple(sorted(
        (i, d["n_programs"], d["work_ns"])
        for i, d in base["devices"].items())))


def ms_per_step(layers, metric, names=(), without_names=False, passes=None,
                kinds=None, categories=None, without_categories=None):
    """``select`` on the worst device in milliseconds a whole step; None
    where nothing matches on any device."""
    def value(d):
        ns = select(d, names, without_names, passes, kinds, categories,
                    without_categories)
        return None if ns is None else R.per_step_ms(d, ns)
    return R.over_devices(classified(layers), metric["better"], value)
