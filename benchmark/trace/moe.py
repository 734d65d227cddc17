"""Device time of a dropless MoE block's parts, by the names the program
gives them.

``scopes.py`` sorts self time by the five blocks of a step; its ``BLOCKS``
is fixed.  Inside ``hvd_mlp`` the program (``parallel/moe.dropless_moe``)
names three parts, ``hvd_moe_route`` / ``hvd_moe_dispatch`` /
``hvd_moe_experts``, and this module sorts the same events, inside the same
window, with the same self-time rule, by those: it takes
``scopes.read_device_planes``, ``scopes.tokens``, the devices, windows and
step counts ``scopes.classified`` settled on, and ``reduce.self_times``.

One thing a name cannot give.  The grouped matmuls are ``lax.ragged_dot``,
which XLA's TPU compiler turns into Mosaic kernels of its own
(``%ragged-dot-none.N = ... custom_call_target="tpu_custom_call"``, and a
small ``%ragged-dot-metadata``) and whose ``op_name`` it overwrites with
``ragged-dot-none``: the scope path, ``jvp`` / ``transpose`` included, is
gone from them.  They are recognised here by their instruction's name and
counted as ``hvd_moe_experts`` (and as part of the ``hvd_mlp`` block, which
``scopes.py`` cannot see them in).  ``scopes.py`` itself files them under
block ``none``, phase ``other``, kind ``kernel``.

A program without these names (every other family; this repository before
them) has no part: the readers return nothing and do not raise.
"""

from __future__ import annotations

import functools
import os
from collections import Counter

from benchmark import loader
from benchmark.trace import reduce as R
from benchmark.trace import scopes as S

PARTS = ("hvd_moe_route", "hvd_moe_dispatch", "hvd_moe_experts")
BLOCK = "hvd_mlp"
GROUPED_MATMUL = "ragged-dot"      # XLA's name for a lowered lax.ragged_dot


def is_grouped_matmul(text: str) -> bool:
    return R.instruction(text)[0].startswith(GROUPED_MATMUL)


def part_of(text: str, op_name: str) -> str | None:
    """Which of ``PARTS`` an event belongs to, or None."""
    if is_grouped_matmul(text):
        return "hvd_moe_experts"
    toks = S.tokens(op_name)
    return next((p for p in PARTS if p in toks), None)


def classify_device(lines: dict, meta: dict) -> dict:
    """One device plane's self time inside ``scopes.classify_device``'s
    window: ``part_ns`` by part (only the parts that occur), ``block_ns``
    the whole MoE block (``hvd_mlp`` and the grouped matmuls), and
    ``grouped_matmul_ns`` the kernels alone."""
    ops = lines.get(R.OPS_LINE, [])
    out = {"part_ns": Counter(), "block_ns": 0, "grouped_matmul_ns": 0}
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
    def classes(mid):
        text, op_name = meta.get(mid, ("", ""))
        grouped = is_grouped_matmul(text)
        return (R.kind_of(text) == "control", part_of(text, op_name),
                grouped or BLOCK in S.tokens(op_name), grouped)

    for mid, ns in R.self_times(ops):
        control, part, in_block, grouped = classes(mid)
        if control:
            continue
        if part:
            out["part_ns"][part] += ns
        if in_block:
            out["block_ns"] += ns
        if grouped:
            out["grouped_matmul_ns"] += ns
    return out


@functools.lru_cache(maxsize=1)
def _classified(path: str, mtime_ns: int, steps: tuple) -> dict:
    """``classify_device`` of the planes in ``steps`` ((device id, whole
    steps) pairs), once for the four readers that share it; the split goes
    to the log as it is first read."""
    planes = S.read_device_planes(path)
    devices = {dev: {**classify_device(planes[dev]["lines"],
                                       planes[dev]["meta"]),
                     "n_programs": n}
               for dev, n in steps if dev in planes}
    if any(d["block_ns"] for d in devices.values()):
        worst = max(devices.values(), key=lambda d: d["block_ns"])
        n = worst["n_programs"] or 1
        S.say("MoE block, device ms a step on its busiest device: "
              + ", ".join(f"{k} {v / n / 1e6:.3f}" for k, v in (
                  [("block", worst["block_ns"])]
                  + sorted(worst["part_ns"].items())
                  + [("grouped-matmul kernels among hvd_moe_experts",
                      worst["grouped_matmul_ns"])])))
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
        (i, d["n_programs"]) for i, d in base["devices"].items())))


def ms_per_step(layers, metric, ns_of):
    """``ns_of(device)`` on the worst device in milliseconds a whole step;
    None where it gives None or 0 (the names do not occur)."""
    def value(d):
        ns = ns_of(d)
        return R.per_step_ms(d, ns) if ns else None
    return R.over_devices(classified(layers), metric["better"], value)
