"""Device time of the parts a block-diffusion step adds inside ``hvd_attn``,
by the scopes the program gives them (``models/transformer.py``): the
per-head QK-norm ``hvd_attn_qknorm`` and the rotary positions
``hvd_attn_rope``.  (The three flash kernels of a call with the
block-diffusion mask, ``hvd_flash_fwd_bd`` / ``hvd_flash_bwd_dq_bd`` /
``hvd_flash_bwd_dkv_bd``, are every attention kernel such a step runs:
``scopes.py`` matches kernels by prefix, so the accepted ``attn_*`` readers
are theirs, and the held experts' block is ``trace/moe.py``'s.)

The same events, window, whole steps and self-time rule as ``scopes.py``,
``trace/moe.py`` and ``trace/laguna.py``: ``scopes.read_device_planes``,
``scopes.tokens``, the devices and step counts ``scopes.classified`` settled
on, ``reduce.self_times``.  A name counts wherever it sits in the
``op_name`` path (forward, the block's recompute under remat and the
backward pass all carry it).

A program without these names (every other family; this repository before
them) has no such time: the reader returns nothing and does not raise.
"""

from __future__ import annotations

import functools
import os
from collections import Counter

from benchmark import loader
from benchmark.trace import reduce as R
from benchmark.trace import scopes as S

NAMES = ("hvd_attn_qknorm", "hvd_attn_rope")


def classify_device(lines: dict, meta: dict) -> dict:
    """One device plane's self time inside ``scopes.classify_device``'s
    window: ``name_ns`` by each of ``NAMES`` that occurs."""
    ops = lines.get(R.OPS_LINE, [])
    out = {"name_ns": Counter()}
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
    def names_of(mid):
        text, op_name = meta.get(mid, ("", ""))
        if R.kind_of(text) == "control":
            return ()
        toks = S.tokens(op_name)
        return tuple(n for n in NAMES if n in toks)

    for mid, ns in R.self_times(ops):
        for name in names_of(mid):
            out["name_ns"][name] += ns
    return out


@functools.lru_cache(maxsize=1)
def _classified(path: str, mtime_ns: int, steps: tuple) -> dict:
    """``classify_device`` of the planes in ``steps`` ((device id, whole
    steps) pairs); the split goes to the log as it is first read."""
    planes = S.read_device_planes(path)
    devices = {dev: {**classify_device(planes[dev]["lines"],
                                       planes[dev]["meta"]),
                     "n_programs": n}
               for dev, n in steps if dev in planes}
    if any(d["name_ns"] for d in devices.values()):
        worst = max(devices.values(),
                    key=lambda d: sum(d["name_ns"].values()))
        n = worst["n_programs"] or 1
        S.say("the attention block's norm and rotation, device ms a step on "
              "their busiest device: " + ", ".join(
                  f"{k} {v / n / 1e6:.3f}"
                  for k, v in sorted(worst["name_ns"].items())))
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


def names_ms_per_step(layers, metric, names):
    """The self time under ``names`` together on the worst device, in
    milliseconds a whole step; None unless the program has every one of
    them."""
    def value(d):
        found = [d["name_ns"].get(n, 0) for n in names]
        return R.per_step_ms(d, sum(found)) if all(found) else None
    return R.over_devices(classified(layers), metric["better"], value)
