"""Device time of a patterned model's blocks and their parts, by the names
the program gives them (``models/transformer.py``): the state-space block
``hvd_ssm`` with ``hvd_ssm_conv`` and ``hvd_ssm_scan`` inside it, and inside
the expert block ``hvd_mlp`` the latent projections ``hvd_moe_latent`` and
the shared expert ``hvd_moe_shared``.

``scopes.py`` sorts self time by the five blocks of a step and its
``BLOCKS`` is fixed; ``hvd_ssm`` is none of them, so this module sorts the
same events, inside the same window, with the same self-time rule, by these
names, as ``trace/moe.py`` does for a dropless MoE's three parts: it takes
``scopes.read_device_planes``, ``scopes.tokens``, the devices, windows and
step counts ``scopes.classified`` settled on, and ``reduce.self_times``.  A
name counts wherever it sits in the ``op_name`` path (forward, the block's
recompute under remat and the backward pass all carry it).

A program without these names (every other family; this repository before
them) has no such time: the readers return nothing and do not raise.
"""

from __future__ import annotations

import functools
import os
from collections import Counter

from benchmark import loader
from benchmark.trace import reduce as R
from benchmark.trace import scopes as S

NAMES = ("hvd_ssm", "hvd_ssm_conv", "hvd_ssm_scan", "hvd_moe_latent",
         "hvd_moe_shared")


def classify_device(lines: dict, meta: dict) -> dict:
    """One device plane's self time inside ``scopes.classify_device``'s
    window by each of ``NAMES`` that occurs (``name_ns``).  A name inside
    another (the scan inside the block) counts under both."""
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
    def names(mid):
        text, op_name = meta.get(mid, ("", ""))
        if R.kind_of(text) == "control":
            return ()
        toks = S.tokens(op_name)
        return tuple(n for n in NAMES if n in toks)

    for mid, ns in R.self_times(ops):
        for name in names(mid):
            out["name_ns"][name] += ns
    return out


@functools.lru_cache(maxsize=1)
def _classified(path: str, mtime_ns: int, steps: tuple) -> dict:
    """``classify_device`` of the planes in ``steps`` ((device id, whole
    steps) pairs), once for the readers that share it; the split goes to
    the log as it is first read."""
    planes = S.read_device_planes(path)
    devices = {dev: {**classify_device(planes[dev]["lines"],
                                       planes[dev]["meta"]),
                     "n_programs": n}
               for dev, n in steps if dev in planes}
    if any(d["name_ns"] for d in devices.values()):
        worst = max(devices.values(),
                    key=lambda d: sum(d["name_ns"].values()))
        n = worst["n_programs"] or 1
        S.say("state-space and latent-expert parts, device ms a step on "
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


def ms_per_step(layers, metric, name: str):
    """The self time under ``name`` on the worst device in milliseconds a
    whole step; None where the name does not occur."""
    def value(d):
        ns = d["name_ns"].get(name)
        return R.per_step_ms(d, ns) if ns else None
    return R.over_devices(classified(layers), metric["better"], value)
