"""Device time of the parts learned sparse attention adds to a step, by the
names the program gives them (``ops/flash_attention.py``,
``ops/sparse_index.py``, ``models/transformer.py``): the three flash kernels
of a selected call ``hvd_flash_fwd_sel`` / ``hvd_flash_bwd_dq_sel`` /
``hvd_flash_bwd_dkv_sel`` (``scopes.py`` matches kernels by prefix, so its
``attn_*`` readers count them too; here they are taken by their whole name),
and inside ``hvd_attn`` the indexer ``hvd_attn_index`` (its projections,
its scores, the choice), inside that the ranking alone ``hvd_attn_select``,
and the indexer's loss ``hvd_attn_index_loss``, forward and backward.

The same events, window, whole steps and self-time rule as ``scopes.py``,
``trace/sdar.py`` and ``trace/laguna.py``: ``scopes.read_device_planes``,
``scopes.tokens``, the devices and step counts ``scopes.classified`` settled
on, ``reduce.self_times``.  A name counts wherever it sits in the
``op_name`` path (forward, the block's recompute under remat and the
backward pass all carry it).

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

SEL_KERNELS = ("hvd_flash_fwd_sel", "hvd_flash_bwd_dq_sel",
               "hvd_flash_bwd_dkv_sel")
NAMES = ("hvd_attn_index", "hvd_attn_select", "hvd_attn_index_loss")
COST = "sparse_attention"      # its key in ``attention_cost()``'s dict


def sel_kernel_of(text: str) -> str | None:
    """Which of ``SEL_KERNELS`` an event's HLO text is, or None: a Pallas
    kernel whose instruction carries that whole name (``name.N``)."""
    if R.kind_of(text) != "kernel":
        return None
    name = R.instruction(text)[0].split(".")[0]
    return name if name in SEL_KERNELS else None


def classify_device(lines: dict, meta: dict) -> dict:
    """One device plane's self time inside ``scopes.classify_device``'s
    window: ``name_ns`` by each of ``NAMES`` that occurs, ``kernel_ns`` by
    selected kernel."""
    ops = lines.get(R.OPS_LINE, [])
    out = {"name_ns": Counter(), "kernel_ns": Counter()}
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
        if R.kind_of(text) == "control":
            return (), None
        toks = S.tokens(op_name)
        return tuple(n for n in NAMES if n in toks), sel_kernel_of(text)

    for mid, ns in R.self_times(ops):
        names, kernel = classes(mid)
        for name in names:
            out["name_ns"][name] += ns
        if kernel:
            out["kernel_ns"][kernel] += ns
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
    if any(d["kernel_ns"] or d["name_ns"] for d in devices.values()):
        worst = max(devices.values(),
                    key=lambda d: sum(d["kernel_ns"].values()))
        n = worst["n_programs"] or 1
        S.say("selected kernels and the indexer's parts, device ms a step "
              "on their busiest device: " + ", ".join(
                  f"{k} {v / n / 1e6:.3f}" for k, v in
                  sorted(worst["kernel_ns"].items())
                  + sorted(worst["name_ns"].items())))
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
    None where it gives 0 (the names do not occur)."""
    def value(d):
        ns = ns_of(d)
        return R.per_step_ms(d, ns) if ns else None
    return R.over_devices(classified(layers), metric["better"], value)


def kernels_ms_per_step(layers, metric):
    return ms_per_step(layers, metric,
                       lambda d: sum(d["kernel_ns"].values()))


def name_ms_per_step(layers, metric, name):
    return ms_per_step(layers, metric, lambda d: d["name_ns"].get(name, 0))
