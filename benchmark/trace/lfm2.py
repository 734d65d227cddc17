"""Device time of the gated short-convolution blocks of a step, by the scopes
the program gives them (``models/transformer.py`` ``_conv_mixer``):
``hvd_conv`` the whole block (its norm, both projections, the gate path, the
residual add) and inside it ``hvd_conv_gate``, everything between the two
matmuls (``B * u``, the causal depthwise convolution, ``C *`` its result);
and the least bytes that gate path must move, for its share of the
roofline.

The same events, window, whole steps and self-time rule as ``scopes.py``,
``trace/moe.py``, ``trace/laguna.py`` and ``trace/sdar.py``:
``scopes.read_device_planes``, ``scopes.tokens``, the devices and step counts
``scopes.classified`` settled on, ``reduce.self_times``.  A name counts
wherever it sits in the ``op_name`` path (forward, the block's recompute
under remat and the backward pass all carry it).

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

BLOCK, GATE = "hvd_conv", "hvd_conv_gate"
NAMES = (BLOCK, GATE)
# The key of the gate path's cost in the dict ``Family.attention_cost``
# returns (the runner hands readers that dict only).
GATE_COST = "short_conv_gate"
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def gate_path_cost(c: dict, n_blocks: int, tokens_per_device: float) -> dict:
    """What the gate path of ``n_blocks`` convolution blocks needs a step on
    one device, forward, the block's recompute under remat (a step runs the
    forward twice: the least a checkpoint a block allows) and backward.  It
    is elementwise but for ``taps`` neighbours along the sequence, which a
    pass keeps on chip, so the least traffic reads and writes each array
    once in the compute type: forward reads B, C, u and writes the gated
    result (4 d values a token), the recompute the same, backward reads B,
    C, u and the result's cotangent and writes dB, dC, du (7 d).  The filter
    and its gradient (d x taps) are nothing beside them.  FLOPs: two
    products and ``taps`` multiply-adds a channel forward, about three times
    that backward; never the bound."""
    d, taps = c["d_model"], c["conv_taps"]
    item = DTYPE_BYTES[c["dtype"]]
    values = (4 + 4 + 7) * d
    forward = (2 + 2 * taps) * d
    return {"flops": n_blocks * tokens_per_device * 5.0 * forward,
            "bytes": n_blocks * tokens_per_device * values * item}


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
        S.say("the convolution blocks and their gate path, device ms a step "
              "on their busiest device: " + ", ".join(
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


def ms_per_step(layers, metric, name):
    """The self time under ``name`` on the worst device, in milliseconds a
    whole step; None where the program has no such name."""
    def value(d):
        ns = d["name_ns"].get(name, 0)
        return R.per_step_ms(d, ns) if ns else None
    return R.over_devices(classified(layers), metric["better"], value)
