#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on the machine it is started on.  It reads the cell, its
configuration and the metrics the cell reports from ``BENCHMARK.json`` and
the data files that names, hands them to the cell's runner
(``runners/<runner>.py``), and prints the contract's one JSON line last:
the cell's end-to-end metrics without a trace, its per-layer metrics (one
reader each, ``metrics/<metric>.py``) with one.  It holds no name of a cell,
configuration, family or metric.

It measures TPUs only: another platform, a ``device_kind`` that
``peaks.json`` does not hold, or fewer chips than the cell asks for, end it
with a non-zero code and no result line.  There is no CPU mode on the
command line.  The test suite rehearses the control flow on the CPU through
``main(argv, rehearsal=Rehearsal(...))``, an argument only a Python caller
can pass; a rehearsal's line carries counts and no device metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # process start, for setup_s

import argparse                        # noqa: E402
import json                            # noqa: E402
import math                            # noqa: E402
import sys                             # noqa: E402
from dataclasses import dataclass, field   # noqa: E402
from pathlib import Path               # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import loader           # noqa: E402
from benchmark.spans import Spans      # noqa: E402


@dataclass
class Rehearsal:
    """What only the tests pass: tiny sizes laid over the configuration
    (``sizes``) and the traffic mix (``traffic``), the directory that stands
    for the checkout's data, and shorter warm-up and trace.  Its presence is
    the permission to run off the TPU, and it marks the result
    ``rehearsal``."""
    sizes: dict = field(default_factory=dict)
    traffic: dict = field(default_factory=dict)
    data_root: Path = loader.REPO_ROOT
    warmup_steps: int = 1
    trace_steps: int = 2


def say(msg: str) -> None:
    print(f"benchmark: {msg}", flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return ap.parse_args(argv)


def check_devices(chips: int, rehearsal) -> dict:
    """The devices this run may use, or BenchmarkError."""
    import jax
    devices = jax.devices()
    found = sorted({d.platform for d in devices})
    if rehearsal is None and found != ["tpu"]:
        raise loader.BenchmarkError(
            f"the benchmark measures TPUs and has no CPU mode; jax found "
            f"{len(devices)} device(s) of platform {'/'.join(found)}")
    if len(devices) < chips:
        raise loader.BenchmarkError(
            f"the cell asks for {chips} chip(s) and jax found {len(devices)}")
    kind = devices[0].device_kind
    return {"devices": devices[:chips], "platform": devices[0].platform,
            "kind": kind, "count": len(devices),
            "peaks": loader.load_peaks(kind) if rehearsal is None else None}


def pick_metrics(wanted: list, values: dict) -> tuple[dict, list]:
    """name -> {value, unit} for every wanted metric that has a finite
    value, and the names of those that have none."""
    out, missing = {}, []
    for m in wanted:
        v = values.get(m["name"])
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        else:
            missing.append(m["name"])
    return out, missing


def cell_metrics(cell: dict, result: dict, trace: bool, rehearsal) -> dict:
    """The line's metrics: per layer with a trace, one reader each, else
    end to end.  A reader that found nothing to read returns nothing and its
    metric is left out, said aloud; a measured run with an end-to-end metric
    missing, or with no per-layer metric at all, has no result."""
    if trace:
        wanted = cell["per_layer"]
        values = {m["name"]: loader.load_code("metrics", m["name"]).read(
            result["layers"], m) for m in wanted}
    else:
        wanted, values = cell["end_to_end"], result["end_to_end"]
    metrics, missing = pick_metrics(wanted, values)
    if missing:
        say(f"no value for {missing}: left out of the line")
        if rehearsal is None and (not trace or not metrics):
            raise loader.BenchmarkError(
                f"the run gave no value for {missing}")
    return metrics


def main(argv=None, rehearsal: Rehearsal | None = None) -> int:
    args = parse(argv)
    spans = Spans(T_START if rehearsal is None else time.perf_counter())
    try:
        cell = loader.load_cell(
            args.workload,
            loader.REPO_ROOT if rehearsal is None else rehearsal.data_root)
        if rehearsal is not None:
            cell["config"] = {**cell["config"], **rehearsal.sizes}
            cell["traffic"] = {**cell["traffic"], **rehearsal.traffic}
        chips = cell["entry"]["chips"]
        runner = loader.load_code("runners", cell["traffic"]["runner"])
        result = runner.run(
            cell=cell, find_devices=lambda: check_devices(chips, rehearsal),
            spans=spans, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), rehearsal=rehearsal, say=say)
        metrics = cell_metrics(cell, result, bool(args.trace), rehearsal)
    except loader.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1

    if rehearsal is not None:
        # A CPU number is never written under a device metric's name: a
        # rehearsal keeps the names it has a value for and drops the values.
        metrics = {"rehearsal_names": sorted(metrics)}

    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics,
            "device": result["device"]}
    if rehearsal is not None:
        line["rehearsal"] = True
        line["device"] = {k: line["device"][k]
                          for k in ("platform", "kind", "count")}
    elif args.trace and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
