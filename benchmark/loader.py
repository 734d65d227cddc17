"""Finds the benchmark's files by the names ``BENCHMARK.json`` gives.

Data (``BENCHMARK.json``, a configuration's file, a traffic mix's file) is read
under ``data_root``, which is the checkout unless a test passes another
directory; code (a family, its reference, a runner, a metric's reader) is
always this directory's own, loaded from ``<kind>/<name>.py``.  Nothing here
knows the name of any cell, configuration, family or metric.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
REPO_ROOT = BENCH_DIR.parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")


class BenchmarkError(Exception):
    """The benchmark's own files are wrong or a name finds nothing."""


def read_json(path: Path) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise BenchmarkError(f"cannot read {path}: {e}") from e


def checked(name: str) -> str:
    """A name as the contract spells one, safe as a file's stem."""
    if not NAME.match(name):
        raise BenchmarkError(f"not a name: {name!r}")
    return name


def load_code(kind: str, name: str) -> ModuleType:
    """``benchmark/<kind>/<name>.py`` as a module, loaded once."""
    path = BENCH_DIR / kind / f"{checked(name)}.py"
    key = f"_benchmark_{kind}_{name}".replace("-", "_").replace(".", "_")
    if key in sys.modules:
        return sys.modules[key]
    if not path.is_file():
        raise BenchmarkError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


def load_benchmark(data_root: Path = REPO_ROOT) -> dict:
    return read_json(Path(data_root) / "BENCHMARK.json")


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchmarkError(
        f"no {what} named {name!r}; there are {[e['name'] for e in entries]}")


def load_cell(workload: str, data_root: Path = REPO_ROOT) -> dict:
    """Everything data says about one cell: its ``BENCHMARK.json`` entry,
    its traffic mix's file, its configuration's file, and the metrics it
    reports."""
    data_root = Path(data_root)
    bench = load_benchmark(data_root)
    entry = find(bench["workloads"], workload, "workload")
    conf_entry = find(bench["configs"], entry["config"], "configuration")
    traffic = read_json(data_root / bench["paths"][0] / "traffic"
                        / f"{checked(entry['traffic'])}.json")
    config = read_json(data_root / conf_entry["file"])

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"name": workload, "entry": entry, "traffic": traffic,
            "config": config,
            "config_entry": conf_entry,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def load_peaks(device_kind: str) -> dict:
    """The peak table's row for exactly this ``device_kind``.  A device
    that is not in the table is an error, never a default, and no
    environment variable is read."""
    table = read_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchmarkError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"(it holds {sorted(table)})")
    return table[device_kind]
