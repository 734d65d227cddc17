"""``benchmark/trace/parts.py``: device time by everything a trace says of an
operation (the program's names, the pass with the recompute told apart, the
kind, XLA's ``hlo_category``), on hand-made parts, on a hand-encoded
``XSpace`` and on the traces recorded on the chip
(``benchmark/trace/testdata``), whose totals must be ``scopes.py``'s and
``reduce.py``'s to the nanosecond; and the six metrics that read it."""

from __future__ import annotations

import gzip
import json
import os
import sys
from collections import Counter
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader                      # noqa: E402
from benchmark.trace import parts as P            # noqa: E402
from benchmark.trace import reduce as R           # noqa: E402
from benchmark.trace import scopes as S           # noqa: E402

TESTDATA = Path(R.__file__).resolve().parent / "testdata"
# The same tiny flagship step (2 layers, dp 2 x mp 2, four v5e chips), PR
# 22's before the program named anything and PR 24's with the step scopes;
# both before the three names of PR 52.
UNNAMED = TESTDATA / "flagship-tiny-dp2mp2.xplane.pb.gz"
NAMED = TESTDATA / "flagship-tiny-dp2mp2-named.xplane.pb.gz"
# A tiny grouped-query patterned step ("*D" x 2, 4 query heads on 2 K / V
# heads, one v5e chip) recorded with PR 52's names in it.
GQA = TESTDATA / "gqa-tiny-1chip-parts.xplane.pb.gz"
RECORDED = [gz for gz in (UNNAMED, NAMED, GQA) if gz.exists()]
CELLS = [w["name"] for w in loader.load_benchmark()["workloads"]]
GQA_CELLS = [c for c in CELLS if c.split("-")[0] in (
    "nemotron", "laguna", "sdar", "lfm2", "smallthinker", "keye")]
METRICS = {
    "recompute_ms_per_step": ("train step", CELLS),
    "xla_matmul_ms_per_step": ("train step", CELLS),
    "xla_copy_ms_per_step": ("train step", CELLS),
    "attn_kv_repeat_ms_per_step": ("train step", GQA_CELLS),
    "attn_delta_ms_per_step": ("attention kernel", CELLS),
    "layer_stack_ms_per_step": ("train step", CELLS),
}


# -- names ---------------------------------------------------------------------

@pytest.mark.parametrize("op_name, which, names", [
    ("jit(step)/jvp(hvd_layers)/while/body/hvd_attn/dot_general", "fwd",
     {"hvd_layers", "hvd_attn"}),
    ("jit(step)/transpose(jvp(hvd_layers))/while/body/checkpoint/"
     "rematted_computation/hvd_attn/hvd_attn_kv_repeat/broadcast_in_dim",
     "recompute", {"hvd_layers", "hvd_attn", "hvd_attn_kv_repeat"}),
    ("jit(step)/transpose(jvp(hvd_layers))/while/body/hvd_attn/"
     "hvd_attn_delta/reduce_sum", "bwd",
     {"hvd_layers", "hvd_attn", "hvd_attn_delta"}),
    ("jit(step)/transpose(jvp(hvd_layers))/while/body/dynamic_slice", "bwd",
     {"hvd_layers"}),
    ("jit(step)/hvd_optimizer/mul", "optimizer", {"hvd_optimizer"}),
    # A token is tested whole: neither a prefix nor a substring counts.
    ("jit(step)/not_rematted_computation/nothvd_x/mul", "other", set()),
    ("", "other", set()),
])
def test_pass_and_names_of_an_op_name(op_name, which, names):
    toks = S.tokens(op_name)
    assert (P.pass_of(toks), P.names_of(toks)) == (which, names)


# -- hand-made parts ------------------------------------------------------------

FUSION = ('%fusion.7 = bf16[8192,2048]{1,0:T(8,128)(2,1)} '
          'fusion(bf16[8192,2048]{1,0} %p.1), kind=kLoop')
WHILE = ('%while.1 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1), '
         'condition=%cond, body=%body')
KERNEL = ('%hvd_flash_bwd_dkv.2 = (bf16[1,8192,1024]{2,1,0}) custom-call(%q),'
          ' custom_call_target="tpu_custom_call"')
GATHER = ('%all-gather.3 = bf16[5,8192,1024]{2,1,0} all-gather('
          'bf16[5,4096,1024]{2,1,0:T(8,128)(2,1)} %x), dimensions={1}')
FWD = "jit(train_step)/jvp(hvd_layers)/while/body/checkpoint/"
REMAT = ("jit(train_step)/transpose(jvp(hvd_layers))/while/body/checkpoint/"
         "rematted_computation/")
BWD = "jit(train_step)/transpose(jvp(hvd_layers))/while/body/checkpoint/"
SOURCE = "/root/repo/horovod_tpu/models/transformer.py:870"
# metadata id: (HLO text, op_name, category, flops, bytes), ns a step; every
# one carries ``SOURCE`` as its sixth
PARTS = {
    1: ((WHILE, "jit(train_step)/jvp(hvd_layers)/while", "while", 0, 0), 0),
    2: ((FUSION, FWD + "hvd_attn/dot_general", "convolution fusion", 900, 90),
        40),
    3: ((FUSION, FWD + "hvd_attn/hvd_attn_kv_repeat/broadcast_in_dim",
         "data formatting", 0, 64), 6),
    4: ((FUSION, REMAT + "hvd_attn/hvd_attn_kv_repeat/broadcast_in_dim",
         "loop fusion", 0, 64), 7),
    5: ((FUSION, BWD + "hvd_attn/hvd_attn_kv_repeat/reduce_sum",
         "loop fusion", 8, 64), 9),
    6: ((FUSION, BWD + "hvd_attn/hvd_attn_delta/reduce_sum", "loop fusion",
         16, 128), 11),
    7: ((KERNEL, BWD + "hvd_attn/hvd_flash_bwd_dkv/pallas_call",
         "custom-call", 0, 0), 30),
    8: ((FUSION, BWD + "dynamic_update_slice", "dynamic-update-slice", 0, 32),
        13),
    9: ((FUSION, BWD + "dot_general", "convolution fusion", 700, 70), 17),
    10: ((FUSION, REMAT + "dynamic_slice", "data formatting", 0, 32), 5),
    11: ((GATHER, BWD + "all_gather", "all-gather", 0, 16), 3),
    12: ((FUSION, BWD + "hvd_attn/mul", "loop fusion", 4, 8), 2),
    13: ((FUSION, "", None, 0, 0), 19),       # the compiler's own copy
}


def synthetic_device(meta_of=lambda m: m):
    """Two whole steps of 200 ns; a third operation before the first."""
    meta = {mid: meta_of(m + (SOURCE,)) for mid, (m, _ns) in PARTS.items()}
    ops = []
    for t0 in (1000, 1200):
        ops.append((1, t0, t0 + 170))
        t = t0 + 2
        for mid, (_m, ns) in PARTS.items():
            if mid != 1:
                ops.append((mid, t, t + ns))
                t += ns
    ops.append((2, 900, 990))
    return ({R.OPS_LINE: ops,
             R.STEPS_LINE: [("s", 1000, 1200), ("s", 1200, 1400)]}, meta)


def test_classify_device_files_every_part_once():
    d = P.classify_device(*synthetic_device())
    assert d["work_ns"] == sum(d["part_ns"].values()) == 2 * (
        sum(ns for _m, ns in PARTS.values()))
    layers, attn = "hvd_layers", "hvd_attn"
    assert d["part_ns"][frozenset({layers, attn}), "fwd", "compute",
                        "convolution fusion"] == 80
    assert d["part_ns"][frozenset({layers}), "bwd", "collective",
                        "all-gather"] == 6
    assert d["part_ns"][frozenset(), "other", "compute", None] == 38
    # The loop itself is no work, and no part.
    assert not any(c == "while" for (_n, _p, _k, c) in d["part_ns"])
    # XLA's own count of the same events, by category.
    assert d["flops"]["convolution fusion"] == 2 * (900 + 700)
    assert d["bytes"]["loop fusion"] == 2 * (64 + 64 + 128 + 8)
    assert P.classify_device({}, {})["part_ns"] == {}


@pytest.mark.parametrize("asked, ns", [
    (dict(), 2 * 162),
    (dict(passes=("recompute",)), 2 * (7 + 5)),
    (dict(passes=("bwd",)), 2 * (9 + 11 + 30 + 13 + 17 + 3 + 2)),
    (dict(kinds=("compute",), categories=P.MATMUL), 2 * (40 + 17)),
    (dict(kinds=("compute",), categories=P.COPY), 2 * (6 + 5)),
    (dict(names=("hvd_attn_kv_repeat",)), 2 * (6 + 7 + 9)),
    (dict(names=("hvd_attn_delta",)), 2 * 11),
    # The scan's own: under ``hvd_layers`` alone, neither the collective
    # nor the matmul a stack was fused behind.
    (dict(names=("hvd_layers",), without_names=True, kinds=("compute",),
          without_categories=P.MATMUL), 2 * (13 + 5)),
    (dict(names=("hvd_layers",)), 2 * (162 - 19)),
    (dict(without_names=True), 2 * 19),
    (dict(names=("hvd_attn", "hvd_layers"), without_names=True,
          kinds=("compute",)), 2 * (40 + 2)),
    (dict(names=("hvd_mlp",)), None),
    (dict(passes=("optimizer",)), None),
    (dict(categories=("convolution",)), None),
])
def test_select_by_names_pass_kind_and_category(asked, ns):
    d = P.classify_device(*synthetic_device())
    assert P.select(d, **asked) == ns


def test_parts_without_a_category_match_no_test_of_categories():
    d = P.classify_device(*synthetic_device(
        lambda m: (m[0], m[1], None) + m[3:]))
    assert P.select(d, categories=P.MATMUL) is None
    assert P.select(d, names=("hvd_layers",), without_names=True,
                    without_categories=P.MATMUL) is None
    assert P.select(d, passes=("recompute",)) == 2 * (7 + 5)
    assert P.select(d, names=("hvd_attn_delta",)) == 2 * 11


def test_tables_add_up_and_name_the_attention_blocks_remainder():
    d = {**P.classify_device(*synthetic_device()), "n_programs": 2}
    for axis in ("part_ns", "flops", "bytes"):       # a ns as a ms, a G
        d[axis] = Counter({k: n * 10 ** 6 for k, n in d[axis].items()})
    d["ops"] = [(ns * 10 ** 6, n, name, part, flops * 10 ** 9, b * 10 ** 9,
                 at) for ns, n, name, part, flops, b, at in d["ops"]]
    rows = P.tables(d)
    totals = [r for r in rows if r.startswith("all ")]
    assert [r.split()[1:] for r in totals] == 2 * [
        ["46.000", "12.000", "85.000", "0.000", "19.000", "162.000"]]
    assert next(r for r in rows if r.startswith("convolution fusion")
                ).split()[2:] == ["40.000", "0.000", "17.000", "0.000",
                                  "0.000", "57.000", "1.6", "0.16"]
    assert any(r.startswith("attn+attn_kv_repeat+layers") for r in rows)
    assert any(r.startswith("(none in the trace)") for r in rows)
    # ``hvd_attn/mul`` (2 ns a step) is the one operation there that is no
    # matmul, no kernel and under none of the block's inner names.
    rest = next(i for i, r in enumerate(rows)
                if r.startswith("hvd_attn, compute that is no matmul"))
    assert rows[rest].endswith("attn_index_loss: bwd 2.000, all 2.000")
    # Then the longest operations that are no kernel, with what the trace
    # says of each: eleven instructions here, the forward matmul first.
    assert rows[rest + 1].split()[:2] == ["ms", "calls"]
    assert rows[rest + 2].split(maxsplit=2) == [
        "40.000", "1", "fusion.7 fusion, convolution fusion, fwd, "
        "attn+layers, 900.0, 90.000, transformer.py:870"]
    assert rows[-1].split(maxsplit=2)[:2] == ["2.000", "1"]
    assert len(rows) == rest + 2 + 11 and "custom-call" not in "".join(
        rows[rest + 2:])
    assert P.select(d, names=("hvd_attn",), kinds=("compute",),
                    without_categories=P.MATMUL) == 2 * 10 ** 6 * (
                        6 + 7 + 9 + 11 + 2)


# -- the wire format -----------------------------------------------------------------

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        n, low = n >> 7, n & 0x7F
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


TF_OP, CATEGORY, FLOPS, BYTES, AT, STACK, REF = 3, 4, 5, 6, 7, 8, 9


def xspace(with_category: bool) -> bytes:
    """One device plane of ``synthetic_device``'s events: ``hlo_category``
    as a string (by reference for the matmuls), ``flops`` an int64,
    ``bytes_accessed`` a uint64, and a stat the reader does not want."""
    lines, meta = synthetic_device()
    plane = field(1, 1) + field(2, "/device:TPU:0")
    ids = {"s": 99}
    for lname, events in lines.items():
        line = field(2, lname) + field(3, 0)
        for mid, start, end in events:
            line += field(4, field(1, ids.get(mid, mid))
                          + field(2, start * 1000)
                          + field(3, (end - start) * 1000))
        plane += field(3, line)
    for mid, (text, op_name, category, flops, byts, source) in meta.items():
        m = field(1, mid) + field(2, text)
        m += field(5, field(1, STACK) + field(5, source + ":11\n"))
        m += field(5, field(1, AT) + field(5, source))
        if op_name:
            m += field(5, field(1, TF_OP) + field(5, op_name))
        if with_category and category is not None:
            m += field(5, field(1, CATEGORY) + (
                field(7, REF) if category == "convolution fusion"
                else field(5, category)))
        m += field(5, field(1, FLOPS) + field(4, flops))
        m += field(5, field(1, BYTES) + field(3, byts))
        plane += field(4, field(1, mid) + field(2, m))
    plane += field(4, field(1, 99) + field(2, field(1, 99) + field(2, "s")))
    for sid, sname in ((TF_OP, "tf_op"), (FLOPS, "flops"),
                       (BYTES, "bytes_accessed"), (AT, "source"),
                       (STACK, "source_stack"),
                       (REF, "convolution fusion")) + (
                           ((CATEGORY, "hlo_category"),) if with_category
                           else ()):
        plane += field(5, field(1, sid)
                       + field(2, field(1, sid) + field(2, sname)))
    return field(1, plane)


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """The runner's ``TRACE_DIR`` for one test."""
    monkeypatch.setattr(loader.load_code("runners", "train"), "TRACE_DIR",
                        tmp_path)
    S._classified.cache_clear()
    P._classified.cache_clear()
    yield tmp_path
    S._classified.cache_clear()
    P._classified.cache_clear()


def written(trace_dir, space: bytes) -> dict:
    """``space`` as this process's trace; the runner's ``layers``."""
    path = trace_dir / "plugins" / "profile" / "now" / "t.xplane.pb"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(space)
    return {"trace": R.reduce_trace(str(path))}


def read(name: str, layers: dict):
    entry = loader.find(loader.load_benchmark()["per_layer"], name, "metric")
    return loader.load_code("metrics", name).read(layers, entry)


def test_the_wider_metadata_reads_from_the_wire(trace_dir):
    layers = written(trace_dir, xspace(with_category=True))
    path = S.newest_trace(trace_dir, 0.0)
    plane = P.read_device_planes(path)[0]
    _lines, meta = synthetic_device()
    assert plane["meta"] == {**meta, 99: ("s", "", None, 0, 0, "")}
    assert plane["lines"] == S.read_device_planes(path)[0]["lines"]
    d = P.classify_device(plane["lines"], plane["meta"])
    assert d == P.classify_device(*synthetic_device())
    assert d["work_ns"] == layers["trace"]["devices"][0]["busy_ns"]


# ms a step over the synthetic trace's two whole steps of ``PARTS``
@pytest.mark.parametrize("name, with_category, ns", [
    ("recompute_ms_per_step", True, 7 + 5),
    ("xla_matmul_ms_per_step", True, 40 + 17),
    ("xla_copy_ms_per_step", True, 6 + 5),
    ("attn_kv_repeat_ms_per_step", True, 6 + 7 + 9),
    ("attn_delta_ms_per_step", True, 11),
    ("layer_stack_ms_per_step", True, 13 + 5),
    # A profiler that writes no ``hlo_category``: what is read by category
    # has no value, what is read by a token of the ``op_name`` still has.
    ("recompute_ms_per_step", False, 7 + 5),
    ("xla_matmul_ms_per_step", False, None),
    ("xla_copy_ms_per_step", False, None),
    ("attn_kv_repeat_ms_per_step", False, 6 + 7 + 9),
    ("attn_delta_ms_per_step", False, 11),
    ("layer_stack_ms_per_step", False, None),
])
def test_readers_on_a_synthetic_trace_of_this_process(
        trace_dir, capsys, name, with_category, ns):
    layers = written(trace_dir, xspace(with_category))
    got = read(name, layers)
    assert got == (None if ns is None else pytest.approx(ns / 1e6))
    assert read(name, layers) == got          # the second read: no parse
    out = capsys.readouterr().out
    assert out.count("by part in") == 1
    assert out.count("the totals are scopes.py's to the nanosecond") == 1
    assert all(ln.startswith("benchmark: ") for ln in out.splitlines())


# -- the entries ------------------------------------------------------------------

def test_the_six_entries_are_the_benchmarks_last_and_nothing_else_moved():
    bench = json.loads((loader.REPO_ROOT / "BENCHMARK.json").read_text())
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(METRICS)
    assert len(CELLS) == 10 and len(GQA_CELLS) == 6


@pytest.mark.parametrize("name", list(METRICS))
def test_an_entry_names_its_cells_and_reads_nothing_off_the_tpu(
        trace_dir, name):
    layer, cells = METRICS[name]
    entry = loader.find(loader.load_benchmark()["per_layer"], name, "metric")
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": layer,
                     "moves": "tokens_per_s_per_chip", "workloads": cells}
    for cell in CELLS:
        mine = {m["name"] for m in loader.load_cell(cell)["per_layer"]}
        assert (name in mine) == (cell in cells), cell
    written(trace_dir, xspace(with_category=True))     # must not be read
    reader = loader.load_code("metrics", name).read
    for trace in (None, {"devices": {}, "host_spans": []}):
        assert reader({"trace": trace}, entry) is None
    assert P._classified.cache_info().misses == 0


# -- the recorded traces ------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    def unpack(gz: Path) -> str:
        path = (tmp_path_factory.mktemp(gz.name.split(".")[0]) / "plugins"
                / "profile" / "recorded" / "recorded.xplane.pb")
        path.parent.mkdir(parents=True)
        path.write_bytes(gzip.decompress(gz.read_bytes()))
        return str(path)
    paths = {gz: unpack(gz) for gz in RECORDED}
    return {gz: (path, P.read_device_planes(path),
                 S.classify_trace(path)["devices"],
                 R.reduce_trace(path)["devices"])
            for gz, path in paths.items()}


def ids(paths):
    return [p.name.split(".")[0] for p in paths]


@pytest.mark.parametrize("gz", RECORDED, ids=ids(RECORDED))
def test_recorded_totals_are_scopes_and_reduces_to_the_nanosecond(
        recorded, gz):
    assert gz.stat().st_size < 512 * 1024
    path, planes, theirs, reduced = recorded[gz]
    assert sorted(planes) == sorted(theirs)
    for dev, plane in planes.items():
        assert plane["lines"] == S.read_device_planes(path)[dev]["lines"]
        d = P.classify_device(plane["lines"], plane["meta"])
        s, r = theirs[dev], reduced[dev]
        assert d["work_ns"] == s["work_ns"] == r["busy_ns"]
        assert sum(d["part_ns"].values()) == d["work_ns"]
        by = {axis: Counter() for axis in ("pass", "kind", "block")}
        for (names, which, kind, category), ns in d["part_ns"].items():
            assert category is not None       # XProf sorts every operation
            by["pass"][which] += ns
            by["kind"][kind] += ns
            by["block"][S.block_of(names)] += ns
        # Over passes, kinds and categories: scopes.py's and reduce.py's.
        by["pass"]["bwd"] += by["pass"].pop("recompute", 0)
        assert by["pass"] == s["phase_ns"]
        assert all(by["kind"][k] == r["self_ns"][k] for k in S.KINDS)
        by["block"].pop("none", None)
        assert by["block"] == s["block_ns"]


@pytest.mark.parametrize("gz", RECORDED, ids=ids(RECORDED))
def test_recompute_and_parts_backward_are_scopes_backward(recorded, gz):
    _path, planes, theirs, _reduced = recorded[gz]
    for dev, plane in planes.items():
        d = P.classify_device(plane["lines"], plane["meta"])
        again = P.select(d, passes=("recompute",))
        back = P.select(d, passes=("bwd",))
        assert again > 0 and back > 0
        assert again + back == theirs[dev]["phase_ns"]["bwd"]
    if gz == NAMED:
        d = P.classify_device(planes[0]["lines"], planes[0]["meta"])
        assert (P.select(d, passes=("recompute",)),
                P.select(d, passes=("bwd",))) == (1109760, 1653626)
        # Four of a step's six forward kernel calls are recomputed ones
        # (the stage's checkpoint and the layer's): two thirds of 394149.
        assert P.select(d, passes=("recompute",), kinds=("kernel",)) == (
            261599)


def as_this_process(recorded, gz, monkeypatch):
    path = recorded[gz][0]
    os.utime(path)
    monkeypatch.setattr(loader.load_code("runners", "train"), "TRACE_DIR",
                        Path(path).parents[3])
    return {"trace": R.reduce_trace(path)}


@pytest.mark.parametrize("name, ns, steps", [
    ("recompute_ms_per_step", 1110707, 6),           # device 2
    ("xla_matmul_ms_per_step", 366787, 6),           # device 2
    ("xla_copy_ms_per_step", 70283, 6),              # device 2
])
def test_the_unnamed_readers_read_the_named_trace(
        recorded, trace_dir, monkeypatch, name, ns, steps):
    layers = as_this_process(recorded, NAMED, monkeypatch)
    got = read(name, layers)
    assert got == pytest.approx(ns / steps / 1e6) and got > 0
    assert got < read("bwd_ms_per_step", layers)
    # Device by device the matmuls and the copies are part of what
    # ``xla_compute_ms_per_step`` counts.
    for d in P.classified(layers)["devices"].values():
        matmul = P.select(d, kinds=("compute",), categories=P.MATMUL)
        copy = P.select(d, kinds=("compute",), categories=P.COPY)
        assert matmul + copy <= P.select(d, kinds=("compute",))
    assert (read("xla_matmul_ms_per_step", layers)
            + read("xla_copy_ms_per_step", layers)
            <= read("xla_compute_ms_per_step", layers))


@pytest.mark.parametrize("name", ["attn_kv_repeat_ms_per_step",
                                  "attn_delta_ms_per_step",
                                  "layer_stack_ms_per_step"])
def test_a_name_the_trace_lacks_gives_nothing(
        recorded, trace_dir, monkeypatch, capsys, name):
    """PR 24's trace, recorded before the three names: what this PR's
    readers give on its parent."""
    layers = as_this_process(recorded, NAMED, monkeypatch)
    assert read(name, layers) is None
    out = capsys.readouterr().out
    assert "attn+flash_fwd" in out and "convolution fusion" in out
    assert "hvd_attn, compute that is no matmul" in out


@pytest.mark.skipif(not GQA.exists(), reason="no trace with the names")
@pytest.mark.parametrize("name", ["attn_kv_repeat_ms_per_step",
                                  "attn_delta_ms_per_step",
                                  "layer_stack_ms_per_step"])
def test_the_three_names_read_from_a_trace_recorded_with_them(
        recorded, trace_dir, monkeypatch, name):
    layers = as_this_process(recorded, GQA, monkeypatch)
    got = read(name, layers)
    assert got is not None and 0 < got < read("bwd_ms_per_step", layers)
    d = next(iter(P.classified(layers)["devices"].values()))
    held = {n for (names, _p, _k, _c) in d["part_ns"] for n in names}
    assert {"hvd_layers", "hvd_attn_delta", "hvd_attn_kv_repeat"} <= held
    # The repeat runs forward, again in the recompute, and as the sum over
    # a group backward; ``delta`` in the backward pass alone.
    assert {p for (names, p, _k, _c) in d["part_ns"]
            if "hvd_attn_kv_repeat" in names} == {"fwd", "recompute", "bwd"}
    assert {p for (names, p, _k, _c) in d["part_ns"]
            if "hvd_attn_delta" in names} == {"bwd"}
