"""``benchmark/trace/scopes.py``: device time by the names the program gives
its work, on hand-made ``op_name``s, on a hand-encoded ``XSpace`` and on the
traces recorded on the chip (``benchmark/trace/testdata``), whose totals must
be ``reduce.py``'s to the nanosecond."""

from __future__ import annotations

import gzip
import json
import os
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader                      # noqa: E402
from benchmark.trace import reduce as R           # noqa: E402
from benchmark.trace import scopes as S           # noqa: E402

TESTDATA = Path(R.__file__).resolve().parent / "testdata"
# PR 22's trace has none of the program's names (recorded before them);
# PR 24's is the same tiny step (2 layers, d_model 256, 4 heads, sequence
# 512, global batch 4, dp 2 x mp 2 on four v5e chips) recorded with them.
UNNAMED = TESTDATA / "flagship-tiny-dp2mp2.xplane.pb.gz"
NAMED = TESTDATA / "flagship-tiny-dp2mp2-named.xplane.pb.gz"
NEW_METRICS = ("attn_fwd_kernel_ms_per_step", "attn_bwd_kernel_ms_per_step",
               "attn_fwd_kernel_calls_per_step", "head_ms_per_step",
               "fwd_ms_per_step", "bwd_ms_per_step", "optimizer_ms_per_step")


# -- names ---------------------------------------------------------------------

# The forms a scope takes in an ``op_name`` under AD (ISSUE 24, off-chip
# probe): a path component, inside ``jvp(...)``, inside
# ``transpose(jvp(...))`` with the forward's path repeated behind it, and
# outside AD altogether.
@pytest.mark.parametrize("op_name, phase, block", [
    ("jit(step)/jvp(hvd_attn)/dot_general", "fwd", "hvd_attn"),
    ("jit(step)/transpose(jvp(hvd_attn))/jvp(hvd_attn)/checkpoint/dot_general",
     "bwd", "hvd_attn"),
    ("jit(step)/hvd_optimizer/mul", "optimizer", "hvd_optimizer"),
    ("jit(train_step)/jvp()/hvd_head/jit(take_along_axis)/gather",
     "fwd", "hvd_head"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/hvd_mlp/reduce_sum", "bwd", "hvd_mlp"),
    ("jit(train_step)/transpose(jvp())/hvd_embed/scatter-add:", "bwd",
     "hvd_embed"),
    ("jit(train_step)/jvp()/while/body/closed_call/squeeze", "fwd", "none"),
    ("jit(train_step)/add:", "other", "none"),
    ("checkpoint/hvd_attn/reduce_sum", "other", "hvd_attn"),
    # A token is tested for membership, never a prefix or a substring.
    ("jit(step)/not_hvd_head/transposed/jvp_like/mul", "other", "none"),
    ("", "other", "none"),
])
def test_phase_and_block_of_an_op_name(op_name, phase, block):
    toks = S.tokens(op_name)
    assert "" not in toks
    assert (S.phase_of(toks), S.block_of(toks)) == (phase, block)


def test_tokens_split_on_slash_and_parentheses():
    assert S.tokens("jit(step)/transpose(jvp(hvd_attn))/mul") == {
        "jit", "step", "transpose", "jvp", "hvd_attn", "mul"}


def kernel_text(name: str) -> str:
    return (f'%{name} = (bf16[5,16,8192,64]{{3,2,1,0:T(8,128)(2,1)}}) '
            'custom-call(s32[1,2]{1,0:T(1,128)S(1)} %copy-done.82), '
            'custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={}')


FUSION = ('%fusion.1 = bf16[5,8192,1024]{2,1,0:T(8,128)(2,1)} '
          'fusion(bf16[5,8192,1024]{2,1,0} %p.1), kind=kOutput')
GATHER = ('%all-gather.3 = bf16[5,8192,1024]{2,1,0} all-gather('
          'bf16[5,4096,1024]{2,1,0:T(8,128)(2,1)} %x), dimensions={1}')
UPDATE = FUSION.replace("%fusion.1 ", "%fusion.9 ")
COPY = "%copy.1 = f32[8]{0:T(128)} copy(f32[8]{0:T(128)} %p.2)"
WHILE = ('%while.1 = (s32[]{:T(128)}, bf16[5,8192,1024]{1,2,0:T(8,128)(2,1)}) '
         'while((s32[]{:T(128)}, bf16[5,8192,1024]{1,2,0}) %tuple.1), '
         'condition=%cond, body=%body')


@pytest.mark.parametrize("text, kernel", [
    (kernel_text("hvd_flash_fwd.15"), "hvd_flash_fwd"),
    (kernel_text("hvd_flash_fwd"), "hvd_flash_fwd"),
    (kernel_text("hvd_flash_bwd_dq.9"), "hvd_flash_bwd_dq"),
    (kernel_text("hvd_flash_bwd_dkv.9"), "hvd_flash_bwd_dkv"),
    (kernel_text("checkpoint.23"), None),          # a kernel, not named
    (FUSION.replace("%fusion.1 ", "%hvd_flash_fwd.2 "), None),  # no kernel
    ("hvd_flash_fwd", None),                       # not HLO text
])
def test_kernel_of_an_event(text, kernel):
    assert S.kernel_of(text) == kernel


# -- the wire format -----------------------------------------------------------------

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        n, low = n >> 7, n & 0x7F
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    """One protobuf field: a varint for an int, else length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_fields_reads_what_was_written():
    msg = (field(1, 300) + field(2, "name") + field(9, 2 ** 40)
           + varint(3 << 3 | 1) + b"\x00" * 8      # a double
           + varint(4 << 3 | 5) + b"\x00" * 4      # a float
           + field(5, field(1, 7)))
    got = [(f, v if isinstance(v, int) else bytes(v))
           for f, v in S.fields(memoryview(msg))]
    assert got == [(1, 300), (2, b"name"), (9, 2 ** 40), (3, b"\x00" * 8),
                   (4, b"\x00" * 4), (5, b"\x08\x07")]
    assert list(S.fields(memoryview(b""))) == []
    with pytest.raises(ValueError, match="wire type 3"):
        list(S.fields(memoryview(varint(1 << 3 | 3))))
    with pytest.raises(ValueError, match="past its message"):
        list(S.fields(memoryview(varint(1 << 3 | 2) + varint(9) + b"ab")))


TF_OP, OTHER_STAT, REF_TARGET = 7, 8, 9
T0_NS = 1_000


def xspace(planes: dict) -> bytes:
    """An ``XSpace``: {plane name: (metadata, lines)} with metadata
    {id: (HLO text, op_name or None, by reference?)} and lines
    {line name: [(metadata id, offset ps, duration ps)]}."""
    out = b""
    for pname, (metadata, lines) in planes.items():
        plane = field(1, 1) + field(2, pname)
        for lname, events in lines.items():
            line = field(2, lname) + field(3, T0_NS)
            for mid, offset, duration in events:
                line += field(4, field(1, mid) + field(2, offset)
                              + field(3, duration)
                              + field(4, field(1, OTHER_STAT) + field(3, 5)))
            plane += field(3, line)
        for mid, (text, op_name, by_ref) in metadata.items():
            meta = field(1, mid) + field(2, text)
            meta += field(5, field(1, OTHER_STAT) + field(5, "fusion"))
            if op_name is not None:
                meta += field(5, field(1, TF_OP) + (
                    field(7, REF_TARGET) if by_ref else field(5, op_name)))
            plane += field(4, field(1, mid) + field(2, meta))
        for sid, sname in ((TF_OP, "tf_op"), (OTHER_STAT, "hlo_category"),
                           (REF_TARGET, "jit(step)/hvd_optimizer/mul")):
            plane += field(5, field(1, sid)
                           + field(2, field(1, sid) + field(2, sname)))
        out += field(1, plane)
    return out


# Two whole steps of 1000 ns on one device.  In each: a ``while`` of 600 ns
# that holds a forward kernel (200), a gather in the MLP (100) and a second
# forward kernel under ``transpose`` (recompute, 150), so 150 of its own;
# then the head's backward fusion (200), the optimizer (100, its ``op_name``
# stored by reference) and a copy the compiler made, with no ``op_name`` (50).
META = {
    1: (WHILE, "jit(step)/jvp()/while", False),
    2: (kernel_text("hvd_flash_fwd.1"),
        "jit(step)/jvp()/while/body/hvd_attn/hvd_flash_fwd/pallas_call:",
        False),
    3: (GATHER, "jit(step)/jvp()/while/body/hvd_mlp/all_gather", False),
    4: (kernel_text("hvd_flash_fwd.2"),
        "jit(step)/transpose(jvp())/while/body/checkpoint/hvd_attn/"
        "hvd_flash_fwd/pallas_call:", False),
    5: (FUSION, "jit(step)/transpose(jvp())/hvd_head/dot_general", False),
    6: (UPDATE, "", True),
    7: (COPY, None, False),
    8: ("step", None, False),
}


def step_events(at_ns: int):
    ps = 1000
    return [(1, at_ns * ps, 600 * ps),
            (2, (at_ns + 50) * ps, 200 * ps),
            (3, (at_ns + 250) * ps, 100 * ps + 999),    # cut to whole ns
            (4, (at_ns + 400) * ps, 150 * ps),
            (5, (at_ns + 600) * ps, 200 * ps),
            (6, (at_ns + 800) * ps, 100 * ps),
            (7, (at_ns + 900) * ps + 999, 50 * ps)]


def synthetic_trace(tmp_path, name="t.xplane.pb") -> str:
    ops = step_events(0) + step_events(1000) + step_events(2000)[:2]
    steps = [(8, 0, 1000 * 1000), (8, 1000 * 1000, 1000 * 1000)]
    space = xspace({
        "/host:CPU": ({1: ("bench.wait_loss", None, False)},
                      {"python": [(1, 0, 5000)]}),
        "/device:TPU:0": (META, {R.STEPS_LINE: steps, R.OPS_LINE: ops,
                                 "Async XLA Ops": [(3, 0, 999)]}),
        "/device:TPU:1": (META, {R.STEPS_LINE: steps, R.OPS_LINE: ops}),
    })
    path = tmp_path / "plugins" / "profile" / "2026_01_01" / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(space)
    return str(path)


def test_synthetic_trace_reads_like_profile_data(tmp_path):
    path = synthetic_trace(tmp_path)
    planes = S.read_device_planes(path)
    assert sorted(planes) == [0, 1]
    plane = planes[0]
    assert set(plane["lines"]) == {R.STEPS_LINE, R.OPS_LINE}
    assert plane["meta"][2][1].endswith("hvd_flash_fwd/pallas_call:")
    assert plane["meta"][6] == (UPDATE, "jit(step)/hvd_optimizer/mul")
    assert plane["meta"][7] == (COPY, "")
    assert plane["lines"][R.OPS_LINE][2] == (3, T0_NS + 250, T0_NS + 350)
    assert plane["lines"][R.OPS_LINE][6] == (7, T0_NS + 900, T0_NS + 950)
    # jax's own reader sees the same events (names in place of ids).
    theirs = R.read_planes(path)["/device:TPU:0"]
    for line, events in plane["lines"].items():
        assert [(plane["meta"][m][0], s, e) for m, s, e in events] == (
            theirs[line])


def test_self_time_by_kernel_phase_and_block_with_a_nested_while(tmp_path):
    path = synthetic_trace(tmp_path)
    d = S.classify_trace(path, devices={0})["devices"][0]
    assert (d["window_ns"], d["n_programs"]) == (2000, 2)
    # The third step is cut by the window: its events do not count.
    assert d["kernel_events"] == {"hvd_flash_fwd": 4}
    assert d["kernel_ns"] == {"hvd_flash_fwd": 2 * (200 + 150)}
    assert d["control_ns"] == 2 * 150
    assert d["phase_ns"] == {"fwd": 2 * 300, "bwd": 2 * 350,
                             "optimizer": 2 * 100, "other": 2 * 50}
    assert d["block_ns"] == {"hvd_attn": 2 * 350, "hvd_mlp": 2 * 100,
                             "hvd_head": 2 * 200, "hvd_optimizer": 2 * 100}
    assert d["table_ns"] == {
        ("hvd_attn", "fwd", "kernel"): 400, ("hvd_mlp", "fwd", "collective"):
        200, ("hvd_attn", "bwd", "kernel"): 300,
        ("hvd_head", "bwd", "compute"): 400,
        ("hvd_optimizer", "optimizer", "compute"): 200,
        ("none", "other", "compute"): 100}
    # The same window, steps and totals as reduce.py.
    r = R.reduce_trace(path)["devices"][0]
    assert (r["window_ns"], r["n_programs"]) == (2000, 2)
    assert d["work_ns"] == sum(d["phase_ns"].values()) == r["busy_ns"] == 1600
    assert d["kernel_ns"]["hvd_flash_fwd"] == r["self_ns"]["kernel"]
    assert d["control_ns"] == r["self_ns"]["control"]
    rows = S.table(d)
    assert rows[0].split() == ["block", "phase", "kernel", "collective",
                               "compute"]
    assert [row.split()[:2] for row in rows[1:]] == [
        ["hvd_attn", "fwd"], ["hvd_attn", "bwd"], ["hvd_mlp", "fwd"],
        ["hvd_head", "bwd"], ["hvd_optimizer", "optimizer"],
        ["none", "other"]]
    assert rows[1].split()[2:] == ["0.000", "0.000", "0.000"]  # 200 ns a step


def test_a_trace_without_op_names_gives_kernels_and_no_phase():
    lines = {R.OPS_LINE: [(2, 0, 100), (7, 100, 150)]}
    meta = {2: (kernel_text("hvd_flash_fwd.1"), ""), 7: (COPY, "")}
    d = S.classify_device(lines, meta)
    assert d["kernel_ns"] == {"hvd_flash_fwd": 100}
    assert d["phase_ns"] == {} and d["block_ns"] == {}
    assert d["n_programs"] == 0 and d["window_ns"] == 150
    assert S.classify_device({}, {}) is None


# -- this process's trace ---------------------------------------------------------

def test_newest_trace_refuses_a_stale_file(tmp_path):
    assert S.newest_trace(tmp_path, 0.0) is None
    old = synthetic_trace(tmp_path / "cell-a", "old.xplane.pb")
    new = synthetic_trace(tmp_path / "cell-b", "new.xplane.pb")
    now = time.time()
    os.utime(old, (now - 100, now - 100))
    os.utime(new, (now - 10, now - 10))
    assert S.newest_trace(tmp_path, now - 50) == new
    assert S.newest_trace(tmp_path, now - 5) is None      # older than that
    started = S.process_start()
    assert now - 24 * 3600 < started <= now + 1
    # What an earlier process left is older than this one; what this one
    # writes is not.
    os.utime(old, (started - 100, started - 100))
    os.utime(new, (started - 10, started - 10))
    assert S.newest_trace(tmp_path, started - 1.0) is None
    os.utime(new, (now, now))
    assert S.newest_trace(tmp_path, started - 1.0) == new


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """The runner's ``TRACE_DIR`` for one test."""
    monkeypatch.setattr(loader.load_code("runners", "train"), "TRACE_DIR",
                        tmp_path)
    S._classified.cache_clear()
    yield tmp_path
    S._classified.cache_clear()


def read(name: str, layers: dict):
    entry = loader.find(loader.load_benchmark()["per_layer"], name, "metric")
    return loader.load_code("metrics", name).read(layers, entry)


def test_readers_on_a_synthetic_trace_of_this_process(trace_dir, capsys):
    path = synthetic_trace(trace_dir / "some-cell")
    layers = {"trace": R.reduce_trace(path)}
    assert read("attn_fwd_kernel_ms_per_step", layers) == 350 / 1e6
    assert read("attn_fwd_kernel_calls_per_step", layers) == 2.0
    assert read("attn_bwd_kernel_ms_per_step", layers) is None  # none ran
    assert read("head_ms_per_step", layers) == 200 / 1e6
    assert read("fwd_ms_per_step", layers) == 300 / 1e6
    assert read("bwd_ms_per_step", layers) == 350 / 1e6
    assert read("optimizer_ms_per_step", layers) == 100 / 1e6
    out = capsys.readouterr().out
    # Seven readers, one parse, one table; every line is the log's.
    assert out.count("by the program's names") == 1
    assert out.count("by scope in") == 1
    assert "kernel events a step: hvd_flash_fwd 2" in out
    assert all(ln.startswith("benchmark: ") for ln in out.splitlines())
    # Forward + backward kernels are the kernels reduce.py lumps together.
    assert read("attn_fwd_kernel_ms_per_step", layers) == loader.load_code(
        "metrics", "attn_kernel_ms_per_step").read(layers, {"better": "lower"})


def test_readers_refuse_a_trace_the_runner_did_not_reduce(trace_dir, capsys):
    path = synthetic_trace(trace_dir / "some-cell")
    reduced = R.reduce_trace(path)
    reduced["devices"][0]["window_ns"] += 1
    assert read("fwd_ms_per_step", {"trace": reduced}) is None
    assert "is not the trace the runner reduced" in capsys.readouterr().out
    os.utime(path, (1.0, 1.0))                       # an earlier process's
    assert read("fwd_ms_per_step", {"trace": R.reduce_trace(path)}) is None
    assert "no trace of this process" in capsys.readouterr().out


@pytest.mark.parametrize("trace", [
    None,                                            # an untraced run
    {"devices": {}, "host_spans": []},               # a CPU rehearsal's
])
def test_every_new_entry_has_a_reader_that_returns_nothing_off_the_tpu(
        trace_dir, trace):
    """What ``test_command_rehearses_end_to_end_on_cpu`` shows through the
    command, reader by reader: no TPU plane, no value, and nothing is looked
    for on disk."""
    bench = json.loads((loader.REPO_ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-7:]] == list(NEW_METRICS)
    synthetic_trace(trace_dir / "some-cell")         # must not be read
    for name in NEW_METRICS:
        entry = entries[name]
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves"}
        assert (entry["source"], entry["moves"], entry["better"]) == (
            "device_trace", "tokens_per_s_per_chip", "lower")
        assert loader.load_code("metrics", name).read(
            {"trace": trace}, entry) is None
    assert S._classified.cache_info().misses == 0


# -- the recorded traces ------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    def unpack(gz: Path) -> str:
        path = (tmp_path_factory.mktemp(gz.name.split(".")[0]) / "plugins"
                / "profile" / "recorded" / "recorded.xplane.pb")
        path.parent.mkdir(parents=True)
        path.write_bytes(gzip.decompress(gz.read_bytes()))
        return str(path)
    return {gz: unpack(gz) for gz in (UNNAMED, NAMED)}


@pytest.mark.parametrize("gz", [UNNAMED, NAMED], ids=["unnamed", "named"])
def test_recorded_trace_totals_are_reduce_devices_to_the_nanosecond(
        recorded, gz):
    assert gz.stat().st_size < 512 * 1024
    path = recorded[gz]
    theirs = R.read_planes(path)
    for dev, plane in S.read_device_planes(path).items():
        for line, events in plane["lines"].items():
            assert [(plane["meta"][m][0], s, e) for m, s, e in events] == (
                theirs[f"/device:TPU:{dev}"][line]), (dev, line)
    reduced = R.reduce_trace(path)["devices"]
    mine = S.classify_trace(path)["devices"]
    assert sorted(mine) == sorted(reduced) == [0, 1, 2, 3]
    for dev, d in mine.items():
        r = reduced[dev]
        assert (d["window_ns"], d["n_programs"]) == (
            r["window_ns"], r["n_programs"])
        assert d["control_ns"] == r["self_ns"]["control"]
        assert d["work_ns"] == (r["self_ns"]["kernel"] + r["self_ns"]["compute"]
                                + r["self_ns"]["collective"])
        assert sum(d["phase_ns"].values()) == d["work_ns"]
        assert sum(d["table_ns"].values()) == d["work_ns"]
        # No two operations that do work overlap on a device's line, so
        # self times add up to the busy time.
        assert d["work_ns"] == r["busy_ns"]
        for kind in S.KINDS:
            assert sum(ns for (_b, _p, k), ns in d["table_ns"].items()
                       if k == kind) == r["self_ns"][kind]


def test_the_unnamed_trace_has_phases_and_no_names(recorded, trace_dir,
                                                   monkeypatch):
    """PR 22's trace, recorded before the program named anything: ``jvp`` and
    ``transpose`` are JAX's own, so forward and backward read; kernels, head
    and optimizer do not, and their readers return nothing (what this PR's
    readers give on its parent)."""
    path = recorded[UNNAMED]
    meta = S.read_device_planes(path)[0]["meta"]
    phases = [S.phase_of(S.tokens(op)) for _text, op in meta.values() if op]
    assert (phases.count("fwd"), phases.count("bwd")) == (41, 108)
    d = S.classify_trace(path, devices={0})["devices"][0]
    assert d["kernel_ns"] == d["kernel_events"] == d["block_ns"] == {}
    assert d["phase_ns"] == {"fwd": 462499, "bwd": 1639198, "other": 120533}
    os.utime(path)                                   # this process's trace
    layers = {"trace": R.reduce_trace(path)}
    monkeypatch.setattr(loader.load_code("runners", "train"), "TRACE_DIR",
                        Path(path).parents[3])
    assert read("fwd_ms_per_step", layers) == pytest.approx(462499 / 4 / 1e6)
    assert read("bwd_ms_per_step", layers) == pytest.approx(1639198 / 4 / 1e6)
    for name in ("attn_fwd_kernel_ms_per_step", "attn_bwd_kernel_ms_per_step",
                 "attn_fwd_kernel_calls_per_step", "head_ms_per_step",
                 "optimizer_ms_per_step"):
        assert read(name, layers) is None, name


def test_the_named_trace_classifies_to_the_nanosecond(recorded, trace_dir,
                                                      monkeypatch):
    """PR 24's trace of the same tiny step with the program's names in it:
    six whole steps of 2 layers, so 2 x 3 forward kernel calls a step (the
    stage's and the layer's checkpoint both recompute) and 2 of each
    backward kernel."""
    path = recorded[NAMED]
    d = S.classify_trace(path, devices={0})["devices"][0]
    assert (d["n_programs"], d["window_ns"]) == (6, 17302873)
    assert d["kernel_events"] == {"hvd_flash_fwd": 36, "hvd_flash_bwd_dq": 12,
                                  "hvd_flash_bwd_dkv": 12}
    assert d["kernel_ns"] == {"hvd_flash_fwd": 394149,
                              "hvd_flash_bwd_dq": 71782,
                              "hvd_flash_bwd_dkv": 113657}
    assert d["phase_ns"] == {"fwd": 720253, "bwd": 2763386,
                             "optimizer": 65118, "other": 260874}
    assert d["block_ns"] == {"hvd_embed": 108235, "hvd_attn": 1519002,
                             "hvd_mlp": 880861, "hvd_head": 120676,
                             "hvd_optimizer": 65118}
    # The optimizer is the scope outside AD; no scope holds a collective
    # that is not attention's or the MLP's, save the gradients' all-reduce.
    assert d["table_ns"][("hvd_optimizer", "optimizer", "compute")] == 65118
    collective_blocks = {b for (b, _p, k), ns in d["table_ns"].items()
                         if k == "collective" and ns}
    assert collective_blocks == {"hvd_attn", "hvd_mlp", "none"}
    # Every kernel is one of the three, in the attention block.
    assert {(b, p) for (b, p, k) in d["table_ns"] if k == "kernel"} == {
        ("hvd_attn", "fwd"), ("hvd_attn", "bwd")}

    os.utime(path)                                   # this process's trace
    monkeypatch.setattr(loader.load_code("runners", "train"), "TRACE_DIR",
                        Path(path).parents[3])
    layers = {"trace": R.reduce_trace(path)}
    fwd = read("attn_fwd_kernel_ms_per_step", layers)
    bwd = read("attn_bwd_kernel_ms_per_step", layers)
    assert fwd == pytest.approx(394149 / 6 / 1e6)            # device 0
    assert bwd == pytest.approx((71787 + 113662) / 6 / 1e6)  # device 2
    assert read("attn_fwd_kernel_calls_per_step", layers) == 6.0
    assert read("head_ms_per_step", layers) == pytest.approx(120676 / 6 / 1e6)
    assert read("fwd_ms_per_step", layers) == pytest.approx(722900 / 6 / 1e6)
    assert read("bwd_ms_per_step", layers) == pytest.approx(2763386 / 6 / 1e6)
    assert read("optimizer_ms_per_step", layers) == pytest.approx(
        65125 / 6 / 1e6)
    # Forward + backward kernels are what reduce.py calls kernels, on
    # every device; phases add up to its busy time.
    reduced = layers["trace"]["devices"]
    for dev, c in S.classified(layers)["devices"].items():
        assert sum(c["kernel_ns"].values()) == reduced[dev]["self_ns"]["kernel"]
        assert sum(c["phase_ns"].values()) == reduced[dev]["busy_ns"]
