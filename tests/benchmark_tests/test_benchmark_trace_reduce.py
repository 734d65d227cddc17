"""``benchmark/trace/reduce.py`` on synthetic intervals and on a small trace
recorded on the chip (``benchmark/trace/testdata``), to the nanosecond."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.trace import reduce as R           # noqa: E402

TESTDATA = Path(R.__file__).resolve().parent / "testdata"


# -- synthetic intervals -----------------------------------------------------------

@pytest.mark.parametrize("intervals, want", [
    ([], []),
    ([(5, 5), (9, 3)], []),                                  # empty, inverted
    ([(0, 10), (2, 4)], [(0, 10)]),                          # nested
    ([(0, 5), (3, 9), (9, 12)], [(0, 12)]),                  # overlap, touch
    ([(20, 30), (0, 5), (7, 8)], [(0, 5), (7, 8), (20, 30)]),
])
def test_union(intervals, want):
    assert R.union(intervals) == want
    assert R.total(R.union(intervals)) == sum(e - s for s, e in want)


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 10)], [(2, 4), (6, 12)], [(0, 2), (4, 6)]),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(0, 4), (6, 10)], [(-5, 1), (9, 20)], [(1, 4), (6, 9)]),
    ([], [(0, 1)], []),
])
def test_subtract(a, b, want):
    assert R.subtract(a, b) == want


def test_gaps_and_clip():
    busy = [(2, 4), (6, 9)]
    assert R.gaps(busy, 0, 10) == [(0, 2), (4, 6), (9, 10)]
    assert R.gaps(busy, 3, 8) == [(4, 6)]
    assert R.gaps([], 0, 0) == []
    assert R.clip([(0, 5), (8, 20)], 3, 10) == [(3, 5), (8, 10)]


def test_self_times_take_children_out_of_parents():
    events = [("while.1", 0, 100), ("fusion.1", 10, 30),
              ("custom-call.2", 30, 70), ("inner", 40, 50),
              ("copy.3", 120, 130)]
    got = dict(R.self_times(events))
    assert got == {"while.1": 40, "fusion.1": 20, "custom-call.2": 30,
                   "inner": 10, "copy.3": 10}
    assert sum(got.values()) == R.total(R.union((s, e) for _, s, e in events))
    assert R.self_times([]) == []


KERNEL = ('%checkpoint.23 = (bf16[5,16,8192,64]{3,2,1,0:T(8,128)(2,1)}) '
          'custom-call(s32[1,2]{1,0:T(1,128)S(1)} %copy-done.82), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
FUSION = ('%fusion.1 = bf16[5,8192,1024]{2,1,0:T(8,128)(2,1)} '
          'fusion(bf16[5,8192,1024]{2,1,0} %p.1), kind=kOutput')
FUSION2 = FUSION.replace("%fusion.1 ", "%fusion.2 ")
WHILE = ('%while.1 = (s32[]{:T(128)}, bf16[5,8192,1024]{1,2,0:T(8,128)(2,1)}) '
         'while((s32[]{:T(128)}, bf16[5,8192,1024]{1,2,0}) %tuple.1), '
         'condition=%cond, body=%body')
ALL_REDUCE = ('%all-reduce.1 = f32[1024,1024]{1,0:T(8,128)} '
              'all-reduce(f32[1024,1024]{1,0:T(8,128)} %dot.1), channel_id=1')
GATHER_START = ('%all-gather-start.3 = (bf16[5,4096,1024]{2,1,0}, '
                'bf16[5,8192,1024]{2,1,0}) all-gather-start(bf16[5,4096,1024]'
                '{2,1,0:T(8,128)(2,1)} %x), dimensions={1}')
COPY = "%copy.1 = f32[8]{0:T(128)} copy(f32[8]{0:T(128)} %p.2)"


def test_kinds_and_names_from_hlo_text():
    assert R.instruction(KERNEL) == ("checkpoint.23", "custom-call")
    assert R.instruction(WHILE) == ("while.1", "while")
    assert R.instruction("jit_step(1)") == ("jit_step(1)", "")
    assert R.kind_of(KERNEL) == "kernel"
    assert R.kind_of(KERNEL.replace("tpu_custom_call", "ConcatBitcast")) \
        == "compute"
    assert R.kind_of(FUSION) == "compute" and R.kind_of(COPY) == "compute"
    assert R.kind_of(WHILE) == "control"
    assert R.kind_of(ALL_REDUCE) == "collective"
    assert R.kind_of(GATHER_START) == "collective"
    assert R.kind_of("%reduce-scatter.2 = f32[2]{0} reduce-scatter(f32[4]{0} "
                     "%a), dimensions={0}") == "collective"
    assert R.kind_of("%collective-permute-done.1 = bf16[2]{0} "
                     "collective-permute-done((bf16[2]{0}) %s)") \
        == "collective"
    assert R.kind_of("%reduce.4 = f32[]{} reduce(f32[8]{0} %a)") == "compute"
    assert R.short_name(KERNEL) == "checkpoint.23 tpu_custom_call"
    assert R.short_name(FUSION) == "fusion.1 fusion"
    assert R.short_name(GATHER_START) == "all-gather-start.3 all-gather-start"


def synthetic_plane():
    """Three programs of 100 ns, 20 ns apart, of which the profiler counts
    the last two as whole steps.  In each: a loop that spans a fusion, a
    kernel and a collective.  The collective's first half overlaps a fusion
    on the same line; an asynchronous all-gather is in flight from +10 to
    +58, the last 3 ns of it with nothing else running."""
    ops, modules, steps, in_flight = [], [], [], []
    for i, t in enumerate((880, 1000, 1120)):
        modules.append(("jit_step(1)", t, t + 100))
        if i:
            steps.append((str(i - 1), t, t + 100))
        ops += [(WHILE, t, t + 90),
                (FUSION, t + 5, t + 25),
                (KERNEL, t + 25, t + 55),
                (ALL_REDUCE, t + 60, t + 80),
                (FUSION2, t + 60, t + 70),
                (COPY, t + 92, t + 98)]
        in_flight.append((GATHER_START, t + 10, t + 58))
    return {R.MODULES_LINE: modules, R.STEPS_LINE: steps, R.OPS_LINE: ops,
            R.ASYNC_LINE: in_flight}


def test_reduce_device_on_a_synthetic_plane():
    d = R.reduce_device(synthetic_plane())
    assert d["window_ns"] == 220 and d["n_programs"] == 2
    # Work: fusion 20, kernel 30, all-reduce 20 (the fusion inside it adds
    # nothing), copy 6; the loop around them is not work.
    assert d["busy_ns"] == 2 * (20 + 30 + 20 + 6)
    assert d["idle_gaps"] == [
        (1000, 1005), (1055, 1060), (1080, 1092), (1098, 1125),
        (1175, 1180), (1200, 1212), (1218, 1220)]
    # Collective time is the op line's: the all-reduce, of which the fusion
    # beside it hides the first half.  The asynchronous all-gather in flight
    # is kept apart.
    assert d["collective_ns"] == 2 * 20
    assert d["collective_exposed_ns"] == 2 * 10
    assert d["collective_async_ns"] == 2 * 48
    assert d["self_ns"] == {"collective": 2 * 10, "kernel": 2 * 30,
                            "control": 2 * 20, "compute": 2 * (20 + 10 + 6)}
    assert sum(d["by_name_ns"].values()) == 2 * (90 + 6)
    assert d["by_name_ns"]["checkpoint.23 tpu_custom_call"] == 60
    # A window cuts events at its edges.
    d = R.reduce_device(synthetic_plane(), window=(1050, 1130))
    assert d["window_ns"] == 80 and d["n_programs"] == 0
    assert d["busy_ns"] == 5 + 20 + 6 + 5
    # Without the profiler's Steps line every program counts.
    plane = synthetic_plane()
    del plane[R.STEPS_LINE]
    d = R.reduce_device(plane)
    assert d["window_ns"] == 340 and d["n_programs"] == 3
    assert R.reduce_device({R.OPS_LINE: []}) is None
    assert R.reduce_device({}) is None


def test_breakdown_and_worst_device():
    plane = synthetic_plane()
    reduced = {"devices": {0: R.reduce_device(plane),
                           1: R.reduce_device(plane, window=(1000, 1100))},
               "host_spans": [("bench.wait_loss", 1095, 1125),
                              ("bench.put_batch", 1079, 1093)]}
    b = R.breakdown(reduced, n_ops=2, n_gaps=2)
    assert b["device_ops"] == [["checkpoint.23 tpu_custom_call", 60 / 1e9],
                               ["fusion.1 fusion", 40 / 1e9]]
    assert b["idle_gaps"] == [["bench.wait_loss", 27 / 1e9],
                              ["bench.put_batch", 12 / 1e9]]
    assert R.attribute_gap((0, 5), reduced["host_spans"]) == "(no span)"
    idle = lambda d: 1 - d["busy_ns"] / d["window_ns"]     # noqa: E731
    assert R.over_devices(reduced, "lower", idle) == pytest.approx(
        1 - 152 / 220)
    assert R.over_devices(reduced, "higher", idle) == pytest.approx(0.24)
    assert R.over_devices(None, "lower", idle) is None
    assert R.over_devices({"devices": {}}, "lower", idle) is None
    assert R.breakdown({"devices": {}, "host_spans": []}) == {}


# -- the recorded trace --------------------------------------------------------------
#
# Taken on the chip in PR 22: the flagship family at a tiny size (2 layers,
# d_model 256, 4 heads, sequence 512, global batch 4) on four v5e chips as
# dp 2 x mp 2, four whole steps, through benchmark/run.py's traced run.  Kept
# gzipped; the numbers below were read from it once and an independent sweep
# over device 0's operations gave the same busy time.

RECORDED = TESTDATA / "flagship-tiny-dp2mp2.xplane.pb.gz"
GOLDEN = {
    0: dict(window_ns=10695666, busy_ns=2222230, n_programs=4,
            collective_ns=1227948, collective_exposed_ns=1227948,
            collective_async_ns=535304,
            self_ns={"collective": 1227948, "kernel": 386495,
                     "control": 46293, "compute": 607787}, n_gaps=1243),
    1: dict(window_ns=10656732, busy_ns=2217101, n_programs=4,
            collective_ns=1223826, collective_exposed_ns=1223826,
            collective_async_ns=0,
            self_ns={"collective": 1223826, "kernel": 386501,
                     "control": 46049, "compute": 606774}, n_gaps=1266),
    2: dict(window_ns=10650722, busy_ns=2216388, n_programs=4,
            collective_ns=1222849, collective_exposed_ns=1222849,
            collective_async_ns=0,
            self_ns={"collective": 1222849, "kernel": 386515,
                     "control": 46376, "compute": 607024}, n_gaps=1246),
    3: dict(window_ns=10730155, busy_ns=2215415, n_programs=4,
            collective_ns=1221362, collective_exposed_ns=1221362,
            collective_async_ns=0,
            self_ns={"collective": 1221362, "kernel": 386503,
                     "control": 46045, "compute": 607550}, n_gaps=1261),
}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import gzip
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    return str(path)


def test_recorded_trace_reduces_to_the_nanosecond(recorded):
    assert RECORDED.stat().st_size < 512 * 1024
    reduced = R.reduce_trace(recorded)
    assert sorted(reduced["devices"]) == [0, 1, 2, 3]
    for dev, want in GOLDEN.items():
        d = reduced["devices"][dev]
        got = {k: d[k] for k in want if k != "n_gaps"}
        assert got == {k: v for k, v in want.items() if k != "n_gaps"}, dev
        assert len(d["idle_gaps"]) == want["n_gaps"]
        # What is not work is idle, and the names add up to the kinds.
        assert d["busy_ns"] + R.total(d["idle_gaps"]) == d["window_ns"]
        assert sum(d["by_name_ns"].values()) == sum(d["self_ns"].values())
        assert d["collective_exposed_ns"] <= d["collective_ns"]
    d0 = reduced["devices"][0]
    assert d0["by_name_ns"]["all-reduce.5 all-reduce"] == 210952
    assert d0["by_name_ns"]["closed_call.185 tpu_custom_call"] == 88432
    kernels = {n for n in d0["by_name_ns"] if n.endswith("tpu_custom_call")}
    assert len(kernels) == 5           # forward x3 (remat twice), dQ, dK/dV
    # The profiler writes the asynchronous line for device 0 only (a
    # collective-permute in flight there), so collective time is the op
    # line's on every device: every collective synchronous and exposed.
    assert R.reduce_trace(recorded, devices={2})["devices"].keys() == {2}


def test_recorded_trace_host_spans_and_breakdown(recorded):
    reduced = R.reduce_trace(recorded)
    spans = reduced["host_spans"]
    assert len(spans) == 16
    assert {n for n, _, _ in spans} == {
        "bench.dispatch_step", "bench.draw_batch", "bench.put_batch",
        "bench.wait_loss"}
    assert spans[0] == ("bench.dispatch_step", 147197386, 148698015)
    b = R.breakdown(reduced)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 5
    assert b["device_ops"][0] == ["all-reduce.5 all-reduce", 210952 / 1e9]
    assert b["idle_gaps"][0] == ["bench.put_batch", 2919234 / 1e9]


def test_recorded_trace_through_the_metric_readers(recorded):
    """Each device-trace reader on the recorded trace: the worst device by
    the metric's ``better``.  (Values of a toy size; they check the readers'
    arithmetic and are nobody's performance.)"""
    from benchmark import loader
    reduced = R.reduce_trace(recorded)
    layers = {"trace": reduced,
              "attention": {"flops": 1e9, "bytes": 1e6},
              "peaks": loader.load_peaks("TPU v5 lite")}

    def read(name, better):
        return loader.load_code("metrics", name).read(
            layers, {"better": better})

    assert read("device_idle", "lower") == pytest.approx(
        100 * (1 - 2215415 / 10730155))
    assert read("collective_ms_per_step", "lower") == pytest.approx(
        1227948 / 4 / 1e6)
    assert read("collective_exposed_ms_per_step", "lower") == pytest.approx(
        1227948 / 4 / 1e6)
    selfs = [g["self_ns"] for g in GOLDEN.values()]
    assert read("attn_kernel_ms_per_step", "lower") == pytest.approx(
        386515 / 4 / 1e6)
    assert read("xla_compute_ms_per_step", "lower") == pytest.approx(
        max(s["compute"] + s["control"] for s in selfs) / 4 / 1e6)
    # 1e9 FLOPs at 197e12/s against 1e6 bytes at 819e9/s: FLOPs bound it.
    assert read("attn_kernel_roofline", "higher") == pytest.approx(
        100 * (1e9 / 197e12) / (386515 / 1e9 / 4))
    layers["trace"] = None
    assert read("device_idle", "lower") is None
