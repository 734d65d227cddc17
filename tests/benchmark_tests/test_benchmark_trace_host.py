"""``benchmark/trace/host.py``: what the host was doing when the device went
idle, on hand-made planes (a gap a collection covers, one late marks cover,
one nothing covers), on the traces recorded on the chip before the program
had a pause sentinel (``benchmark/trace/testdata``: nothing is read and
nothing raises) and on a trace taken here on the CPU with the sentinel
armed."""

from __future__ import annotations

import gc
import gzip
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader, spans                # noqa: E402
from benchmark.trace import host as H              # noqa: E402
from benchmark.trace import reduce as R            # noqa: E402

TESTDATA = Path(R.__file__).resolve().parent / "testdata"
RECORDED = sorted(TESTDATA.glob("*.xplane.pb.gz"))
METRICS = ("host_gc_share", "host_pause_ms_max", "idle_unexplained_ms_max")
MS = 1_000_000
P = H.PERIOD_NS


def read(name, layers):
    entry = next(m for m in loader.load_benchmark()["per_layer"]
                 if m["name"] == name)
    return loader.load_code("metrics", name).read(layers, entry)


def ticks(*times):
    return [(H.TICK, t, t + 1000) for t in times]


def planes_of(main, sentinel=(), runtime=()):
    """Planes as ``reduce.read_planes`` gives them: the main thread's line,
    the heartbeat's, one of the runtime's, and an empty device plane."""
    return {"/host:CPU": {"python3": list(main),
                          "python3/77": list(sentinel),
                          "tfrt-non-blocking-queue/9": list(runtime)},
            "/device:TPU:0": {R.OPS_LINE: []}, "/host:metadata": {}}


# One traced interval of 1000 ms under bench.* spans, marks every period
# except where a case leaves them out.
BENCH = [("bench.dispatch_step", 0, 5 * MS),
         ("bench.wait_loss", 5 * MS, 1000 * MS)]
ON_TIME = list(range(0, 1000 * MS + 1, P))


# -- hand-made planes ----------------------------------------------------------------

def test_a_quiet_interval_reads_zero_and_leaves_a_gap_unexplained():
    host = H.reduce_host(planes_of(BENCH, ticks(*ON_TIME)))
    assert host["window"] == (0, 1000 * MS)
    assert host["gc_ns"] == 0 and host["pause_ns"] == 0
    assert host["n_ticks"] == len(ON_TIME) and host["late"] == []
    gap = (400 * MS, 700 * MS)
    assert H.unexplained_ns(gap, host) == 300 * MS


def test_a_gap_a_collection_covers_is_explained_by_it():
    main = BENCH + [("hvd.gc.gen2", 410 * MS, 690 * MS),
                    ("hvd.gc.gen0", 100 * MS, 101 * MS)]
    # The collection holds the lock: no mark between 400 and 700 ms.
    marks = [t for t in ON_TIME if not 400 * MS < t < 700 * MS]
    host = H.reduce_host(planes_of(main, ticks(*marks)))
    assert host["gc_ns"] == 281 * MS and host["n_gc"] == 2
    assert host["pause_ns"] == 300 * MS - P
    gap = (400 * MS, 700 * MS)
    # Collection 410-690, late marks 420-700: 400-410 is left.
    assert H.unexplained_ns(gap, host) == 10 * MS
    assert H.unexplained_ns((100 * MS, 101 * MS), host) == 0


def test_a_gap_late_marks_cover_without_a_collection():
    marks = [t for t in ON_TIME if not 200 * MS < t < 900 * MS]
    host = H.reduce_host(planes_of(BENCH, ticks(*marks)))
    assert host["gc_ns"] == 0
    assert host["pause_ns"] == 700 * MS - P
    assert host["late"] == [(200 * MS + P, 900 * MS)]
    assert H.unexplained_ns((250 * MS, 850 * MS), host) == 0
    # A gap that began before the mark was due keeps that part.
    assert H.unexplained_ns((190 * MS, 850 * MS), host) == 30 * MS


def test_marks_a_few_milliseconds_late_explain_nothing():
    # The interpreter's switch interval: every mark 5 ms after it was due.
    marks = [t + (5 * MS if i % 2 else 0) for i, t in enumerate(ON_TIME)]
    host = H.reduce_host(planes_of(BENCH, ticks(*marks)))
    assert host["pause_ns"] == 5 * MS and host["late"] == []
    assert H.unexplained_ns((300 * MS, 330 * MS), host) == 30 * MS


def test_a_stop_that_began_before_the_interval_is_the_intervals():
    marks = [-300 * MS, 100 * MS] + [t for t in ON_TIME if t > 100 * MS]
    host = H.reduce_host(planes_of(BENCH, ticks(*marks)))
    assert host["pause_ns"] == 400 * MS - P


@pytest.mark.parametrize("main, sentinel", [
    (BENCH, ()),                                     # the parent's trace
    ([("PjitFunction(jit(train_step))", 0, MS)], ticks(*ON_TIME)),  # no bench
    ((), ()),
])
def test_without_marks_or_without_bench_spans_there_is_nothing(main,
                                                                sentinel):
    assert H.reduce_host(planes_of(main, sentinel)) is None


def test_the_row_of_a_gap_names_its_spans_and_the_runtimes_events():
    main = BENCH + [("hvd.gc.gen2", 410 * MS, 690 * MS),
                    ("hvd.allreduce.grads#bytes=4", 395 * MS, 405 * MS)]
    runtime = [("tpu::System::Execute=>Done", 500 * MS, 520 * MS),
               ("ReadSyncFlag", 650 * MS, 651 * MS),
               ("MemoryDeallocation", 10 * MS, 11 * MS),
               ("CompleteCallbacks", 600 * MS, 602 * MS),
               ("Release semaphore", 640 * MS, 640 * MS + 1000)]
    marks = [t for t in ON_TIME if not 400 * MS < t < 700 * MS]
    host = H.reduce_host(planes_of(main, ticks(*marks), runtime))
    row = H.gap_row((400 * MS, 700 * MS), host)
    assert row.startswith("idle 300.000 ms (unexplained 10.000 ms) under "
                          "bench.wait_loss; hvd spans: hvd.gc.gen2 280.000 "
                          "ms, hvd.allreduce.grads#bytes=4 5.000 ms, late "
                          "marks cover 280.000 ms; other host threads: ")
    # The three that overlap it most, longest first, each with its line.
    assert row.endswith(
        "tfrt-non-blocking-queue/9: tpu::System::Execute=>Done 20.000 ms, "
        "tfrt-non-blocking-queue/9: CompleteCallbacks 2.000 ms, "
        "tfrt-non-blocking-queue/9: ReadSyncFlag 1.000 ms")
    quiet = H.reduce_host(planes_of(BENCH, ticks(*ON_TIME)))
    assert H.gap_row((300 * MS, 302 * MS), quiet) == (
        "idle 2.000 ms (unexplained 2.000 ms) under bench.wait_loss; hvd "
        "spans: none, marks on time; other host threads: no host thread "
        "has an event inside it")


def test_the_table_holds_the_five_longest_gaps_over_a_millisecond(capsys):
    host = H.reduce_host(planes_of(BENCH, ticks(*ON_TIME)))
    gaps = [(i * 10 * MS, i * 10 * MS + i * MS // 2) for i in range(1, 12)]
    H.say_table(host, gaps)
    out = capsys.readouterr().out.splitlines()
    assert "the 5 longest idle gap(s) over 1 ms" in out[0]
    assert [ln.split(" (")[0] for ln in out[1:]] == [
        f"benchmark:   idle {ms:.3f} ms" for ms in (5.5, 5, 4.5, 4, 3.5)]
    H.say_table(host, gaps[:2])
    assert "no idle gap over 1 ms among 2 (longest 1.000 ms)" in \
        capsys.readouterr().out


def test_the_period_is_the_programs():
    from horovod_tpu.debug import pause
    assert H.PERIOD_NS == round(pause.PERIOD_S * 1e9)
    assert (H.TICK, H.GC_PREFIX) == (pause.TICK, pause.GC_SPAN[:-3])
    assert pause.GC_SPAN.startswith(H.PROGRAM_PREFIX)


def test_the_three_entries_are_the_devices_and_every_training_cells():
    bench = loader.load_benchmark()
    entries = [m for m in bench["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in entries] == list(METRICS)
    for m in entries:
        assert m["layer"] == "device" and m["better"] == "lower"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert "workloads" not in m
    assert [m["source"] for m in entries] == [
        "program_span", "program_span", "device_trace"]
    assert [m["unit"] for m in entries] == ["%", "ms", "ms"]
    for w in bench["workloads"]:
        names = {m["name"] for m in loader.load_cell(w["name"])["per_layer"]}
        assert names >= set(METRICS)


# -- through the readers, on traces in the runner's directory --------------------------

@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """The runner's ``TRACE_DIR`` for one test."""
    monkeypatch.setattr(loader.load_code("runners", "train"), "TRACE_DIR",
                        tmp_path)
    H._reduced.cache_clear()
    yield tmp_path
    H._reduced.cache_clear()


def layers_of(path, idle_gaps=()) -> dict:
    """What the train runner hands the readers of a traced run."""
    reduced = R.reduce_trace(str(path))
    for i, gaps in enumerate(idle_gaps):
        reduced["devices"].setdefault(i, {})["idle_gaps"] = list(gaps)
    return {"trace": reduced}


@pytest.mark.parametrize("recorded", RECORDED, ids=lambda p: p.name)
def test_a_trace_without_the_sentinel_gives_nothing_and_nothing_raises(
        recorded, trace_dir, capsys):
    path = trace_dir / recorded.name[:-3]
    path.write_bytes(gzip.decompress(recorded.read_bytes()))
    layers = layers_of(path)
    assert layers["trace"]["devices"] and layers["trace"]["host_spans"]
    assert [read(m, layers) for m in METRICS] == [None, None, None]
    assert "no pause sentinel" in capsys.readouterr().out


def test_an_untraced_run_gives_nothing(trace_dir):
    assert [read(m, {"trace": None}) for m in METRICS] == [None] * 3


def hold_the_lock(seconds=0.7, interval=0.5):
    old = sys.getswitchinterval()
    sys.setswitchinterval(interval)
    try:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            pass
    finally:
        sys.setswitchinterval(old)


@pytest.fixture
def cpu_trace(trace_dir):
    """A trace as the train runner takes it (``spans.start_trace``), with the
    sentinel armed: under ``bench.*`` spans a forced full collection over
    three million objects, then the interpreter lock held half a second."""
    import jax

    import horovod_tpu as hvd
    hvd.init()
    gc.disable()
    try:
        junk = [[] for _ in range(3_000_000)]
    finally:
        gc.enable()
    where = trace_dir / "cell"
    spans.start_trace(str(where))
    try:
        with spans.host_span("bench.dispatch_step"):
            time.sleep(0.05)
        with spans.host_span("bench.wait_loss"):
            gc.collect()
            time.sleep(0.05)
            hold_the_lock()
            time.sleep(0.1)
    finally:
        jax.profiler.stop_trace()
        del junk[:]
        hvd.shutdown()
    return R.find_xplane(str(where))


def test_on_the_cpu_a_collection_and_a_held_lock_are_read_back(cpu_trace,
                                                              capsys):
    planes = R.read_planes(cpu_trace)
    events = [ev for evs in H.host_threads(planes).values() for ev in evs]
    full = max((ev for ev in events if ev[0] == "hvd.gc.gen2"),
               key=lambda ev: ev[2] - ev[1])
    assert full[2] - full[1] >= 100 * MS
    wait = next(ev for ev in events if ev[0] == "bench.wait_loss")
    assert wait[1] <= full[1] and full[2] <= wait[2]

    # A CPU trace has no device plane: the two host metrics give values, the
    # third nothing (what test_benchmark_run_cpu demands of the sources).
    layers = layers_of(cpu_trace)
    assert not layers["trace"]["devices"]
    share = read("host_gc_share", layers)
    lo, hi = H.reduce_host(planes)["window"]
    assert share >= 100.0 * (full[2] - full[1]) / (hi - lo) * 0.999
    assert share < 100.0
    assert read("host_pause_ms_max", layers) >= 300.0
    assert read("idle_unexplained_ms_max", layers) is None
    assert "host while the device was idle" in capsys.readouterr().out

    # With a device whose idle gaps are the collection, the held lock and a
    # stretch nothing covers, the subtraction uses both spans and leaves the
    # third gap whole.
    host = H.reduce_host(planes)
    held = max(host["late"], key=lambda se: se[1] - se[0])
    assert held[1] - held[0] >= 300 * MS
    free = max(R.subtract([(lo, hi)], host["covered"]),
               key=lambda se: se[1] - se[0])
    quiet = (free[0], min(free[1], free[0] + 5 * MS))
    gaps = [(full[1] - MS, full[2] + MS), held, quiet]
    assert H.unexplained_ns(gaps[0], host) <= 2 * MS
    assert H.unexplained_ns(held, host) == 0
    assert H.unexplained_ns(quiet, host) == quiet[1] - quiet[0] > 0
    H._reduced.cache_clear()
    left = read("idle_unexplained_ms_max", layers_of(cpu_trace, [gaps]))
    assert left == max(H.unexplained_ns(g, host) for g in gaps) / 1e6
    out = capsys.readouterr().out
    assert "longest idle gap(s) over 1 ms" in out
    assert "hvd.gc.gen2" in out and "late marks cover" in out


def test_another_process_newer_trace_beside_ours_is_passed_over(cpu_trace,
                                                                trace_dir):
    layers = layers_of(cpu_trace)
    newer = trace_dir / "other" / "plugins" / "profile" / "x"
    newer.mkdir(parents=True)
    recorded = RECORDED[0]
    (newer / recorded.name[:-3]).write_bytes(
        gzip.decompress(recorded.read_bytes()))
    assert H.traces_of_this_process(trace_dir)[0].startswith(str(newer))
    assert read("host_pause_ms_max", layers) >= 300.0
    # A reduction whose spans no trace here holds reads nothing.
    H._reduced.cache_clear()
    layers["trace"]["host_spans"] = [("bench.wait_loss", 1, 2)]
    assert [read(m, layers) for m in METRICS] == [None] * 3
