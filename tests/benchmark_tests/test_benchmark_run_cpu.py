"""The benchmark's data files validate, and its command rehearses end to end
on the suite's eight virtual CPU devices at a tiny size.

The rehearsal goes through ``run.main(argv, rehearsal=...)``, an argument
only a Python caller can pass: the command line has no CPU mode.  A rehearsal
line carries counts and the names of the metrics it could compute, never a
time, rate or utilisation under a device metric's name."""

from __future__ import annotations

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader, run                 # noqa: E402

BENCH = loader.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TINY = {"vocab_size": 512, "d_model": 64, "n_heads": 4, "d_ff": 128,
        "n_layers": 2, "seq_len": 128, "max_predictions_per_seq": 16}
WIDTHS = ("d_model", "n_heads", "d_ff")


# -- the data ------------------------------------------------------------------

def test_benchmark_json_meets_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark", "tests/benchmark_tests"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e, m
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert c["file"].startswith("benchmark/")
        assert not set(c["reduced"]) & set(WIDTHS)


def test_every_file_under_paths_has_a_contract_name():
    ok = re.compile(r"[A-Za-z0-9_.\-/]+\Z")
    for root in BENCH["paths"]:
        for p in (loader.REPO_ROOT / root).rglob("*"):
            if "__pycache__" in p.parts or p.suffix == ".pyc":
                continue
            assert ok.match(str(p.relative_to(loader.REPO_ROOT))), p


@pytest.mark.parametrize("workload", CELLS)
def test_cell_files_load_and_every_name_finds_its_code(workload):
    cell = loader.load_cell(workload)
    config, traffic = cell["config"], cell["traffic"]
    assert config["reduced"] == cell["config_entry"]["reduced"]
    family = loader.load_code("families", config["family"])
    assert callable(loader.load_code("reference", config["family"]).loss)
    assert callable(loader.load_code("runners", traffic["runner"]).run)
    fam = family.Family(config, traffic["mesh"])
    chips = 1
    for n in fam.mesh_shape.values():
        chips *= n
    assert chips == cell["entry"]["chips"]
    assert traffic["global_batch"] % fam.dp == 0
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(loader.load_code("metrics", m["name"]).read)


def test_peak_table_is_keyed_by_exact_device_kind():
    assert loader.load_peaks("TPU v5 lite")["flops_per_s_bf16"] == 197e12
    with pytest.raises(loader.BenchmarkError):
        loader.load_peaks("TPU v5")
    with pytest.raises(loader.BenchmarkError):
        loader.load_peaks("cpu")


# -- the command ------------------------------------------------------------------

@pytest.fixture
def interpreted_kernels(monkeypatch):
    """Off the chip the dispatch takes the XLA branch and a Mosaic kernel
    cannot run: ask for the kernels and run them in the Pallas interpreter.
    Steering in the test, no option of the program."""
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setenv("HVD_TPU_FLASH", "1")
    seen = []
    real = fa.flash_attention

    def interpreted(*args, **kwargs):
        seen.append(True)
        return real(*args, **kwargs, interpret=True)

    monkeypatch.setattr(fa, "flash_attention", interpreted)
    return seen


def last_line(capsys) -> dict:
    return json.loads(output_lines(capsys)[-1])


def output_lines(capsys) -> list[str]:
    return capsys.readouterr().out.strip().splitlines()


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_command_rehearses_end_to_end_on_cpu(
        workload, trace, interpreted_kernels, capsys):
    cell = loader.load_cell(workload)
    dp = cell["traffic"]["mesh"]["dp"]
    rc = run.main(
        ["--workload", workload, "--seed", "3", "--seconds", "1",
         "--trace", str(trace)],
        rehearsal=run.Rehearsal(sizes=TINY,
                                traffic={"global_batch": 2 * dp}))
    assert rc == 0
    assert interpreted_kernels, "the flash kernels did not engage"
    out = output_lines(capsys)
    line = json.loads(out[-1])
    # The loss is compared in every run; the gradients in every run or in
    # the traced run only, as the cell's traffic file says.
    checks = [ln for ln in out if "reference check" in ln]
    with_gradients = [ln for ln in checks if "relative L2" in ln]
    assert checks and all(ln.endswith("-> ok") for ln in checks)
    if cell["traffic"]["gradient_check"] == "every_run":
        assert len(checks) == 1 and with_gradients == checks
    else:
        assert cell["traffic"]["gradient_check"] == "traced_run"
        assert len(checks) == 1 + trace and with_gradients == checks[1:]
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    # Names only: no CPU number under a device metric's name.
    assert set(line["metrics"]) == {"rehearsal_names"}
    names = set(line["metrics"]["rehearsal_names"])
    wanted = {m["name"] for m in
              (cell["per_layer"] if trace else cell["end_to_end"])}
    assert names <= wanted
    if trace:
        # Spans, counters and the compiled step give values anywhere; the
        # device-trace readers find no TPU plane here and return nothing.
        assert names == {m["name"] for m in cell["per_layer"]
                         if m["source"] != "device_trace"}
    else:
        assert names == wanted - {"mfu"}        # no peak for a CPU


def test_command_refuses_to_run_without_a_tpu(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0
    assert "no CPU mode" in captured.err
    assert "{" not in captured.out


def test_command_refuses_an_unknown_cell(capsys):
    rc = run.main(["--workload", "no-such-cell", "--seed", "1", "--seconds",
                   "1", "--trace", "0"], rehearsal=run.Rehearsal())
    assert rc != 0 and "{" not in capsys.readouterr().out


def test_command_refuses_an_unknown_gradient_check(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"],
                  rehearsal=run.Rehearsal(
                      sizes=TINY, traffic={"gradient_check": "sometimes"}))
    captured = capsys.readouterr()
    assert rc != 0 and "gradient_check" in captured.err
    assert "{" not in captured.out


def fake_window(completions, *, seconds, warmup_steps, monkeypatch):
    """A ``Window`` fed completions at the given times on a fake clock."""
    train = loader.load_code("runners", "train")

    class Compiles:
        on = False

    window = train.Window(seconds=seconds, warmup_steps=warmup_steps,
                          compiles=Compiles(), trace_dir=None, trace_steps=0)
    for t in completions:
        monkeypatch.setattr(train.time, "perf_counter", lambda t=t: t)
        if window.t_close is None:
            window.completed(1.0)
    return window


@pytest.mark.parametrize("stall, want_steps, want_seconds", [
    (0.0, 5, 10.0),         # steady: completions 2 s apart, window of 10 s
    (3.0, 4, 11.0),         # one completion 3 s late and the next on time
    (-0.5, 5, 10.0),        # a completion seen early: the pace is kept
])
def test_throughput_is_the_windows_steps_over_its_time(
        monkeypatch, stall, want_steps, want_seconds):
    """The end-to-end rate counts the whole steps between the two
    completions that bound the window, so a step that took longer costs
    throughput; the median step does not see it."""
    times = [2.0 * i for i in range(1, 12)]
    times[4] += stall                          # the fourth step of the window
    if stall > 0:
        times[5:] = [t + stall for t in times[5:]]   # the device fell behind
    w = fake_window(times, seconds=10.0, warmup_steps=1,
                    monkeypatch=monkeypatch)
    assert (w.i_open, w.t_open) == (1, 2.0)
    assert w.i_close - w.i_open == want_steps
    assert w.t_close - w.t_open == pytest.approx(want_seconds)
    assert w.steps_per_s() == pytest.approx(want_steps / want_seconds)
    assert len(w.step_ms()) == want_steps
    assert sum(w.step_ms()) == pytest.approx(1e3 * want_seconds)
    import statistics
    assert statistics.median(w.step_ms()) == pytest.approx(2000.0)


def test_a_run_without_an_end_to_end_value_has_no_result():
    cell = loader.load_cell(CELLS[0])
    some = {"setup_s": 20.0, "tokens_per_s_per_chip": None, "mfu": 16.0}
    with pytest.raises(loader.BenchmarkError, match="tokens_per_s_per_chip"):
        run.cell_metrics(cell, {"end_to_end": some}, False, None)
    every = {**some, "tokens_per_s_per_chip": 1e4}
    assert set(run.cell_metrics(cell, {"end_to_end": every}, False, None)) \
        == {"setup_s", "tokens_per_s_per_chip", "mfu"}


def test_a_reader_that_finds_nothing_leaves_its_metric_out(capsys):
    """Per layer the contract's rule: no value, no entry, said aloud; a
    traced run with no per-layer value at all has no result."""
    cell = loader.load_cell(CELLS[0])
    layers = {"spans": {"setup_check": 2.0}, "step_ms": [], "trace": None,
              "compiles_in_window": 0, "step_peak_bytes": 1e9,
              "attention": None, "peaks": None}
    got = run.cell_metrics(cell, {"layers": layers}, True, None)
    assert set(got) == {"setup_check_s", "compiles_in_window", "peak_hbm_gb"}
    assert "attn_kernel_ms_per_step" in capsys.readouterr().out
    cell["per_layer"] = [m for m in cell["per_layer"]
                         if m["source"] == "device_trace"]
    with pytest.raises(loader.BenchmarkError):
        run.cell_metrics(cell, {"layers": layers}, True, None)


def test_adding_a_cell_is_data_only(tmp_path, interpreted_kernels, capsys):
    """What a later PR adds for ``flagship-s1024-train-1chip``: one
    configuration file, one traffic file and the entries in
    ``BENCHMARK.json``.  No existing file is touched and no code is added."""
    before = {p: p.read_bytes()
              for p in (loader.REPO_ROOT / "benchmark").rglob("*.json")}
    data = tmp_path / "checkout"
    shutil.copytree(loader.REPO_ROOT / "benchmark" / "configs",
                    data / "benchmark" / "configs")
    shutil.copytree(loader.REPO_ROOT / "benchmark" / "traffic",
                    data / "benchmark" / "traffic")
    base = loader.read_json(
        data / "benchmark" / "configs" / "flagship-12l-s8192.json")
    (data / "benchmark" / "configs" / "flagship-12l-s1024.json").write_text(
        json.dumps({**base, "seq_len": 1024}))
    (data / "benchmark" / "traffic" / "train-b40-1chip.json").write_text(
        json.dumps({"runner": "train", "global_batch": 40,
                    "mesh": {"dp": 1, "pp": 1, "mp": 1}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "flagship-12l-s1024", "source": "this repository",
        "file": "benchmark/configs/flagship-12l-s1024.json", "reduced": [],
        "why": "the fp32 vocabulary head and the MLP take the step"})
    bench["workloads"].append({
        "name": "flagship-s1024-train-1chip", "config": "flagship-12l-s1024",
        "traffic": "train-b40-1chip", "chips": 1, "why": "S1's cell"})
    (data / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = loader.load_cell("flagship-s1024-train-1chip", data_root=data)
    assert cell["config"]["seq_len"] == 1024
    assert cell["traffic"]["global_batch"] == 40
    assert "collective_share" not in {m["name"] for m in cell["per_layer"]}
    rc = run.main(
        ["--workload", "flagship-s1024-train-1chip", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        rehearsal=run.Rehearsal(sizes=TINY, traffic={"global_batch": 2},
                                data_root=data))
    assert rc == 0 and last_line(capsys)["correct"] is True
    after = {p: p.read_bytes()
             for p in (loader.REPO_ROOT / "benchmark").rglob("*.json")}
    assert after == before
