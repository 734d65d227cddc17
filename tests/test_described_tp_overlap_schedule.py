"""The four-chip flagship step's schedule, compiled for a described v5e:2x2:
every transfer over ``mp`` is an asynchronous collective-permute with a matmul
between its start and its done.

``parallel/tensor_parallel.py`` writes the sequence-parallel gathers and
scatters as rings of ``lax.ppermute`` because that is the one form of the
traffic this compiler runs beside independent compute (ISSUE 37: an
all-gather or a reduce-scatter it emits synchronous).  Whether it does is a
property of the compiled schedule, read here from the scheduled HLO of
``flagship-s8192-train-dp2mp2`` lowered from shapes alone, as the benchmark's
``test_benchmark_compile_v5e.compile_step`` lowers it (no chip: a schedule
that passes is not a chip run, and says nothing of how long a transfer
takes).  This file also holds what that test's ``all-gather`` assertion
meant for this cell: collectives over ``mp`` and over ``dp`` are both there.

The topology is described inside the sibling's module-scoped fixture, so
nothing touches libtpu while a module is imported.

(``tests/test_tp_overlap_schedule.py`` until PR 48.  Its one fixture is a
described compile of the whole four-chip step, two minutes beside five other
workers; under ``--dist loadfile`` a file starts in the alphabet's order, and
as one of the last it was what the suite's time limit cut.)
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "tests" / "benchmark_tests"))

from test_benchmark_compile_v5e import compile_step, topo  # noqa: E402,F401

CELL = "flagship-s8192-train-dp2mp2"
GIB = 1024 ** 3
# mesh (dp, pp, mp) = (2, 1, 2) over devices 0..3: mp pairs neighbours.
MP_PAIRS = {(0, 1), (1, 0), (2, 3), (3, 2)}
MP_GROUPS, DP_GROUPS = "{{0,1},{2,3}}", "{{0,2},{1,3}}"
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(")
COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\) -> .* \{$")


def computations(hlo: str):
    """{name: [instruction line, ...]} of a scheduled module's text: the
    order of the lines is the order the device runs them in."""
    out, name = {}, None
    for line in hlo.splitlines():
        head = COMPUTATION.match(line)
        if head:
            name = head.group(1)
            out[name] = []
        elif name is not None and line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def is_matmul(line: str, fused: dict) -> bool:
    """A fusion whose computation holds a convolution (what a dot is on
    this compiler), or a bare one."""
    if " convolution(" in line:
        return True
    callee = re.search(r" fusion\(.*calls=(%[\w.\-]+)", line)
    return bool(callee) and any(
        " convolution(" in inner for inner in fused.get(callee.group(1), ()))


def permutes(lines, fused):
    """[(op_name, matmuls between start and done)] of the asynchronous
    collective-permutes over the ``mp`` pairs in one computation."""
    open_, out = {}, []
    for line in lines:
        found = INSTRUCTION.match(line)
        if not found:
            continue
        name, op = found.groups()
        if op == "collective-permute-start":
            pairs = re.search(r"source_target_pairs=\{([\d,{}]*)\}", line)
            if {tuple(map(int, pair.split(","))) for pair in re.findall(
                    r"\{(\d+,\d+)\}", pairs.group(1))} == MP_PAIRS:
                open_[name] = 0
        elif op == "collective-permute-done":
            start = re.search(r"collective-permute-done\((%[\w.\-]+)",
                              line).group(1)
            if start in open_:
                op_name = re.search(r'op_name="([^"]*)"', line)
                out.append((op_name.group(1) if op_name else "",
                            open_.pop(start)))
        elif is_matmul(line, fused):
            for start in open_:
                open_[start] += 1
    assert not open_, f"collective-permute-start without a done: {open_}"
    return out


@pytest.fixture(scope="module")
def step(topo):                                   # noqa: F811
    with pytest.MonkeyPatch.context() as mp:
        # Off the chip the dispatch would take the XLA attention branch.
        mp.setenv("HVD_TPU_FLASH", "1")
        compiled, _fam, _traffic = compile_step(topo, CELL)
    return compiled.as_text(), compiled.memory_analysis()


def test_every_mp_permute_has_a_matmul_between_start_and_done(step):
    hlo, _mem = step
    comps = computations(hlo)
    rings = [p for lines in comps.values() for p in permutes(lines, comps)]
    # Forward, rematerialised and backward bodies, gathers and scatters
    # (the scan over the layers lies under ``hvd_layers``).
    phases = ("jvp()", "rematted_computation",
              "transpose(jvp())/shard_map/hvd_layers/while/body/closed_call/"
              "checkpoint/hvd_")
    for phase in phases:
        for form in ("hvd_tp_ring_gather", "hvd_tp_ring_scatter"):
            assert any(phase in name and form in name
                       for name, _ in rings), (phase, form)
    exposed = [name for name, matmuls in rings if matmuls == 0]
    assert not exposed, exposed
    assert all("hvd_tp_ring_" in name for name, _ in rings)


def test_no_synchronous_gather_or_scatter_over_mp_is_left(step):
    hlo, _mem = step
    left = [line.split(" = ")[0].strip() for line in hlo.splitlines()
            if re.search(r" (all-gather|reduce-scatter)(-start)?\(", line)
            and f"replica_groups={MP_GROUPS}" in line]
    assert not left, left


def test_the_gradients_all_reduce_over_dp_is_still_there(step):
    hlo, _mem = step
    assert any(re.search(r" all-reduce(-start)?\(", line)
               and f"replica_groups={DP_GROUPS}" in line
               for line in hlo.splitlines())


def test_the_step_fits_the_14_gib_rule_with_the_kernels_in(step):
    hlo, mem = step
    assert hlo.count("tpu_custom_call") >= 3, "the flash kernels are missing"
    peak = mem.peak_memory_in_bytes / GIB
    print(f"{CELL}: peak {peak:.3f} GiB per device")
    assert 11.0 <= peak <= 14.0
