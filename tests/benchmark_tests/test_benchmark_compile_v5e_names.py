"""The names every cell's step carries when compiled for a described v5e.

The same compile as ``test_benchmark_compile_v5e.py`` (no chip; a compile
that passes is not a chip run), read for what ``benchmark/trace/scopes.py``
will find in a trace of it: (i) every Pallas kernel's instruction is named
after one of the program's three kernel names, so the trace and the ledger's
``breakdown.device_ops`` name it; (ii) each of the program's five scopes
occurs in some operation's ``op_name``; (iii) names are metadata and do not
move the program: the step compiled with the names taken out again has the
same size in memory, byte for byte.

The sibling's ``topo`` fixture and ``compile_step`` are used as they are:
the topology is described inside a module-scoped fixture, once for this file,
and nothing touches libtpu while a module is imported.
"""

from __future__ import annotations

import contextlib
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark.trace import scopes                # noqa: E402
from test_benchmark_compile_v5e import compile_step, topo  # noqa: E402,F401

CELLS = ("flagship-s8192-train-1chip", "bert-base-s512-train-1chip",
         "flagship-s8192-train-dp2mp2")
KERNEL = re.compile(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"')
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def compiled_cells(topo):
    """{cell: (HLO text, memory analysis)}, each cell compiled once for the
    three tests that read it."""
    cache = {}

    def get(workload):
        if workload not in cache:
            with pytest.MonkeyPatch.context() as mp:
                mp.setenv("HVD_TPU_FLASH", "1")
                compiled, _fam, _traffic = compile_step(
                    topo, workload)
            cache[workload] = (compiled.as_text(),
                               compiled.memory_analysis())
        return cache[workload]

    return get


@pytest.mark.parametrize("workload", CELLS)
def test_every_kernel_instruction_has_a_kernel_name(compiled_cells, workload):
    hlo, _mem = compiled_cells(workload)
    names = KERNEL.findall(hlo)
    assert hlo.count('custom_call_target="tpu_custom_call"') == len(names)
    unnamed = [n for n in names if not n.startswith(scopes.KERNELS)]
    assert not unnamed, unnamed
    # How many there are of each is the program's business (at PR 24 the
    # flagship's stage and layer checkpoints make 3 forward instructions and
    # BERT's one makes 2, with one dQ and one dK/dV each): every one of the
    # three is there.
    count = {k: sum(n.startswith(k) for n in names) for k in scopes.KERNELS}
    print(f"{workload}: kernel instructions {count}")
    assert all(count.values()), count
    forward = [n for n in names if n.startswith("hvd_flash_fwd")]
    # What the trace's event will carry is what the reader classifies.
    line = next(ln for ln in hlo.splitlines() if f"%{forward[0]} = " in ln)
    assert scopes.kernel_of(line.strip().removeprefix("ROOT ")) == (
        "hvd_flash_fwd")


@pytest.mark.parametrize("workload", CELLS)
def test_every_scope_occurs_in_some_op_name(compiled_cells, workload):
    hlo, _mem = compiled_cells(workload)
    op_names = set(OP_NAME.findall(hlo))
    seen = set().union(*(scopes.tokens(o) for o in op_names))
    assert set(scopes.BLOCKS) <= seen, set(scopes.BLOCKS) - seen
    phases = {scopes.phase_of(scopes.tokens(o)) for o in op_names}
    assert {"fwd", "bwd", "optimizer"} <= phases
    # The kernels sit in the attention block, forward and backward.
    kernels = {(scopes.phase_of(t), scopes.block_of(t))
               for t in map(scopes.tokens, op_names) if "pallas_call" in t}
    assert kernels == {("fwd", "hvd_attn"), ("bwd", "hvd_attn")}


@pytest.mark.parametrize("workload", CELLS)
def test_names_do_not_move_the_program(topo, compiled_cells, workload):
    """The same step with the scopes and the kernels' ``name=`` taken out:
    the arguments, temporaries and peak of the compiled program are equal to
    the byte."""
    from jax.experimental import pallas as pl

    from horovod_tpu.models import bert, transformer
    named_hlo, named = compiled_cells(workload)
    real = pl.pallas_call
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HVD_TPU_FLASH", "1")
        for module in (transformer, bert):
            mp.setattr(module, "scope",
                       lambda name: contextlib.nullcontext())
        mp.setattr(pl, "pallas_call",
                   lambda *a, name=None, **kw: real(*a, **kw))
        compiled, _fam, _traffic = compile_step(topo, workload)
    hlo = compiled.as_text()
    assert "hvd_" not in hlo
    assert (hlo.count('custom_call_target="tpu_custom_call"')
            == named_hlo.count('custom_call_target="tpu_custom_call"'))
    bare = compiled.memory_analysis()
    for field in ("argument_size_in_bytes", "temp_size_in_bytes",
                  "peak_memory_in_bytes", "output_size_in_bytes"):
        assert getattr(bare, field) == getattr(named, field), field
