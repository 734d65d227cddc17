"""The SmallThinker cell's step compiled for a described v5e at the
published widths.

The same rehearsal as ``test_benchmark_compile_v5e.py`` (no chip; a compile
that passes is not a chip run; its ``topo`` fixture and ``compile_step`` are
used as they are), one module-scoped compile shared by this file's tests.
The batch the traffic file gives peaks under 14 GiB with 12 bytes a
parameter of arguments; the step holds the three windowed flash kernels once
each (one call site in the scanned period's unrolled body a windowed block,
three blocks) and the three causal ones, the rotation's scope in the
windowed blocks only, the held experts' grouped matmuls, and no collective.

Marked slow, as the Laguna and the LFM2 cells' are and for their reason: the
compile takes every core for a minute and more (16,384 positions through
eight blocks), and in the whole suite, beside five other workers, theirs
passed the 180 s ceiling of a test's set-up (ROADMAP B1 (f)).  Run it after a
change to the step or the kernels: ``pytest -m slow
tests/benchmark_tests/test_benchmark_compile_v5e_smallthinker.py``.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark.trace import laguna, moe, scopes           # noqa: E402
from test_benchmark_compile_v5e import (                  # noqa: E402,F401
    COLLECTIVES, GIB, compile_step, topo)
from test_benchmark_compile_v5e_names import KERNEL, OP_NAME  # noqa: E402

CELL = "smallthinker-21b-a3b-s16384-train-1chip"
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def compiled(topo):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HVD_TPU_FLASH", "1")
        step, fam, traffic = compile_step(topo, CELL)
    return step.as_text(), step.memory_analysis(), fam, traffic


def test_smallthinker_step_compiles_at_the_published_widths_under_14_gib(
        compiled):
    _hlo, mem, fam, traffic = compiled
    c = fam.c
    assert (c["d_model"], c["attn_head_dim"], c["n_heads"], c["n_kv_heads"],
            c["d_ff"], c["attn_window"], c["top_k"], c["n_experts"],
            c["n_experts_held"], c["vocab_size"], c["seq_len"],
            c["n_layers"]) == (
                2560, 128, 28, 4, 768, 4096, 6, 64, 16, 18992, 16384, 8)
    assert traffic["global_batch"] == 1
    peak = mem.peak_memory_in_bytes / GIB
    print(f"{CELL}: arguments {mem.argument_size_in_bytes / GIB:.2f} "
          f"temporaries {mem.temp_size_in_bytes / GIB:.2f} peak {peak:.2f} "
          f"GiB per device")
    # Weights and two moments among the arguments, the gradients among the
    # temporaries.
    assert mem.argument_size_in_bytes >= 12 * c["parameters"]
    assert 9.0 <= peak <= 14.0


def test_smallthinker_step_holds_its_names_and_kernels(compiled):
    hlo, _mem, fam, _traffic = compiled
    op_names = OP_NAME.findall(hlo)
    seen = set().union(*(scopes.tokens(o) for o in op_names))
    assert {"hvd_attn_rope"} | set(moe.PARTS) | set(scopes.BLOCKS) <= seen
    # No gate, no QK-norm, no dense block, no shared expert in this model.
    assert not {"hvd_attn_gate", "hvd_attn_qknorm", laguna.DENSE,
                "hvd_moe_shared"} & seen
    names = KERNEL.findall(hlo)
    whole = [n.split(".")[0] for n in names]
    # One period, unrolled in the scan's body: a call site a kernel and
    # block, three windowed and one full, none in the recompute.
    for kernel in laguna.WINDOW_KERNELS:
        assert whole.count(kernel) == 3, (kernel, names)
    for kernel in scopes.KERNELS:
        assert whole.count(kernel) == 1, (kernel, names)
    grouped = [n for n in names if n.startswith(moe.GROUPED_MATMUL + "-none")]
    assert grouped, names
    assert all(n.startswith(scopes.KERNELS + (moe.GROUPED_MATMUL,))
               for n in names), names
    # One chip, one rank: no exchange stands in for the absent chips.
    assert not [op for op in COLLECTIVES
                if f" {op}(" in hlo or f" {op}-start(" in hlo]
    assert fam.cfg.layer_pattern == "*EWEWEWE" and fam.cfg.n_layers == 8
    assert fam.cfg.router_before_attention and fam.cfg.rope_theta is None
