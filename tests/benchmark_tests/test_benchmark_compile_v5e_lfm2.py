"""The LFM2 cell's step compiled for a described v5e at the published
widths.

The same rehearsal as ``test_benchmark_compile_v5e.py`` (no chip; a compile
that passes is not a chip run; its ``topo`` fixture and ``compile_step`` are
used as they are), one module-scoped compile shared by this file's tests
(about a minute).  The batch the traffic file gives peaks under 14 GiB with
12 bytes a parameter of arguments; the step holds the three causal flash
kernels at 32,768 positions — dQ's transposes in four pieces a head, which
whole do not fit Mosaic's scoped VMEM — the convolution block's scopes in
forward, recompute and backward, the dense and the expert blocks' names,
the held experts' grouped matmuls, and no collective.

Marked slow: the compile is a minute of every core alone (32,768 positions
through ten blocks), and in the whole suite, beside five other workers, it
and the Laguna cell's each passed the 180 s ceiling of a test's set-up (PR
41's whole run; ROADMAP B1 (f)).  Run it after a change to the step or the
kernels: ``pytest -m slow tests/benchmark_tests/test_benchmark_compile_v5e_lfm2.py``.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark.trace import laguna, lfm2, moe, scopes     # noqa: E402
from test_benchmark_compile_v5e import (                  # noqa: E402,F401
    COLLECTIVES, GIB, compile_step, topo)
from test_benchmark_compile_v5e_names import KERNEL, OP_NAME  # noqa: E402

CELL = "lfm2-24b-a2b-s32768-train-1chip"
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def compiled(topo):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HVD_TPU_FLASH", "1")
        step, fam, traffic = compile_step(topo, CELL)
    return step.as_text(), step.memory_analysis(), fam, traffic


def test_lfm2_step_compiles_at_the_published_widths_under_14_gib(compiled):
    _hlo, mem, fam, traffic = compiled
    c = fam.c
    assert (c["d_model"], c["attn_head_dim"], c["n_heads"], c["n_kv_heads"],
            c["d_ff"], c["dense_ff"], c["top_k"], c["n_experts"],
            c["n_experts_held"], c["conv_taps"], c["seq_len"],
            c["n_layers"]) == (
                2048, 64, 32, 8, 1536, 11776, 4, 64, 8, 3, 32768, 10)
    assert traffic["global_batch"] == 1
    peak = mem.peak_memory_in_bytes / GIB
    print(f"{CELL}: arguments {mem.argument_size_in_bytes / GIB:.2f} "
          f"temporaries {mem.temp_size_in_bytes / GIB:.2f} peak {peak:.2f} "
          f"GiB per device")
    # Weights and two moments among the arguments, the gradients among the
    # temporaries: 60 % of the chip (batch 2 reads 13.35 GiB: PERF.md
    # section 4 says why the cell keeps ISSUE 41's one sequence).
    assert mem.argument_size_in_bytes >= 12 * c["parameters"]
    assert 9.0 <= peak <= 14.0


def test_lfm2_step_holds_its_names_and_kernels(compiled):
    hlo, _mem, fam, _traffic = compiled
    op_names = OP_NAME.findall(hlo)
    seen = set().union(*(scopes.tokens(o) for o in op_names))
    assert set(lfm2.NAMES) | {laguna.DENSE, "hvd_attn_qknorm",
                              "hvd_attn_rope"} | set(moe.PARTS) | set(
        scopes.BLOCKS) <= seen
    # The gate path in the forward, in the block's recompute and in the
    # backward pass; in the scanned period's body and in the leading block.
    gate = [o for o in op_names if lfm2.GATE in scopes.tokens(o)]
    assert any("transpose(jvp" not in o for o in gate)
    assert any("rematted_computation" in o for o in gate)
    assert any("transpose(jvp" in o and "rematted_computation" not in o
               for o in gate)
    assert any("/while/" in o for o in gate)
    assert any("/while/" not in o for o in gate)
    assert all(lfm2.BLOCK in scopes.tokens(o) for o in gate)
    names = KERNEL.findall(hlo)
    whole = [n.split(".")[0] for n in names]
    # One attention block: one call site a kernel, none in the recompute.
    for kernel in scopes.KERNELS:
        assert whole.count(kernel) == 1, (kernel, names)
    grouped = [n for n in names if n.startswith(moe.GROUPED_MATMUL + "-none")]
    assert grouped, names
    assert all(n.startswith(scopes.KERNELS + (moe.GROUPED_MATMUL,))
               for n in names), names
    # dQ's transposes take a head's 32 query tiles eight at a time.
    dq = next(ln for ln in hlo.splitlines() if "hvd_flash_bwd_dq" in ln
              and "custom-call" in ln)
    assert re.search(r"bf16\[1,32,32,64,1024\]", dq), dq
    # One chip, one rank: no exchange stands in for the absent chips.
    assert not [op for op in COLLECTIVES
                if f" {op}(" in hlo or f" {op}-start(" in hlo]
    assert fam.cfg.leading_pattern == "CD"
    assert fam.cfg.layer_pattern == "*ECECECE" and fam.cfg.n_layers == 10
