"""The Laguna-S-2.1 cell's step compiled for a described v5e at the
published widths.

The same rehearsal as ``test_benchmark_compile_v5e.py`` (no chip; a compile
that passes is not a chip run; its ``topo`` fixture and ``compile_step`` are
used as they are).  The batch the traffic file gives peaks at or under
14 GiB with 12 bytes a parameter of arguments; the step holds the windowed
kernels under their own names beside the full ones, one forward call site
each (the five layers' forwards: one leading full block, three windowed and
one full in the scanned period; the recompute runs none), the new blocks'
names, the held experts' grouped matmuls, and no collective.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark.trace import laguna, moe, scopes   # noqa: E402
from test_benchmark_compile_v5e import (          # noqa: E402,F401
    COLLECTIVES, GIB, compile_step, topo)
from test_benchmark_compile_v5e_names import KERNEL, OP_NAME  # noqa: E402

CELL = "laguna-s-2.1-s8192-train-1chip"


@pytest.fixture(scope="module")
def compiled(topo):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HVD_TPU_FLASH", "1")
        step, fam, traffic = compile_step(topo, CELL)
    return step.as_text(), step.memory_analysis(), fam, traffic


def test_laguna_step_compiles_at_the_published_widths_under_14_gib(compiled):
    _hlo, mem, fam, traffic = compiled
    c = fam.c
    assert (c["d_model"], c["attn_head_dim"], c["dense_ff"], c["d_ff"],
            c["shared_expert_ff"], c["attn_window"], c["top_k"],
            c["n_experts"], c["seq_len"], c["n_layers"]) == (
                3072, 128, 12288, 1024, 1024, 512, 10, 256, 8192, 10)
    assert traffic["global_batch"] == 3
    peak = mem.peak_memory_in_bytes / GIB
    print(f"{CELL}: arguments {mem.argument_size_in_bytes / GIB:.2f} "
          f"temporaries {mem.temp_size_in_bytes / GIB:.2f} peak {peak:.2f} "
          f"GiB per device")
    # Weights and two moments among the arguments, the gradients among the
    # temporaries: a deployment's fill, three quarters of the chip.
    assert mem.argument_size_in_bytes >= 12 * c["parameters"]
    assert 12.5 <= peak <= 14.0


def test_laguna_step_holds_its_names_and_kernels(compiled):
    hlo, _mem, _fam, _traffic = compiled
    seen = set().union(*(scopes.tokens(o) for o in OP_NAME.findall(hlo)))
    assert set(laguna.NAMES) | set(moe.PARTS) | set(scopes.BLOCKS) | {
        "hvd_moe_shared"} <= seen
    names = KERNEL.findall(hlo)
    whole = [n.split(".")[0] for n in names]
    # A call site a kernel and kind of layer outside the scan (the leading
    # full block) and inside it (the period's body): the forward kernels
    # run 1 + 1 full and 3 windowed times a step, five in all, and the
    # layer checkpoint's recompute runs none.
    assert whole.count("hvd_flash_fwd") == 2
    assert whole.count("hvd_flash_fwd_win") == 3
    for kernel in laguna.WINDOW_KERNELS + scopes.KERNELS:
        assert kernel in whole, (kernel, names)
    assert whole.count("hvd_flash_bwd_dq_win") == \
        whole.count("hvd_flash_bwd_dkv_win") == 3
    grouped = [n for n in names if n.startswith(moe.GROUPED_MATMUL + "-none")]
    assert grouped, names
    assert all(n.startswith(scopes.KERNELS + (moe.GROUPED_MATMUL,))
               for n in names), names
    # One chip, one rank: no exchange stands in for the absent chips.
    assert not [op for op in COLLECTIVES
                if f" {op}(" in hlo or f" {op}-start(" in hlo]
