"""The SDAR cell's step compiled for a described v5e at the published
widths.

The same rehearsal as ``test_benchmark_compile_v5e.py`` (no chip; a compile
that passes is not a chip run; its ``topo`` fixture and ``compile_step`` are
used as they are).  The batch the traffic file gives peaks at or under
14 GiB with 12 bytes a parameter of arguments; the step holds the
block-diffusion kernels under their own names and no attention kernel
without the suffix, one forward call site (the six layers' forwards are the
scanned period's body; the recompute runs none), the new scopes, the held
experts' grouped matmuls, and no collective.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark.trace import moe, scopes, sdar     # noqa: E402
from test_benchmark_compile_v5e import (          # noqa: E402,F401
    COLLECTIVES, GIB, compile_step, topo)
from test_benchmark_compile_v5e_names import KERNEL, OP_NAME  # noqa: E402

CELL = "sdar-30b-a3b-s4096-train-1chip"
BD_KERNELS = ("hvd_flash_fwd_bd", "hvd_flash_bwd_dq_bd",
              "hvd_flash_bwd_dkv_bd")


@pytest.fixture(scope="module")
def compiled(topo):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HVD_TPU_FLASH", "1")
        step, fam, traffic = compile_step(topo, CELL)
    return step.as_text(), step.memory_analysis(), fam, traffic


def test_sdar_step_compiles_at_the_published_widths_under_14_gib(compiled):
    _hlo, mem, fam, traffic = compiled
    c = fam.c
    assert (c["d_model"], c["attn_head_dim"], c["n_heads"], c["n_kv_heads"],
            c["d_ff"], c["top_k"], c["n_experts"], c["n_experts_held"],
            c["seq_len"], c["diffusion_block"], c["n_layers"]) == (
                2048, 128, 32, 4, 768, 8, 128, 16, 4096, 4, 12)
    assert traffic["global_batch"] == 1
    peak = mem.peak_memory_in_bytes / GIB
    print(f"{CELL}: arguments {mem.argument_size_in_bytes / GIB:.2f} "
          f"temporaries {mem.temp_size_in_bytes / GIB:.2f} peak {peak:.2f} "
          f"GiB per device")
    # Weights and two moments among the arguments, the gradients among the
    # temporaries: a deployment's fill, three quarters of the chip.
    assert mem.argument_size_in_bytes >= 12 * c["parameters"]
    assert 11.5 <= peak <= 14.0


def test_sdar_step_holds_its_names_and_kernels(compiled):
    hlo, _mem, _fam, _traffic = compiled
    seen = set().union(*(scopes.tokens(o) for o in OP_NAME.findall(hlo)))
    assert set(sdar.NAMES) | set(moe.PARTS) | set(scopes.BLOCKS) <= seen
    names = KERNEL.findall(hlo)
    whole = [n.split(".")[0] for n in names]
    # One call site a kernel, in the scanned period's body: the forward
    # kernel runs six times a step (the trace counts them:
    # ``attn_fwd_kernel_calls_per_step``) and the layer checkpoint's
    # recompute runs none.
    for kernel in BD_KERNELS:
        assert whole.count(kernel) == 1, (kernel, names)
    # No attention kernel without the suffix: every call carries the mask.
    assert not set(whole) & set(scopes.KERNELS), names
    grouped = [n for n in names if n.startswith(moe.GROUPED_MATMUL + "-none")]
    assert grouped, names
    assert all(n.startswith(scopes.KERNELS + (moe.GROUPED_MATMUL,))
               for n in names), names
    # One chip, one rank: no exchange stands in for the absent chips.
    assert not [op for op in COLLECTIVES
                if f" {op}(" in hlo or f" {op}-start(" in hlo]
    # The scan runs the period six times.
    assert _fam.cfg.n_layers == 12 and _fam.cfg.layer_pattern == "*E"
