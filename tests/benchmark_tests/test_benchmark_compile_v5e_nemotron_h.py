"""The Nemotron 3 Super cell's step compiled for a described v5e at the
published widths.

The same rehearsal as ``test_benchmark_compile_v5e.py`` (no chip; a compile
that passes is not a chip run; its ``topo`` fixture and ``compile_step`` are
used as they are).  The batch the traffic file gives peaks at or under
14 GiB with 12 bytes a parameter of arguments; the step holds the new
blocks' names, the flash kernels of the one attention block, the held
experts' grouped matmuls as XLA's own Mosaic kernels, and no collective.
(One sequence more peaks at 14.18 GiB: ``PERF.md`` section 4; not compiled
here, a minute a compile.)
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark.trace import moe, scopes, ssm      # noqa: E402
from test_benchmark_compile_v5e import (          # noqa: E402,F401
    COLLECTIVES, GIB, compile_step, topo)
from test_benchmark_compile_v5e_names import KERNEL, OP_NAME  # noqa: E402

CELL = "nemotron-3-super-s8192-train-1chip"


@pytest.fixture(scope="module")
def compiled(topo):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HVD_TPU_FLASH", "1")
        step, fam, traffic = compile_step(topo, CELL)
    return step.as_text(), step.memory_analysis(), fam, traffic


def test_nemotron_step_compiles_at_the_published_widths_under_14_gib(
        compiled):
    _hlo, mem, fam, traffic = compiled
    c = fam.c
    assert (c["d_model"], c["moe_latent"], c["d_ff"], c["shared_expert_ff"],
            c["attn_head_dim"], c["ssm_head_dim"], c["ssm_state"],
            c["ssm_chunk"], c["top_k"], c["n_experts"], c["seq_len"],
            c["n_layers"]) == (4096, 1024, 2688, 5376, 128, 64, 128, 128, 22,
                               512, 8192, 11)
    assert traffic["global_batch"] == 2
    peak = mem.peak_memory_in_bytes / GIB
    print(f"{CELL}: arguments {mem.argument_size_in_bytes / GIB:.2f} "
          f"temporaries {mem.temp_size_in_bytes / GIB:.2f} peak {peak:.2f} "
          f"GiB per device")
    # Weights and two moments among the arguments, the gradients among the
    # temporaries: a deployment's fill, three quarters of the chip.
    assert mem.argument_size_in_bytes >= 12 * c["parameters"]
    assert 11.0 <= peak <= 14.0


def test_nemotron_step_holds_its_names_and_kernels(compiled):
    hlo, _mem, _fam, _traffic = compiled
    seen = set().union(*(scopes.tokens(o) for o in OP_NAME.findall(hlo)))
    assert set(ssm.NAMES) | set(moe.PARTS) | set(scopes.BLOCKS) <= seen
    names = KERNEL.findall(hlo)
    for kernel in scopes.KERNELS:
        assert any(n.startswith(kernel) for n in names), (kernel, names)
    grouped = [n for n in names if n.startswith(moe.GROUPED_MATMUL + "-none")]
    assert grouped, names
    assert all(n.startswith(scopes.KERNELS + (moe.GROUPED_MATMUL,))
               for n in names), names
    # One chip, one rank: no exchange stands in for the absent chips.
    assert not [op for op in COLLECTIVES
                if f" {op}(" in hlo or f" {op}-start(" in hlo]
