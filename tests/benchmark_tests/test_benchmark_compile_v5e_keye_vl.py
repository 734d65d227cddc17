"""The Keye-VL cell's step compiled for a described v5e at the published
widths.

The same rehearsal as ``test_benchmark_compile_v5e.py`` (no chip; a compile
that passes is not a chip run; its ``topo`` fixture and ``compile_step`` are
used as they are), one module-scoped compile shared by this file's tests.
The batch the traffic file gives peaks under 14 GiB with 12 bytes a
parameter of arguments; the step holds the three flash kernels of a selected
call once each (one call site in the scanned period's body), the held
experts' token sums and grouped matmuls, the indexer's scopes, no other
kernel and no collective; and of (S, S) arrays only the int8 visibility: no
fp32 score array of a layer, no array a head.

Marked slow, as the Laguna, the LFM2 and the SmallThinker cells' are and for
their reason: the compile takes every core for a minute (16,384 positions
through eight blocks), and in the whole suite, beside five other workers,
theirs passed the 180 s ceiling of a test's set-up (ROADMAP B1 (f)).  Run it
after a change to the step or the kernels: ``pytest -m slow
tests/benchmark_tests/test_benchmark_compile_v5e_keye_vl.py``.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark.trace import keye_vl, moe, scopes          # noqa: E402
from test_benchmark_compile_v5e import (                  # noqa: E402,F401
    COLLECTIVES, GIB, compile_step, topo)
from test_benchmark_compile_v5e_names import KERNEL, OP_NAME  # noqa: E402

CELL = "keye-vl-2.0-30b-a3b-s16384-train-1chip"
TOKEN_SUM = "hvd_moe_token_sum"
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def compiled(topo):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HVD_TPU_FLASH", "1")
        step, fam, traffic = compile_step(topo, CELL)
    return step.as_text(), step.memory_analysis(), fam, traffic


def test_keye_vl_step_compiles_at_the_published_widths_under_14_gib(compiled):
    _hlo, mem, fam, traffic = compiled
    c = fam.c
    assert (c["d_model"], c["attn_head_dim"], c["n_heads"], c["n_kv_heads"],
            c["d_ff"], c["top_k"], c["n_experts"], c["n_experts_held"],
            c["index_heads"], c["index_head_dim"], c["index_topk"],
            c["vocab_size"], c["seq_len"], c["n_layers"]) == (
                2048, 128, 32, 4, 768, 8, 128, 16, 16, 64, 2048, 18992,
                16384, 8)
    assert traffic["global_batch"] == 1
    peak = mem.peak_memory_in_bytes / GIB
    print(f"{CELL}: arguments {mem.argument_size_in_bytes / GIB:.2f} "
          f"temporaries {mem.temp_size_in_bytes / GIB:.2f} peak {peak:.2f} "
          f"GiB per device")
    # Weights and two moments among the arguments, the gradients among the
    # temporaries.
    assert mem.argument_size_in_bytes >= 12 * c["parameters"]
    assert 9.0 <= peak <= 14.0


def test_keye_vl_step_holds_its_names_and_kernels(compiled):
    hlo, _mem, fam, _traffic = compiled
    op_names = OP_NAME.findall(hlo)
    seen = set().union(*(scopes.tokens(o) for o in op_names))
    assert ({"hvd_attn_rope", "hvd_attn_qknorm"} | set(keye_vl.NAMES)
            | set(moe.PARTS) | set(scopes.BLOCKS) <= seen)
    names = KERNEL.findall(hlo)
    whole = [n.split(".")[0] for n in names]
    # One period in the scan's body: a call site a kernel, none in the
    # recompute (the forward's output, lse and the visibility are saved).
    for kernel in keye_vl.SEL_KERNELS:
        assert whole.count(kernel) == 1, (kernel, names)
    assert TOKEN_SUM in whole
    grouped = [n for n in names if n.startswith(moe.GROUPED_MATMUL + "-none")]
    assert grouped, names
    assert all(n.startswith(keye_vl.SEL_KERNELS + (moe.GROUPED_MATMUL,
                                                   TOKEN_SUM))
               for n in names), names
    # One chip, one rank: no exchange stands in for the absent chips.
    assert not [op for op in COLLECTIVES
                if f" {op}(" in hlo or f" {op}-start(" in hlo]
    assert fam.cfg.layer_pattern == "SE" and fam.cfg.n_layers == 8


def test_keye_vl_step_makes_no_score_array_of_a_layer_or_a_head(compiled):
    """Every buffer with two axes of the sequence's 16,384 is 8 bits an
    element: the visibility (and the booleans it is made from), one a layer
    saved for the backward.  The scores are (512, 16384) tiles."""
    hlo, _mem, _fam, _traffic = compiled
    square = set(re.findall(r"([a-z]+[0-9]*)\[[0-9,]*16384,16384[0-9,]*\]",
                            hlo))
    assert square and square <= {"s8", "pred", "u8"}, square
    assert "s8[4,1,16384,16384]" in hlo        # the four layers' saved
    tiles = set(re.findall(r"f32\[(?:[0-9]+,)*512,16384\]", hlo))
    assert tiles, "the indexer's scores are made a tile of 512 queries"
