"""The OLMoE cell's step compiled for a described v5e at the published
widths, and the flagship's step beside it, lowered with the defaults.

The same rehearsal as ``test_benchmark_compile_v5e.py`` (no chip; a compile
that passes is not a chip run; its ``topo`` fixture and ``compile_step`` are
used as they are).  For the new cell: the batch the traffic file gives peaks
at or under 14 GiB and one sequence more does not fit that rule's sense (the
file says "the largest"); the step holds the dropless path's three names,
its grouped matmuls as XLA's own Mosaic kernels, and the flash forward twice
(forward and the layer's recompute).  For the flagship: nothing of what this
configuration brought (no rotary position, no sort, no ``hvd_moe_*`` name, no
grouped matmul), and the parameter tree it had.
"""

from __future__ import annotations

import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import loader                      # noqa: E402
from benchmark.trace import moe, scopes           # noqa: E402
from test_benchmark_compile_v5e import GIB, compile_step, topo  # noqa: E402,F401
from test_benchmark_compile_v5e_names import KERNEL, OP_NAME  # noqa: E402

CELL = "olmoe-1b-7b-s4096-train-1chip"
FLAGSHIP = "flagship-s8192-train-1chip"
MOE_NAMES = {"hvd_moe_route", "hvd_moe_dispatch", "hvd_moe_experts"}


@pytest.fixture(scope="module")
def compiled(topo):
    """{cell: (HLO text, memory analysis, family)}, each compiled once."""
    cache = {}

    def get(workload):
        if workload not in cache:
            with pytest.MonkeyPatch.context() as mp:
                mp.setenv("HVD_TPU_FLASH", "1")
                step, fam, _traffic = compile_step(topo, workload)
            cache[workload] = (step.as_text(), step.memory_analysis(), fam)
        return cache[workload]

    return get


def test_olmoe_step_compiles_at_the_published_widths_under_14_gib(compiled):
    hlo, mem, fam = compiled(CELL)
    c = fam.c
    assert (c["d_model"], c["n_heads"], c["d_ff"], c["n_experts"],
            c["top_k"], c["vocab_size"], c["seq_len"], c["n_layers"]) == (
                2048, 16, 1024, 64, 8, 50304, 4096, 1)
    peak = mem.peak_memory_in_bytes / GIB
    print(f"{CELL}: arguments {mem.argument_size_in_bytes / GIB:.2f} "
          f"temporaries {mem.temp_size_in_bytes / GIB:.2f} peak {peak:.2f} "
          f"GiB per device")
    # 12 bytes a parameter of arguments (weights and two moments), and the
    # gradients among the temporaries: a deployment's fill, over the 25 %
    # floor by a wide margin.
    assert mem.argument_size_in_bytes >= 12 * c["parameters"]
    assert 11.0 <= peak <= 14.0


def test_olmoe_step_holds_its_names_and_kernels(compiled):
    hlo, _mem, _fam = compiled(CELL)
    seen = set().union(*(scopes.tokens(o) for o in OP_NAME.findall(hlo)))
    assert MOE_NAMES | set(scopes.BLOCKS) <= seen
    names = KERNEL.findall(hlo)
    flash_fwd = [n for n in names if n.startswith("hvd_flash_fwd")]
    assert len(flash_fwd) == 2, names       # the forward and its recompute
    assert any(n.startswith("hvd_flash_bwd_dq") for n in names)
    assert any(n.startswith("hvd_flash_bwd_dkv") for n in names)
    # lax.ragged_dot: three grouped matmuls forward, three in the layer's
    # recompute, a data and a weight gradient for each backward, as Mosaic
    # kernels XLA names itself; what trace/moe.py recognises them by.
    grouped = [n for n in names if n.startswith(moe.GROUPED_MATMUL + "-none")]
    assert len(grouped) == 12, names
    line = next(ln for ln in hlo.splitlines() if f"%{grouped[0]} = " in ln)
    text = line.strip().removeprefix("ROOT ")
    assert moe.is_grouped_matmul(text)
    assert moe.part_of(text, "ragged-dot-none") == "hvd_moe_experts"
    assert scopes.kernel_of(text) is None
    # Every other kernel is one of the program's three or the grouped
    # matmul's small metadata kernel.
    assert all(n.startswith(scopes.KERNELS + (moe.GROUPED_MATMUL,))
               for n in names), names
    # The (token, choice) pairs are sorted, not one-hot dispatched.
    assert " sort(" in hlo


def test_flagship_step_has_nothing_of_the_new_configuration(compiled):
    import jax
    hlo, _mem, fam = compiled(FLAGSHIP)
    assert "hvd_moe" not in hlo and moe.GROUPED_MATMUL not in hlo
    assert " cosine(" not in hlo and " sine(" not in hlo     # no rotary
    # The one sort a step has always had: the embedding gradient's
    # scatter-add orders its indices.
    sorts = [ln for ln in hlo.splitlines() if " sort(" in ln]
    assert all("hvd_embed" in ln for ln in sorts), sorts
    shapes = jax.eval_shape(fam.init_params, jax.random.PRNGKey(0))
    tree = {jax.tree_util.keystr(k): v.shape for k, v in
            jax.tree_util.tree_leaves_with_path(shapes)}
    assert tree == {
        "['embed']": (32768, 1024), "['final_norm']": (1024,),
        "['pos']": (8192, 1024),
        "['layers']['ln1']": (1, 12, 1024), "['layers']['ln2']": (1, 12, 1024),
        "['layers']['wqkv']": (1, 12, 1024, 3072),
        "['layers']['wo']": (1, 12, 1024, 1024),
        "['layers']['w1']": (1, 12, 1024, 4096),
        "['layers']['w2']": (1, 12, 4096, 1024)}
    assert loader.load_cell(FLAGSHIP)["config"]["parameters"] == sum(
        math.prod(s) for s in tree.values())
