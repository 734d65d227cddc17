"""Every cell's step compiles for a described v5e at the cell's real size.

No chip is attached: the TPU compiler installed here compiles for a
topology that is described (on-chip-measurement guide, section 2.3).  What
it refuses here costs no chip time, and ``memory_analysis()`` is the number
the cells' batches were sized by (at or under 14 GiB a device).  A compile
that passes is not a chip run.

The topology is described inside a module-scoped fixture of this one file:
nothing touches libtpu while a module is imported, so every xdist worker
collects the same tests and only the worker that runs this file loads the
library.
"""

from __future__ import annotations

import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader                      # noqa: E402

GIB = 1024 ** 3
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                         # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache and
    # cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_step(topo, workload: str):
    """The cell's train step compiled for the described devices, from
    shapes alone: (compiled, family, traffic)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    train = loader.load_code("runners", "train")
    cell = loader.load_cell(workload)
    config, traffic = cell["config"], cell["traffic"]
    fam = loader.load_code("families", config["family"]).Family(
        config, traffic["mesh"])
    shape = tuple(fam.mesh_shape.values())
    mesh = Mesh(np.array(topo.devices[:math.prod(shape)]).reshape(shape),
                tuple(fam.mesh_shape))
    replicated = NamedSharding(mesh, P())
    param_shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), fam.param_specs(),
        is_leaf=lambda x: isinstance(x, P))
    opt = train.make_optimizer(config["optimizer"])

    def with_sharding(shapes, shardings):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            shapes, shardings)

    params = jax.eval_shape(fam.init_params, jax.random.PRNGKey(0))
    state = jax.eval_shape(opt.init, params)
    state = with_sharding(state, train.state_shardings(
        state, param_shardings, replicated))
    params = with_sharding(params, param_shardings)
    data = NamedSharding(mesh, P("dp"))
    batch = tuple(
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=data)
        for x in fam.draw_batch(np.random.default_rng(0),
                                traffic["global_batch"]))
    compiled = fam.train_step(mesh, opt).lower(
        params, state, *batch).compile()
    return compiled, fam, traffic


@pytest.mark.parametrize("workload, min_gib, collectives", [
    ("flagship-s8192-train-1chip", 11.0, False),
    ("bert-base-s512-train-1chip", 4.0, False),
    ("flagship-s8192-train-dp2mp2", 11.0, True),
])
def test_cell_step_compiles_for_v5e_under_14_gib(
        topo, monkeypatch, workload, min_gib, collectives):
    # Off the chip the dispatch reads jax.default_backend() and would take
    # the XLA attention branch: steer it here, in the test.
    monkeypatch.setenv("HVD_TPU_FLASH", "1")
    compiled, _fam, _traffic = compile_step(topo, workload)
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 3, "the flash kernels are missing"
    mem = compiled.memory_analysis()
    peak = mem.peak_memory_in_bytes / GIB
    print(f"{workload}: arguments {mem.argument_size_in_bytes / GIB:.2f} "
          f"temporaries {mem.temp_size_in_bytes / GIB:.2f} peak {peak:.2f} "
          f"GiB per device")
    assert min_gib <= peak <= 14.0
    if collectives:
        found = {op for op in COLLECTIVES
                 if f" {op}(" in hlo or f" {op}-start(" in hlo}
        assert {"all-reduce", "all-gather"} <= found, found
