"""A pipeline of one stage is its stage (ISSUE 25).

When the pipeline axis has one member ``pipeline_apply`` maps the stage over
the microbatches: no tick loop, no ``where`` on the stage index, no output
buffer, no permute of the activation to itself; and the transformer asks for
the checkpoint around the whole stage only where there are stages to fill and
drain, so at ``pp`` = 1 the forward runs twice a step (once, and once a layer
in the backward pass), not three times.  The same operations on the same
values: results equal the tick loop's, which stays the multi-stage path
untouched (``test_parallel.py`` at pp = 4, ``test_moe_pipeline.py`` at pp = 2).

The last test is the off-chip proof: both flagship cells' steps compiled for a
described ``v5e:2x2`` (no chip; a compile that passes is not a chip run).  The
topology is described inside the benchmark tests' module-scoped fixtures, used
as they are: nothing touches libtpu while a module is imported.
"""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.compat import shard_map
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import pipeline as pp_lib
from horovod_tpu.parallel.mesh import create_mesh

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmark_tests"))

from benchmark.trace.scopes import KERNELS        # noqa: E402
from test_benchmark_compile_v5e import GIB, topo  # noqa: E402,F401
from test_benchmark_compile_v5e_names import (    # noqa: E402,F401
    KERNEL, compiled_cells)

CFG = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, d_ff=64, n_layers=2, seq_len=128,
    dtype=jnp.float32, remat=True)
CHECKPOINT = "checkpoint"       # reported under this name; JAX's is "remat2"


def walk(jaxpr, depth=0):
    """(primitive name, number of enclosing checkpoints) of every equation,
    through every sub-jaxpr (scan and checkpoint bodies, shard_map, pjit)."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("remat2", "remat"):
            name = CHECKPOINT
        yield name, depth
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from walk(sub, depth + (name == CHECKPOINT))


def primitives(fn, *args):
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


# -- pipeline_apply at one stage, a bare stage function ------------------------

def stage_fn(w, a):
    return jnp.tanh(a @ w)


def mapped(apply, mesh=None):
    """``apply(stage_fn, w, xs, "pp")`` under shard_map on a mesh whose
    ``pp`` axis has one member: (w, xs) -> (n_micro, mb, d)."""
    mesh = mesh or create_mesh({"pp": 1}, devices=jax.devices()[:1])
    return shard_map(lambda w, xs: apply(stage_fn, w, xs, "pp"), mesh=mesh,
                     in_specs=(P(), P()), out_specs=P(), check_vma=False)


def tick_loop(remat):
    """What ``pipeline_apply`` built at one stage before ISSUE 25."""
    def apply(fn, w, xs, axis_name):
        fn = jax.checkpoint(fn) if remat else fn
        return pp_lib._gpipe_forward(fn, w, xs, axis_name)
    return apply


def one_stage(remat):
    return lambda fn, w, xs, axis_name: pp_lib.pipeline_apply(
        fn, w, xs, axis_name, remat=remat)


def inputs(n_micro, mb=2, d=8):
    w = jax.random.normal(jax.random.PRNGKey(0), (d, d)) * 0.5
    xs = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
    return w, xs


@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("n_micro", [1, 4])
def test_one_stage_equals_the_stage_mapped_and_the_tick_loop(n_micro, remat):
    w, xs = inputs(n_micro)
    cot = jax.random.normal(jax.random.PRNGKey(2), xs.shape)

    def out_and_grads(fn):
        out, vjp = jax.vjp(jax.jit(fn), w, xs)
        return (out,) + vjp(cot)

    got = out_and_grads(mapped(one_stage(remat)))
    plain = out_and_grads(
        lambda w, xs: jnp.stack([stage_fn(w, x) for x in xs]))
    ticks = out_and_grads(mapped(tick_loop(remat)))
    assert got[0].shape == xs.shape
    # The same operations on the same values as the tick loop: the forward
    # to the bit (XLA batches the unrolled reference's matmuls otherwise).
    assert np.asarray(got[0]).tobytes() == np.asarray(ticks[0]).tobytes()
    np.testing.assert_allclose(got[0], plain[0], rtol=1e-6, atol=1e-7)
    for g, p, t in zip(got[1:], plain[1:], ticks[1:]):
        np.testing.assert_allclose(g, p, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(g, t, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("n_micro", [1, 4])
def test_one_stage_builds_no_tick_loop(n_micro, remat):
    w, xs = inputs(n_micro)
    names = [n for n, _ in primitives(mapped(one_stage(remat)), w, xs)]
    # No permute of the activation to itself, no where(stage == 0, ...), no
    # masked write into an output buffer; a scan only to map microbatches.
    for gone in ("ppermute", "select_n", "axis_index",
                 "dynamic_update_slice", "dynamic_slice"):
        assert gone not in names, (gone, names)
    assert names.count("scan") == (n_micro > 1)
    # remat keeps its meaning: a bare stage function is checkpointed once.
    assert names.count(CHECKPOINT) == remat
    # The reader is not blind: the tick loop has all of them.
    old = [n for n, _ in primitives(mapped(tick_loop(remat)), w, xs)]
    for there in ("ppermute", "select_n", "axis_index",
                  "dynamic_update_slice", "scan"):
        assert there in old, (there, old)


def test_two_stages_still_build_the_tick_loop():
    mesh = create_mesh({"pp": 2}, devices=jax.devices()[:2])
    w, xs = inputs(4)
    names = [n for n, _ in primitives(mapped(one_stage(True), mesh), w, xs)]
    for there in ("ppermute", "select_n", "dynamic_update_slice", "scan",
                  CHECKPOINT):
        assert there in names, (there, names)


def test_no_microbatch_is_refused():
    w, xs = inputs(1)
    with pytest.raises(ValueError, match="at least one microbatch"):
        mapped(one_stage(True))(w, xs[:0])


# -- the transformer: which checkpoints the loss asks for ----------------------

def loss_and_args(par, cfg=CFG, batch=4):
    mesh = create_mesh({"dp": par.dp, "pp": par.pp, "mp": par.mp},
                       devices=jax.devices()[:par.dp * par.pp * par.mp])
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, par)
    data = tfm.synthetic_batch(jax.random.PRNGKey(1), cfg, batch)
    return tfm.make_loss_fn(cfg, par, mesh), (params, *data)


def checkpoint_depths(par, cfg=CFG):
    """Sorted nesting depths (0 = outermost) of the loss's checkpoints."""
    loss, args = loss_and_args(par, cfg)
    return sorted(d for n, d in primitives(loss, *args) if n == CHECKPOINT)


@pytest.mark.parametrize("n_micro", [1, 2])
def test_one_stage_loss_has_no_nested_checkpoint(n_micro):
    hvd.init()
    par = tfm.ParallelConfig(dp=1, pp=1, mp=1, n_microbatches=n_micro)
    assert checkpoint_depths(par) == [0]         # the layer's, alone
    loss, args = loss_and_args(par)
    names = [n for n, _ in primitives(loss, *args)]
    assert "ppermute" not in names
    assert "dynamic_update_slice" not in names


def test_two_stage_loss_keeps_the_nested_pair():
    hvd.init()
    par = tfm.ParallelConfig(dp=1, pp=2, mp=1, n_microbatches=2)
    assert checkpoint_depths(par) == [0, 1]      # the stage's around the layer's


def test_without_remat_no_checkpoint_at_all():
    hvd.init()
    par = tfm.ParallelConfig(dp=1, pp=1, mp=1)
    assert checkpoint_depths(par, CFG._replace(remat=False)) == []


# -- the flagship's loss and gradients against the parent's formulation --------

def parent_pipeline_apply(stage_fn, stage_params, xs, axis_name, remat=True):
    """PR 24's: the tick loop around a checkpointed stage whatever the
    axis size (``forward_loss`` passed ``remat=cfg.remat``, True here)."""
    return pp_lib._gpipe_forward(jax.checkpoint(stage_fn), stage_params, xs,
                                 axis_name)


@pytest.mark.parametrize("dp, mp, n_micro", [(1, 1, 1), (2, 2, 1), (1, 1, 2)])
def test_flagship_loss_and_gradients_equal_the_parents(
        dp, mp, n_micro, interpreted_kernels, monkeypatch):
    hvd.init()
    par = tfm.ParallelConfig(dp=dp, pp=1, mp=mp, n_microbatches=n_micro)

    def run():
        loss, args = loss_and_args(par)
        text = str(jax.make_jaxpr(loss)(*args))
        assert "hvd_flash_fwd" in text           # the kernels engaged
        value, grads = jax.jit(jax.value_and_grad(loss))(*args)
        return np.asarray(value), jax.tree_util.tree_map(np.asarray, grads)

    value, grads = run()
    monkeypatch.setattr(pp_lib, "pipeline_apply", parent_pipeline_apply)
    assert checkpoint_depths(par) == [0, 1]      # the parent's nesting is back
    parent_value, parent_grads = run()
    assert np.isfinite(value)
    assert value.tobytes() == parent_value.tobytes()
    for (path, g), p in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(parent_grads)):
        assert np.abs(g).max() > 0, path
        np.testing.assert_allclose(g, p, rtol=1e-6, atol=1e-6,
                                   err_msg=str(path))


# -- off the chip: the flagship cells compiled for a described v5e -------------

@pytest.mark.parametrize("workload, max_gib", [
    ("flagship-s8192-train-1chip", 12.5),
    ("flagship-s8192-train-dp2mp2", 13.8),
])
def test_flagship_cell_compiles_with_one_forward_and_no_permute(
        compiled_cells, workload, max_gib):
    hlo, mem = compiled_cells(workload)
    names = KERNEL.findall(hlo)
    count = {k: sum(n.split(".")[0] == k for n in names) for k in KERNELS}
    peak = mem.peak_memory_in_bytes / GIB
    print(f"{workload}: kernel instructions {count}, arguments "
          f"{mem.argument_size_in_bytes / GIB:.2f} temporaries "
          f"{mem.temp_size_in_bytes / GIB:.2f} peak {peak:.2f} GiB per device")
    # The forward alone: the recompute of the whole stage went with the
    # stage checkpoint (PR 25), the layer's recompute of the kernel with the
    # layer checkpoint keeping its output and lse (PR 32; the twelve saved
    # (B, S, H·D) stacks are the four-chip cell's 12.47 -> 13.45 GiB).
    assert count == {"hvd_flash_fwd": 1, "hvd_flash_bwd_dq": 1,
                     "hvd_flash_bwd_dkv": 1}, names
    assert len(names) == 3, names
    # No permute of the activation to itself (PR 25).  Since PR 37 the
    # four-chip step has permutes of another kind, the rings over mp of
    # parallel/tensor_parallel.py, each under its scope.
    permutes = [line for line in hlo.splitlines()
                if re.search(r" collective-permute(-start)?\(", line)]
    assert all("hvd_tp_ring_" in line for line in permutes), permutes
    assert bool(permutes) == (workload == "flagship-s8192-train-dp2mp2")
    assert peak <= max_gib
