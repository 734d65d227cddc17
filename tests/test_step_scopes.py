"""The names the compiled training step carries for a device profile: the
five ``hvd_*`` scopes of ``utils/profiler.STEP_SCOPES`` around the blocks of
both models' ``make_train_step`` and the three ``name=``s of the flash
kernels.  Names are HLO metadata: they must be in the lowered text and must
not move a single bit of the result."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from _flash_kernels import KERNELS
from horovod_tpu.models import bert, transformer as tfm
from horovod_tpu.parallel.mesh import create_mesh
from horovod_tpu.utils import profiler

TFM_CFG = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, d_ff=64, n_layers=2, seq_len=128,
    dtype=jnp.float32, remat=True)
TFM_PAR = tfm.ParallelConfig(dp=2, pp=1, mp=2, n_microbatches=1)
BERT_CFG = bert.BertConfig(
    vocab_size=64, d_model=32, n_heads=2, d_ff=64, n_layers=2, seq_len=128,
    dtype=jnp.float32, remat=True)


def transformer_step():
    mesh = create_mesh({"dp": 2, "pp": 1, "mp": 2})
    tx = optax.adamw(1e-3)
    step, shard = tfm.make_train_step(TFM_CFG, TFM_PAR, mesh, tx)
    params = shard(tfm.init_params(jax.random.PRNGKey(0), TFM_CFG, TFM_PAR))
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), TFM_CFG, 4)
    return step, (params, tx.init(params), *batch)


def bert_step():
    mesh = create_mesh({"dp": 2, "mp": 2})
    tx = optax.adamw(1e-3)
    step, shard = bert.make_train_step(BERT_CFG, mesh, tx, gathered=True)
    params = shard(bert.init_params(jax.random.PRNGKey(0), BERT_CFG))
    batch = bert.synthetic_mlm_batch(jax.random.PRNGKey(1), BERT_CFG, 4)
    return step, (params, tx.init(params), *batch)


STEPS = {"transformer": (transformer_step, tfm), "bert": (bert_step, bert)}


def test_scope_is_a_named_scope_with_the_hvd_prefix():
    assert profiler.STEP_SCOPES == ("embed", "attn", "mlp", "head",
                                    "optimizer")

    def f(x):
        with profiler.scope("head"):
            return x * 2.0

    text = jax.jit(f).lower(1.0).as_text(debug_info=True)
    assert "hvd_head" in text


def test_scope_has_no_knob(monkeypatch):
    """Neither the scopes of the compiled step nor the host ranges have a
    knob: whatever the environment holds (here the reference's
    ``HOROVOD_DISABLE_NVTX_RANGES``), the name is in the lowered step."""
    monkeypatch.setenv("HOROVOD_DISABLE_NVTX_RANGES", "1")

    def f(x):
        with profiler.scope("optimizer"):
            return x + 1.0

    assert "hvd_optimizer" in jax.jit(f).lower(1.0).as_text(debug_info=True)


@pytest.mark.parametrize("model", sorted(STEPS))
def test_lowered_step_holds_the_scopes_and_kernel_names(
        model, interpreted_kernels):
    hvd.init()
    make, _module = STEPS[model]
    step, args = make()
    text = step.lower(*args).as_text(debug_info=True)
    for name in profiler.STEP_SCOPES:
        assert f"hvd_{name}" in text, name
    for kernel in KERNELS:
        assert kernel in text, kernel


@pytest.mark.parametrize("model", sorted(STEPS))
def test_scopes_do_not_move_a_bit_of_the_result(
        model, interpreted_kernels, monkeypatch):
    hvd.init()
    make, module = STEPS[model]

    def two_steps():
        step, args = make()
        params, state, *batch = args
        params, state, first = step(params, state, *batch)
        params, state, second = step(params, state, *batch)
        return (np.asarray(first), np.asarray(second),
                [np.asarray(x) for x in jax.tree_util.tree_leaves(params)])

    with_scopes = two_steps()
    monkeypatch.setattr(module, "scope",
                        lambda name: contextlib.nullcontext())
    step, args = make()
    assert "hvd_" not in step.lower(*args).as_text(debug_info=True).replace(
        "hvd_flash_", "")
    without = two_steps()
    assert with_scopes[0].tobytes() == without[0].tobytes()
    assert with_scopes[1].tobytes() == without[1].tobytes()
    for a, b in zip(with_scopes[2], without[2]):
        assert a.tobytes() == b.tobytes()
