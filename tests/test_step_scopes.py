"""The names the compiled training step carries for a device profile: the
five ``hvd_*`` scopes of ``utils/profiler.STEP_SCOPES`` around the blocks of
both models' ``make_train_step``, the three ``name=``s of the flash
kernels, the scope around the scan over the layers and the two around what
XLA does to make the kernels' operands.  Names are HLO metadata: they must
be in the lowered text and must not move a single bit of the result."""

import contextlib
import sys

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


# A patterned model of two periods, 4 query heads on 2 key / value heads.
GQA_CFG = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=64, n_layers=4, seq_len=128,
    dtype=jnp.float32, remat=True, layer_pattern="*D", dense_ff=48,
    learned_positions=False, n_kv_heads=2, rope_theta=1e4, tied_head=False)


def gqa_step():
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])
    par, tx = tfm.ParallelConfig(), optax.adamw(1e-3)
    step, shard = tfm.make_train_step(GQA_CFG, par, mesh, tx)
    params = shard(tfm.init_params(jax.random.PRNGKey(0), GQA_CFG, par))
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), GQA_CFG, 2)
    return step, (params, tx.init(params), *batch)


STEPS = {"transformer": transformer_step, "bert": bert_step}
# The scan over the layers, ``delta`` in front of the backward kernel, and
# K / V repeated to the query heads where there are fewer of them.
PART_NAMES = {"transformer": ("hvd_layers", "hvd_attn_delta"),
              "bert": ("hvd_layers", "hvd_attn_delta"),
              "gqa": ("hvd_layers", "hvd_attn_delta", "hvd_attn_kv_repeat")}


def silence_scopes(monkeypatch):
    """``scope`` a no-op in every module of the package that holds it,
    whichever took it by name (the models, ``ops/flash_attention.py``,
    ``ops/sparse_index.py``, ``parallel/moe.py``, ...)."""
    real = profiler.scope
    for name, module in list(sys.modules.items()):
        if name.startswith("horovod_tpu") and (
                getattr(module, "scope", None) is real):
            monkeypatch.setattr(module, "scope",
                                lambda name: contextlib.nullcontext())


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
    step, args = STEPS[model]()
    text = step.lower(*args).as_text(debug_info=True)
    for name in profiler.STEP_SCOPES:
        assert f"hvd_{name}" in text, name
    for kernel in KERNELS:
        assert kernel in text, kernel


def test_the_operand_and_layer_scopes_are_tuples_beside_the_others():
    assert profiler.ATTN_OPERAND_SCOPES == ("attn_kv_repeat", "attn_delta")
    assert profiler.LAYERS_SCOPE == "layers"
    assert profiler.ATTN_PART_SCOPES == ("attn_rope", "attn_gate",
                                         "attn_qknorm")


@pytest.mark.parametrize("model", sorted(PART_NAMES))
def test_lowered_step_names_the_layer_scan_and_the_kernels_operands(
        model, interpreted_kernels):
    hvd.init()
    step, args = {**STEPS, "gqa": gqa_step}[model]()
    text = step.lower(*args).as_text(debug_info=True)
    for name in PART_NAMES[model]:
        assert name in text, name
    if model != "gqa":      # as many K / V heads as query heads: no repeat
        assert "hvd_attn_kv_repeat" not in text
    # ``delta``'s operations carry the name behind the block's, in the
    # backward pass (the scopes are JAX's name stack, innermost last).
    assert "hvd_attn/hvd_attn_delta/" in text or (
        "hvd_attn)/hvd_attn_delta/" in text)


def test_position_qk_names_its_qk_norm_and_rotation():
    """``_position_qk``'s QK-norm and rotary positions (OLMoE's path) carry
    the names ``_gqa_mixer`` gives its own."""
    cfg = TFM_CFG._replace(qk_norm=True, rope_theta=1e4)
    q = jnp.zeros((1, 8, cfg.n_heads, cfg.head_dim))
    lp = {"q_norm": jnp.ones(cfg.d_model), "k_norm": jnp.ones(cfg.d_model)}
    text = jax.jit(lambda q, k: tfm._position_qk(
        cfg, lp, q, k, jnp.arange(8), None)).lower(q, q).as_text(
            debug_info=True)
    assert "hvd_attn_qknorm" in text and "hvd_attn_rope" in text
    plain = jax.jit(lambda q, k: tfm._position_qk(
        TFM_CFG, lp, q, k, jnp.arange(8), None)).lower(q, q).as_text(
            debug_info=True)
    assert "hvd_" not in plain


@pytest.mark.parametrize("model", sorted(STEPS))
def test_scopes_do_not_move_a_bit_of_the_result(
        model, interpreted_kernels, monkeypatch):
    hvd.init()
    make = STEPS[model]

    def two_steps():
        step, args = make()
        params, state, *batch = args
        params, state, first = step(params, state, *batch)
        params, state, second = step(params, state, *batch)
        return (np.asarray(first), np.asarray(second),
                [np.asarray(x) for x in jax.tree_util.tree_leaves(params)])

    with_scopes = two_steps()
    silence_scopes(monkeypatch)
    step, args = make()
    assert "hvd_" not in step.lower(*args).as_text(debug_info=True).replace(
        "hvd_flash_", "")
    without = two_steps()
    assert with_scopes[0].tobytes() == without[0].tobytes()
    assert with_scopes[1].tobytes() == without[1].tobytes()
    for a, b in zip(with_scopes[2], without[2]):
        assert a.tobytes() == b.tobytes()
