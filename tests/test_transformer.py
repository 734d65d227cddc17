"""Flagship transformer: the fully-sharded (dp×pp×mp) training step must
match the unsharded serial oracle in loss and gradients; MoE and ring modes
must run and train."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from _flash_kernels import ONCE, kernel_calls
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import ring_attention as ra
from horovod_tpu.parallel.mesh import create_mesh

CFG = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=64, n_layers=4, seq_len=32,
    dtype=jnp.float32, remat=False)
PAR = tfm.ParallelConfig(dp=2, pp=2, mp=2, n_microbatches=2)
BATCH = 4


def _setup(cfg=CFG, par=PAR):
    hvd.init()
    mesh = create_mesh({"dp": par.dp, "pp": par.pp, "mp": par.mp})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, par)
    tokens, labels = tfm.synthetic_batch(jax.random.PRNGKey(1), cfg, BATCH)
    return mesh, params, tokens, labels


def test_sharded_loss_matches_serial():
    mesh, params, tokens, labels = _setup()
    loss_of = tfm.make_loss_fn(CFG, PAR, mesh)
    loss = jax.jit(loss_of)(params, tokens, labels)
    expected = tfm.serial_forward_loss(CFG, params, tokens, labels)
    np.testing.assert_allclose(float(loss), float(expected), rtol=1e-5)


def test_sharded_grads_match_serial():
    mesh, params, tokens, labels = _setup()
    loss_of = tfm.make_loss_fn(CFG, PAR, mesh)
    g_sharded = jax.jit(jax.grad(loss_of))(params, tokens, labels)
    g_serial = jax.grad(
        lambda p: tfm.serial_forward_loss(CFG, p, tokens, labels))(params)
    flat_s, _ = jax.tree_util.tree_flatten_with_path(g_sharded)
    flat_r = dict(jax.tree_util.tree_flatten_with_path(g_serial)[0])
    checked = 0
    for path, leaf in flat_s:
        ref = flat_r[path]
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(ref), rtol=2e-3, atol=1e-5,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}")
        checked += 1
    assert checked >= 8


def test_ring_mode_matches_serial():
    cfg = CFG._replace(attn_mode="ring")
    mesh, params, tokens, labels = _setup(cfg)
    loss_of = tfm.make_loss_fn(cfg, PAR, mesh)
    loss = jax.jit(loss_of)(params, tokens, labels)
    expected = tfm.serial_forward_loss(CFG, params, tokens, labels)
    np.testing.assert_allclose(float(loss), float(expected), rtol=1e-4)


def test_train_step_descends_loss():
    mesh, params, tokens, labels = _setup()
    tx = optax.adam(1e-2)
    step, shard_params = tfm.make_train_step(CFG, PAR, mesh, tx)
    params = shard_params(params)
    opt_state = tx.init(params)
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_moe_mode_trains():
    cfg = CFG._replace(n_experts=4, capacity_factor=2.0)
    mesh, params, tokens, labels = _setup(cfg)
    tx = optax.adam(1e-2)
    step, shard_params = tfm.make_train_step(cfg, PAR, mesh, tx)
    params = shard_params(params)
    opt_state = tx.init(params)
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_moe_expert_grads_sharded_over_dp():
    cfg = CFG._replace(n_experts=4, capacity_factor=2.0)
    mesh, params, tokens, labels = _setup(cfg)
    loss_of = tfm.make_loss_fn(cfg, PAR, mesh)
    g = jax.jit(jax.grad(loss_of))(params, tokens, labels)
    # Expert weights exist and receive gradient signal somewhere.
    assert float(jnp.abs(g["layers"]["w_in"]).sum()) > 0.0


def test_bf16_compiles_and_runs():
    cfg = CFG._replace(dtype=jnp.bfloat16, remat=True)
    mesh, params, tokens, labels = _setup(cfg)
    tx = optax.sgd(1e-2)
    step, shard_params = tfm.make_train_step(cfg, PAR, mesh, tx)
    params = shard_params(params)
    opt_state = tx.init(params)
    params, opt_state, loss = step(params, opt_state, tokens, labels)
    assert np.isfinite(float(loss))


def test_ulysses_mode_matches_serial():
    cfg = CFG._replace(attn_mode="ulysses")
    mesh, params, tokens, labels = _setup(cfg)
    loss_of = tfm.make_loss_fn(cfg, PAR, mesh)
    loss = jax.jit(loss_of)(params, tokens, labels)
    expected = tfm.serial_forward_loss(CFG, params, tokens, labels)
    np.testing.assert_allclose(float(loss), float(expected), rtol=1e-4)


# -- what the layer checkpoint keeps (ROADMAP Sr) ----------------------------

# The flagship's stage function (a scan of checkpointed layers) and a layer
# pattern with a "*" block (a scan of periods, each block its own
# checkpoint), at sizes the interpreted kernels take in seconds.
KEPT = {
    "flagship": CFG._replace(n_layers=3, seq_len=128, remat=True),
    "pattern": tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, d_ff=24, n_layers=6,
        seq_len=128, n_experts=4, top_k=2, dtype=jnp.float32, remat=True,
        dropless=True, tied_head=False, layer_pattern="EM*",
        learned_positions=False, n_kv_heads=1, attn_head_dim=8, ssm_heads=2,
        ssm_head_dim=4, ssm_groups=1, ssm_state=8, ssm_chunk=16,
        router_scoring="sigmoid", router_renormalise=True, moe_latent=16,
        shared_expert_ff=40, expert_activation="relu2"),
}


def _loss_and_grads(cfg):
    """(the jitted loss-and-gradients function on one device, its inputs)."""
    hvd.init()
    par = tfm.ParallelConfig()
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, par)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
    return (jax.jit(jax.value_and_grad(tfm.make_loss_fn(cfg, par, mesh))),
            (params, *batch))


@pytest.mark.parametrize("model", sorted(KEPT))
def test_a_checkpointed_layer_calls_the_forward_kernel_once(
        model, interpreted_kernels, monkeypatch):
    """``remat=True`` is one checkpoint a layer that keeps the flash
    forward's output and lse: the gradient's program holds one call of each
    kernel (the scan's body, printed once), the bare checkpoint's the
    forward and its recompute."""
    fn, args = _loss_and_grads(KEPT[model])
    assert kernel_calls(jax.make_jaxpr(fn)(*args)) == ONCE
    monkeypatch.setattr(ra, "checkpoint_keeping_attention", jax.checkpoint)
    fn, args = _loss_and_grads(KEPT[model])
    assert kernel_calls(jax.make_jaxpr(fn)(*args)) == {
        **ONCE, "hvd_flash_fwd": 2}


@pytest.mark.parametrize("model", sorted(KEPT))
def test_keeping_the_forward_leaves_the_models_gradients_alone(
        model, interpreted_kernels, monkeypatch):
    """The backward reads the ``out`` and ``lse`` the forward wrote in
    place of an identical second computation of them: loss and gradients
    are the bare checkpoint's and ``remat=False``'s to the last bits, which
    is as near as two programs XLA fuses each in its own way come (the
    kernels' path alone is held to the bit in test_flash_attention.py)."""
    cfg = KEPT[model]
    fn, args = _loss_and_grads(cfg)
    kept = jax.tree_util.tree_leaves(fn(*args))
    plain_fn, _ = _loss_and_grads(cfg._replace(remat=False))
    plain = jax.tree_util.tree_leaves(plain_fn(*args))
    monkeypatch.setattr(ra, "checkpoint_keeping_attention", jax.checkpoint)
    bare_fn, _ = _loss_and_grads(cfg)
    bare = jax.tree_util.tree_leaves(bare_fn(*args))
    for a, b, c in zip(kept, bare, plain):
        for other in (b, c):
            np.testing.assert_allclose(np.asarray(a), np.asarray(other),
                                       rtol=1e-4, atol=1e-6)
