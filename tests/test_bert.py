"""BERT encoder family (models/bert.py): sharded (dp×mp) loss vs the
unsharded oracle, training-step smoke, and MLM batch semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from _flash_kernels import ONCE, kernel_calls
from horovod_tpu.models import bert
from horovod_tpu.parallel import ring_attention as ra
from horovod_tpu.parallel.mesh import create_mesh


CFG = bert.BertConfig(vocab_size=211, d_model=32, n_heads=4, d_ff=64,
                      n_layers=2, seq_len=16, dtype=jnp.float32, remat=False)


@pytest.fixture()
def mesh():
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    return create_mesh({"dp": 2, "mp": 2}, devices=devs[:4])


def test_synthetic_batch_masks():
    inputs, labels = bert.synthetic_batch(jax.random.PRNGKey(0), CFG, 4,
                                          mask_rate=0.5)
    masked = labels != bert.IGNORE_INDEX
    assert bool(masked.any()) and not bool(masked.all())
    # Masked inputs are zeroed; unmasked labels ignored.
    assert bool((inputs[masked] == 0).all())
    assert bool((labels[~masked] == bert.IGNORE_INDEX).all())


def test_sharded_loss_matches_oracle(mesh):
    params = bert.init_params(jax.random.PRNGKey(0), CFG)
    inputs, labels = bert.synthetic_batch(jax.random.PRNGKey(1), CFG, 8)
    oracle = bert.serial_forward_loss(CFG, params, inputs, labels)
    loss = bert.make_loss_fn(CFG, mesh)(params, inputs, labels)
    np.testing.assert_allclose(float(loss), float(oracle), rtol=1e-4)


def test_train_step_reduces_loss(mesh):
    import optax
    params = bert.init_params(jax.random.PRNGKey(0), CFG)
    step, shard_params = bert.make_train_step(CFG, mesh, optax.adam(1e-2))
    params = shard_params(params)
    opt_state = optax.adam(1e-2).init(params)
    inputs, labels = bert.synthetic_batch(jax.random.PRNGKey(1), CFG, 8)
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, inputs, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_loss_grad_nonzero():
    params = bert.init_params(jax.random.PRNGKey(0), CFG)
    inputs, labels = bert.synthetic_batch(jax.random.PRNGKey(1), CFG, 2)
    g = jax.grad(lambda p: bert.serial_forward_loss(CFG, p, inputs,
                                                    labels))(params)
    norms = [float(jnp.abs(x).max()) for x in jax.tree_util.tree_leaves(g)]
    assert max(norms) > 0


def test_synthetic_mlm_batch_positions():
    inputs, positions, labels = bert.synthetic_mlm_batch(
        jax.random.PRNGKey(0), CFG, 4)
    n_pred = bert.max_predictions(CFG)
    assert positions.shape == (4, n_pred) and labels.shape == (4, n_pred)
    for row_pos, row_in, row_lb in zip(np.asarray(positions),
                                       np.asarray(inputs),
                                       np.asarray(labels)):
        assert len(set(row_pos.tolist())) == n_pred  # distinct positions
        assert (row_in[row_pos] == 0).all()          # masked in inputs
        assert (row_lb > 0).all()                    # original ids kept


def test_gathered_loss_matches_dense():
    """The gathered (max_predictions_per_seq) MLM head computes the same
    cross entropy as the dense head over an identical mask pattern."""
    params = bert.init_params(jax.random.PRNGKey(0), CFG)
    inputs, positions, labels = bert.synthetic_mlm_batch(
        jax.random.PRNGKey(1), CFG, 4)
    dense_labels = jnp.full((4, CFG.seq_len), bert.IGNORE_INDEX, jnp.int32)
    dense_labels = jnp.put_along_axis(dense_labels, positions, labels,
                                      axis=1, inplace=False)
    l_dense = bert.serial_forward_loss(CFG, params, inputs, dense_labels)
    l_gath = bert.serial_forward_loss(CFG, params, inputs, labels,
                                      positions=positions)
    np.testing.assert_allclose(float(l_gath), float(l_dense), rtol=1e-4)


def test_gathered_sharded_matches_oracle(mesh):
    params = bert.init_params(jax.random.PRNGKey(0), CFG)
    inputs, positions, labels = bert.synthetic_mlm_batch(
        jax.random.PRNGKey(1), CFG, 8)
    oracle = bert.serial_forward_loss(CFG, params, inputs, labels,
                                      positions=positions)
    loss = bert.make_loss_fn(CFG, mesh, gathered=True)(
        params, inputs, positions, labels)
    np.testing.assert_allclose(float(loss), float(oracle), rtol=1e-4)


def test_gathered_train_step_reduces_loss(mesh):
    import optax
    params = bert.init_params(jax.random.PRNGKey(0), CFG)
    step, shard_params = bert.make_train_step(CFG, mesh, optax.adam(1e-2),
                                              gathered=True)
    params = shard_params(params)
    opt_state = optax.adam(1e-2).init(params)
    inputs, positions, labels = bert.synthetic_mlm_batch(
        jax.random.PRNGKey(1), CFG, 8)
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, inputs,
                                       positions, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("remat", [True, "dots"])
def test_remat_modes_same_loss_and_grad(remat):
    """Rematerialization choices change memory/compute scheduling, never
    values: loss and gradients agree across none/full/dots policies."""
    cfg = CFG._replace(remat=remat)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    inputs, positions, labels = bert.synthetic_mlm_batch(
        jax.random.PRNGKey(1), cfg, 4)

    def loss_fn(p):
        return bert.serial_forward_loss(cfg, p, inputs, labels,
                                        positions=positions)

    loss, g = jax.value_and_grad(loss_fn)(params)
    base_cfg = CFG._replace(remat=False)
    base_loss, base_g = jax.value_and_grad(
        lambda p: bert.serial_forward_loss(base_cfg, p, inputs, labels,
                                           positions=positions))(params)
    np.testing.assert_allclose(float(loss), float(base_loss), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(base_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


# -- what the layer checkpoint keeps (ROADMAP Sr) ----------------------------

def _kept_loss_and_grads(remat):
    """(loss-and-gradients of ``_encode``'s scan under the gathered head at
    a length the interpreted kernels take, its parameters)."""
    cfg = CFG._replace(seq_len=128, n_layers=3, remat=remat)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    inputs, positions, labels = bert.synthetic_mlm_batch(
        jax.random.PRNGKey(1), cfg, 2)
    return jax.jit(jax.value_and_grad(
        lambda p: bert.serial_forward_loss(cfg, p, inputs, labels,
                                           positions=positions))), params


def test_a_checkpointed_encoder_layer_calls_the_forward_kernel_once(
        interpreted_kernels, monkeypatch):
    """``remat=True`` keeps the flash forward's output and lse: one call of
    each kernel in the gradient's program (the scan's body, printed once),
    where the bare checkpoint's holds the forward and its recompute — as
    ``remat="dots"``, which this leaves alone, still does."""
    fn, params = _kept_loss_and_grads(True)
    assert kernel_calls(jax.make_jaxpr(fn)(params)) == ONCE
    twice = {**ONCE, "hvd_flash_fwd": 2}
    fn, params = _kept_loss_and_grads("dots")
    assert kernel_calls(jax.make_jaxpr(fn)(params)) == twice
    monkeypatch.setattr(ra, "checkpoint_keeping_attention", jax.checkpoint)
    fn, params = _kept_loss_and_grads(True)
    assert kernel_calls(jax.make_jaxpr(fn)(params)) == twice


def test_keeping_the_forward_leaves_berts_gradients_alone(
        interpreted_kernels, monkeypatch):
    """Loss and gradients are the bare checkpoint's and ``remat=False``'s
    to the last bits (the kernels' path alone is held to the bit in
    test_flash_attention.py; whole programs XLA fuses each in its own
    way)."""
    fn, params = _kept_loss_and_grads(True)
    kept = jax.tree_util.tree_leaves(fn(params))
    plain = jax.tree_util.tree_leaves(_kept_loss_and_grads(False)[0](params))
    monkeypatch.setattr(ra, "checkpoint_keeping_attention", jax.checkpoint)
    bare = jax.tree_util.tree_leaves(_kept_loss_and_grads(True)[0](params))
    for a, b, c in zip(kept, bare, plain):
        for other in (b, c):
            np.testing.assert_allclose(np.asarray(a), np.asarray(other),
                                       rtol=1e-4, atol=1e-6)
