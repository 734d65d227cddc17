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


# -- a block kind is one row of ``tfm.BLOCKS`` -------------------------------------------------

PATTERNED = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=64, n_layers=3, seq_len=16,
    dtype=jnp.float32, layer_pattern="*ZD", learned_positions=False,
    rope_theta=1e4, dense_ff=48, tied_head=False)
ONE = tfm.ParallelConfig()


def _toy_kind():
    """A seventh kind, written as a new one is: its mixer (a learned scale
    of the normed stream), its leaves, its FLOPs, and what it asks — here
    of ``dense_ff``, a field it borrows, there being no way to give
    ``TransformerConfig`` a field from a test."""
    def mixer(cfg, lp, x):
        return tfm._rmsnorm(x, lp["ln"], cfg.norm_eps) * lp["scale"].astype(
            x.dtype)

    return tfm.BlockKind(
        "toy", "mlp", (),
        lambda cfg, here: here and cfg.dense_ff == 7 and
        'a "Z" block and a dense_ff of 7 do not go together',
        lambda cfg, new: {"ln": new.ones(cfg.d_model),
                          "scale": new.rand(cfg.d_model)},
        mixer, lambda cfg: 2.0 * cfg.d_model)


def test_a_seventh_kind_is_its_mixer_and_one_row(monkeypatch):
    """Registered by this test alone, the kind is refused as a letter until
    its row is there; then it initialises, is specified, counts its FLOPs,
    is refused where its row says, leads, and trains beside "*" and "D"."""
    with pytest.raises(ValueError, match="letters are"):
        tfm.init_params(jax.random.PRNGKey(0), PATTERNED, ONE)
    monkeypatch.setitem(tfm.BLOCKS, "Z", _toy_kind())
    params = tfm.init_params(jax.random.PRNGKey(0), PATTERNED, ONE)
    assert tfm.pattern_counts(PATTERNED) == {"attn": 1, "dense": 1, "toy": 1}
    toy = params["layers"]["toy"]
    assert toy["ln"].shape == toy["scale"].shape == (1, 1, 1, 32)
    assert float(jnp.abs(toy["scale"]).max()) > 0
    specs = tfm.param_specs(PATTERNED, ONE)
    assert jax.tree_util.tree_structure(
        specs, is_leaf=lambda s: isinstance(s, tfm.P)
    ) == jax.tree_util.tree_structure(params)
    without = tfm.train_flops_per_seq(PATTERNED._replace(
        layer_pattern="*D", n_layers=2))
    assert tfm.train_flops_per_seq(PATTERNED) == without + 3 * 16 * 2.0 * 32
    with pytest.raises(ValueError, match="dense_ff of 7 do not go together"):
        tfm.param_specs(PATTERNED._replace(dense_ff=7), ONE)
    # It returns no router statistics, so it may lead; nothing crosses
    # between a diffusion_block's copies, so that admits it.
    leads = PATTERNED._replace(leading_pattern="Z", n_layers=4)
    assert tfm.pattern_counts(leads, leading=True) == {"toy": 1}
    assert tfm.init_params(jax.random.PRNGKey(0), leads, ONE)["layers"][
        "leading"]["toy"]["scale"].shape == (1, 1, 32)
    tfm._check_layout(PATTERNED._replace(diffusion_block=4), ONE)

    hvd.init()
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])
    opt = optax.adamw(1e-2)
    step, shard = tfm.make_train_step(leads, ONE, mesh, opt)
    p = shard(tfm.init_params(jax.random.PRNGKey(0), leads, ONE))
    before = np.asarray(p["layers"]["toy"]["scale"])
    state, batch = opt.init(p), tfm.synthetic_batch(
        jax.random.PRNGKey(1), leads, 2)
    losses = []
    for _ in range(4):
        p, state, loss = step(p, state, *batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert np.abs(np.asarray(p["layers"]["toy"]["scale"]) - before).max() > 0
    assert np.abs(np.asarray(p["layers"]["leading"]["toy"]["scale"])).max() > 0


@pytest.mark.parametrize("letter", list(tfm.BLOCK_KINDS))
def test_a_kinds_own_fields_are_a_patterned_models(letter):
    """The fields a model without a ``layer_pattern`` is refused for are
    the rows' own, computed: every field of the row is named in the
    refusal, and setting any one of them draws it."""
    row = tfm.BLOCKS[letter]
    assert tfm.BLOCK_KINDS[letter] == (row.key, row.scope)
    assert row.fields and set(row.fields) <= set(tfm.TransformerConfig._fields)
    off_default = {bool: True, int: 3, float: 0.5, str: "x"}
    for field in row.fields:
        default = tfm.TransformerConfig._field_defaults[field]
        value = ((1, 2, 3) if default is None or isinstance(default, tuple)
                 else off_default[type(default)])
        with pytest.raises(ValueError, match="are a patterned model's: set "
                                             "layer_pattern") as refusal:
            tfm._check_layout(CFG._replace(**{field: value}), ONE)
        assert all(f in str(refusal.value) for f in row.fields)
    tfm._check_layout(CFG._replace(n_kv_heads=CFG.n_heads), ONE)
