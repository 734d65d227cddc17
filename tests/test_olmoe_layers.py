"""What ``TransformerConfig``'s architecture fields add to the flagship's
training path (OLMoE-1B-7B: rotary positions, QK-norm, dropless gated
experts, an untied head, the router's auxiliary losses), piece by piece
against formulas written out here, and the layouts against each other.  The
whole model against the benchmark's plain reference is
``tests/benchmark_tests/test_benchmark_olmoe.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.mesh import create_mesh
from horovod_tpu.utils import profiler

CFG = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=4, d_ff=32, n_layers=2, seq_len=64,
    n_experts=16, top_k=4, dtype=jnp.float32, remat=True,
    rope_theta=10000.0, qk_norm=True, norm_eps=1e-5, gated_experts=True,
    dropless=True, tied_head=False, aux_loss_coef=0.01, z_loss_coef=0.001)
BATCH = 4
HIGHEST = jax.lax.Precision.HIGHEST


def loss_and_grads(cfg, shape):
    hvd.init()
    par = tfm.ParallelConfig(*shape)
    mesh = create_mesh(dict(zip(("dp", "pp", "mp"), shape)),
                       devices=jax.devices()[:int(np.prod(shape))])
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, par)
    params["layers"]["wqkv"] = params["layers"]["wqkv"] * 8.0
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), cfg, BATCH)
    return jax.jit(jax.value_and_grad(tfm.make_loss_fn(cfg, par, mesh)))(
        params, *batch)


# -- rotary positions ----------------------------------------------------------------

def test_rope_is_the_rotation_of_each_pair_by_its_angle():
    """Pair i of a head is (t[i], t[i + hd/2]); at position p it turns by
    p * theta^(-2i/hd).  Checked as complex multiplication."""
    hd, s = 16, 12
    t = jax.random.normal(jax.random.PRNGKey(0), (2, s, 3, hd))
    pos = jnp.arange(5, 5 + s)
    got = np.asarray(tfm._rope(t, pos, 10000.0))
    z = np.asarray(t[..., :hd // 2]) + 1j * np.asarray(t[..., hd // 2:])
    angle = (np.asarray(pos)[:, None]
             * 10000.0 ** (-2.0 * np.arange(hd // 2) / hd))
    want = z * np.exp(1j * angle)[None, :, None, :]
    np.testing.assert_allclose(got[..., :hd // 2], want.real, atol=1e-5)
    np.testing.assert_allclose(got[..., hd // 2:], want.imag, atol=1e-5)


def test_rope_scores_depend_on_the_distance_alone():
    hd = 16
    q, k = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 1, 1, hd))

    def score(pq, pk):
        return float(jnp.sum(tfm._rope(q, jnp.array([pq]), 1e4)
                             * tfm._rope(k, jnp.array([pk]), 1e4)))

    assert score(7, 3) == pytest.approx(score(104, 100), abs=1e-4)
    assert score(7, 3) != pytest.approx(score(7, 4), abs=1e-3)


# -- QK-norm -----------------------------------------------------------------------

def test_qk_norm_is_rmsnorm_over_every_head_at_once():
    t = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 4, 8)) * 3.0
    scale = jax.random.normal(jax.random.PRNGKey(3), (32,))
    got = tfm._qk_norm(t, scale, 1e-5, None)
    flat = np.asarray(t).reshape(2, 5, 32)
    want = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + 1e-5) * scale
    np.testing.assert_allclose(np.asarray(got).reshape(2, 5, 32), want,
                               rtol=1e-5, atol=1e-6)
    # Not a norm of each head by itself.
    per_head = np.asarray(t) / np.sqrt(
        (np.asarray(t) ** 2).mean(-1, keepdims=True) + 1e-5)
    assert np.abs(np.asarray(got) - per_head
                  * np.asarray(scale).reshape(4, 8)).max() > 0.1


# -- the dropless layer ------------------------------------------------------------------

def moe_params(key, d=32, f=16, e=16):
    k = jax.random.split(key, 4)
    return moe.GatedMoEParams(
        gate=jax.random.normal(k[0], (d, e)) * 0.5,
        w_gate=jax.random.normal(k[1], (e, d, f)) * 0.2,
        w_up=jax.random.normal(k[2], (e, d, f)) * 0.2,
        w_down=jax.random.normal(k[3], (e, f, d)) * 0.2)


def dense_mask_moe(p, x, top_k):
    """Every expert for every token, weighted by the router's probability
    where the expert is among the token's top_k and by 0 elsewhere."""
    probs = jax.nn.softmax(jnp.dot(x, p.gate, precision=HIGHEST), axis=-1)
    kth = jax.lax.top_k(probs, top_k)[0][:, -1:]
    w = jnp.where(probs >= kth, probs, 0.0)
    h = (jax.nn.silu(jnp.einsum("td,edf->tef", x, p.w_gate,
                                precision=HIGHEST))
         * jnp.einsum("td,edf->tef", x, p.w_up, precision=HIGHEST))
    y = jnp.einsum("tef,efd->ted", h, p.w_down, precision=HIGHEST)
    return jnp.einsum("te,ted->td", w, y, precision=HIGHEST)


@pytest.mark.parametrize("skew", [0.0, 4.0])
def test_dropless_layer_is_the_dense_mask_formula(skew):
    """Output, statistics and every gradient; with ``skew`` a router that
    sends every token to experts 0..3 (a constant feature it weighs
    heavily): four groups of all the rows, twelve of none."""
    p = moe_params(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (96, 32))
    x = x.at[:, 0].set(1.0)
    p = p._replace(gate=p.gate.at[0, :4].add(skew * 5))
    with jax.default_matmul_precision("highest"):
        out, stats = jax.jit(lambda p, x: moe.dropless_moe(p, x, 4))(p, x)
        want = dense_mask_moe(p, x, 4)
        np.testing.assert_allclose(out, want, atol=2e-6)
        assert float(stats.counts.sum()) == 96 * 4          # nothing dropped
        if skew:
            assert stats.counts.tolist() == [96.0] * 4 + [0.0] * 12
        np.testing.assert_allclose(float(stats.prob_sum.sum()), 96.0,
                                   rtol=1e-5)
        got = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(
            moe.dropless_moe(p, x, 4)[0])), argnums=(0, 1)))(p, x)
        ref = jax.grad(lambda p, x: jnp.sum(jnp.sin(
            dense_mask_moe(p, x, 4))), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)


def test_dropless_layer_without_a_gate_projection():
    p = moe_params(jax.random.PRNGKey(6))._replace(w_gate=None)
    x = jax.random.normal(jax.random.PRNGKey(7), (40, 32))
    with jax.default_matmul_precision("highest"):
        out, _ = moe.dropless_moe(p, x, 2, activation=jax.nn.gelu)
        probs = jax.nn.softmax(x @ p.gate, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, 2)
        want = sum(
            top_p[:, j:j + 1] * jnp.einsum(
                "tf,tfd->td",
                jax.nn.gelu(jnp.einsum("td,tdf->tf", x, p.w_up[top_i[:, j]])),
                p.w_down[top_i[:, j]]) for j in range(2))
    np.testing.assert_allclose(out, want, atol=2e-6)


def test_router_losses_are_hf_load_balancing_and_the_z_loss():
    logits = jax.random.normal(jax.random.PRNGKey(8), (50, 8)) * 2.0
    probs = jax.nn.softmax(logits, -1)
    chosen = jax.nn.one_hot(jax.lax.top_k(probs, 2)[1], 8)      # (T, k, E)
    stats = moe.RouterStats(
        counts=chosen.sum((0, 1)), prob_sum=probs.sum(0),
        z_sum=jnp.sum(jax.nn.logsumexp(logits, -1) ** 2))
    balance, z = moe.router_losses(stats, 50)
    # HF: sum over (k, E) of mean_t(mask) * mean_t(prob), times E.
    hf = 8 * jnp.sum(chosen.mean(0) * probs.mean(0)[None, :])
    assert float(balance) == pytest.approx(float(hf), rel=1e-6)
    assert float(z) == pytest.approx(
        float(jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)), rel=1e-6)
    uniform = moe.RouterStats(counts=jnp.full((8,), 50 * 2 / 8),
                              prob_sum=jnp.full((8,), 50 / 8), z_sum=0.0)
    assert float(moe.router_losses(uniform, 50)[0]) == pytest.approx(2.0)


# -- the model ---------------------------------------------------------------------------

def test_parameter_tree_of_the_architecture_fields():
    par = tfm.ParallelConfig()
    shapes = jax.eval_shape(
        lambda k: tfm.init_params(k, CFG, par), jax.random.PRNGKey(0))
    assert "pos" not in shapes and shapes["lm_head"].shape == (128, 64)
    assert set(shapes["layers"]) == {
        "ln1", "ln2", "wqkv", "wo", "q_norm", "k_norm", "gate", "w_gate",
        "w_up", "w_down"}
    assert shapes["layers"]["w_down"].shape == (1, 2, 16, 32, 64)
    specs = tfm.param_specs(CFG, par)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, shapes)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda s: 0, specs,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))
    # The defaults are the flagship's tree, as it was.
    flagship = jax.eval_shape(
        lambda k: tfm.init_params(k, tfm.TransformerConfig(), par),
        jax.random.PRNGKey(0))
    assert set(flagship) == {"embed", "pos", "final_norm", "layers"}
    assert set(flagship["layers"]) == {"ln1", "ln2", "wqkv", "wo", "w1", "w2"}
    with pytest.raises(ValueError, match="gated_experts"):
        tfm.init_params(jax.random.PRNGKey(0),
                        CFG._replace(dropless=False), par)


@pytest.mark.parametrize("shape, attn_mode", [
    ((2, 1, 2), "megatron"), ((1, 1, 2), "ring"), ((1, 1, 2), "ulysses")])
def test_every_layout_gives_one_devices_loss_and_gradients(shape, attn_mode):
    """fp32, so layouts differ by summation order only.  Megatron on mp 2
    shards the heads (the QK-norm's mean square is a psum); ring and Ulysses
    shard the sequence (each member rotates its own chunk's positions); dp 2
    shards the batch (the router's statistics are summed before the
    load-balancing loss multiplies its two means)."""
    loss_1, grads_1 = loss_and_grads(CFG, (1, 1, 1))
    loss_n, grads_n = loss_and_grads(CFG._replace(attn_mode=attn_mode), shape)
    assert float(loss_n) == pytest.approx(float(loss_1), abs=2e-5)
    flat = dict(jax.tree_util.tree_leaves_with_path(grads_1))
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads_n):
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat[path]), rtol=2e-3, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))


def test_auxiliary_losses_are_in_the_loss_and_reach_the_router():
    with_aux, g_aux = loss_and_grads(CFG, (1, 1, 1))
    without, g_none = loss_and_grads(
        CFG._replace(aux_loss_coef=0.0, z_loss_coef=0.0), (1, 1, 1))
    # top_k = 4 of 16 near-uniform experts: balance ~ 4, so ~0.04 + z.
    assert 0.03 < float(with_aux) - float(without) < 0.2
    assert not np.allclose(g_aux["layers"]["gate"], g_none["layers"]["gate"])
    np.testing.assert_allclose(g_aux["lm_head"], g_none["lm_head"],
                               atol=1e-7)


def test_routing_fn_counts_every_assignment_and_trains():
    hvd.init()
    par = tfm.ParallelConfig(2, 1, 2)
    mesh = create_mesh({"dp": 2, "pp": 1, "mp": 2})
    tx = optax.adamw(1e-2)
    step, shard = tfm.make_train_step(CFG, par, mesh, tx)
    params = shard(tfm.init_params(jax.random.PRNGKey(0), CFG, par))
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), CFG, BATCH)
    r = tfm.make_routing_fn(CFG, par, mesh)(params, *batch)
    assert r["assignments"].shape == (2, 16)
    assert np.asarray(r["assignments"]).sum(-1).tolist() == [
        BATCH * 64 * 4] * 2
    assert float(r["dropped"]) == 0.0
    assert all(1.0 <= x < 2.0 for x in np.asarray(r["load"]))
    assert all(3.9 < x < 4.3 for x in np.asarray(r["load_balancing_loss"]))
    state = tx.init(params)
    losses = []
    for _ in range(4):
        params, state, loss = step(params, state, *batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()


def test_a_dropless_moe_refuses_pipeline_stages():
    hvd.init()
    par = tfm.ParallelConfig(1, 2, 1)
    mesh = create_mesh({"dp": 1, "pp": 2, "mp": 1},
                       devices=jax.devices()[:2])
    params = tfm.init_params(jax.random.PRNGKey(0), CFG, par)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), CFG, BATCH)
    with pytest.raises(NotImplementedError, match="pp must be 1"):
        jax.jit(tfm.make_loss_fn(CFG, par, mesh))(params, *batch)


def test_step_names_the_parts_of_the_moe_block():
    assert profiler.MOE_SCOPES == ("moe_route", "moe_dispatch", "moe_experts")
    hvd.init()
    par = tfm.ParallelConfig()
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1},
                       devices=jax.devices()[:1])
    params = tfm.init_params(jax.random.PRNGKey(0), CFG, par)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), CFG, BATCH)
    text = jax.jit(jax.grad(tfm.make_loss_fn(CFG, par, mesh))).lower(
        params, *batch).as_text(debug_info=True)
    for name in profiler.MOE_SCOPES:
        assert f"hvd_mlp/hvd_{name}" in text, name


@pytest.mark.parametrize("field", [
    {"rope_theta": 10000.0}, {"qk_norm": True},
    {"gated_experts": True}, {"tied_head": False}],
    ids=lambda f: next(iter(f)))
def test_serving_forward_refuses_what_it_does_not_compute(field):
    """Each of the four fields ``_check_servable`` names refuses alone,
    before the forward touches its arguments."""
    servable = tfm.TransformerConfig()
    tfm._check_servable(servable)
    with pytest.raises(NotImplementedError, match="learned positions"):
        tfm.chunk_forward(servable._replace(**field), {},
                          jnp.zeros((1, 8), jnp.int32),
                          jnp.zeros((1,), jnp.int32), {}, None)
