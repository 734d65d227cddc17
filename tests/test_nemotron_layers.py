"""What ``TransformerConfig``'s layer pattern adds to the training path
(Nemotron 3's hybrid, ``nemotron_h``: Mamba-2 mixers, grouped-query
attention with a head size of its own, latent-space experts behind a sigmoid
router, a share of the experts held), piece by piece against formulas
written out here, the shares of a deployment against the whole block, and
the layouts against each other.  The whole model against the benchmark's
plain reference is ``tests/benchmark_tests/test_benchmark_nemotron_h.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import ssd
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.mesh import create_mesh
from horovod_tpu.utils import profiler

# A whole layer at a small size: 8 shares of 2 Mamba heads and one group,
# 8 shares of 2 query heads (kv head r // 4), 64 shares of 2 experts.
WHOLE = tfm.TransformerConfig(
    vocab_size=128, d_model=32, n_heads=16, d_ff=24, n_layers=3, seq_len=48,
    n_experts=128, top_k=10, dtype=jnp.float32, remat=True, norm_eps=1e-5,
    dropless=True, tied_head=False, layer_pattern="EM*",
    learned_positions=False, n_kv_heads=2, attn_head_dim=8, ssm_heads=16,
    ssm_head_dim=4, ssm_groups=8, ssm_state=8, ssm_chunk=16,
    router_scoring="sigmoid", router_renormalise=True, router_scale=2.5,
    moe_latent=16, shared_expert_ff=40, expert_activation="relu2",
    expert_buffer_factor=128.0)
SHARE = WHOLE._replace(n_heads=2, n_kv_heads=1, ssm_heads=2, ssm_groups=1,
                       n_experts_held=2)
# The model the layouts and refusals are tried on: one rank's.
CFG = SHARE._replace(n_layers=6, expert_buffer_factor=4.0)
BATCH = 4


def one_block(cfg, kind, key=0):
    """One block's parameters (no stage, period or block axes) of ``kind``."""
    layers = tfm.init_params(jax.random.PRNGKey(key), cfg,
                             tfm.ParallelConfig())["layers"][kind]
    return {k: v[0, 0, 0] for k, v in layers.items()}


def stream(cfg, key=1, batch=2):
    return jax.random.normal(jax.random.PRNGKey(key),
                             (batch, cfg.seq_len, cfg.d_model))


# -- the scan --------------------------------------------------------------------

def scan_inputs(s, h=4, g=2, p=8, n=16, bsz=2):
    ks = jax.random.split(jax.random.PRNGKey(s), 5)
    return (jax.random.normal(ks[0], (bsz, s, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, h)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7)),
            jax.random.normal(ks[3], (bsz, s, g, n)),
            jax.random.normal(ks[4], (bsz, s, g, n)), jnp.ones((h,)))


@pytest.mark.parametrize("seq", [64, 50, 7])
def test_chunked_scan_is_the_recurrence_values_and_gradients(seq):
    """At a multiple of the chunk (16), at a length that is not, and at one
    shorter than a chunk: the chunked algorithm equals the position-by-
    position recurrence, and so do its gradients to all six arguments."""
    args = scan_inputs(seq)
    with jax.default_matmul_precision("highest"):
        got = ssd.ssd_scan(*args, chunk=16)
        want = ssd.ssd_recurrence(*args)
        np.testing.assert_allclose(got, want, atol=2e-5)
        grads = [jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                          argnums=tuple(range(6)))(*args)
                 for f in (lambda *a: ssd.ssd_scan(*a, chunk=16),
                           ssd.ssd_recurrence)]
    for g, w in zip(*grads):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()))


def test_scan_in_bf16_keeps_decays_and_state_in_fp32():
    """bf16 operands, fp32 log-decays and carried state: close to the fp32
    recurrence over 8 chunks, where a bf16 cumulative sum would not be."""
    args = scan_inputs(128)
    want = ssd.ssd_recurrence(*args)
    x, dt, a, b, c, d = args
    got = ssd.ssd_scan(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
                       c.astype(jnp.bfloat16), d, chunk=16)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want).max() / jnp.abs(want).max()
    assert float(err) < 0.02


def test_conv_is_causal_and_is_its_formula():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (5, 4))
    b = jax.random.normal(jax.random.PRNGKey(2), (5,))
    got = np.asarray(ssd.causal_conv1d(x, w, b))
    xp = np.concatenate([np.zeros((2, 3, 5)), np.asarray(x)], axis=1)
    want = np.asarray(b) + sum(xp[:, j:j + 12] * np.asarray(w)[:, j]
                               for j in range(4))
    np.testing.assert_allclose(got, want, atol=1e-5)
    # A future token changes nothing before it.
    later = np.asarray(ssd.causal_conv1d(x.at[:, 7].add(3.0), w, b))
    assert (later[:, :7] == got[:, :7]).all()
    assert np.abs(later[:, 7:11] - got[:, 7:11]).min() > 0


def test_group_norm_gates_first_then_norms_each_group():
    y = jax.random.normal(jax.random.PRNGKey(0), (3, 12))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 12))
    scale = jax.random.normal(jax.random.PRNGKey(2), (12,))
    g = np.asarray(y * jax.nn.silu(z)).reshape(3, 2, 6)
    want = (g / np.sqrt((g ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 12) * np.asarray(scale)
    np.testing.assert_allclose(
        ssd.gated_group_rmsnorm(y, z, scale, 2, 1e-5), want, atol=1e-5)


# -- the shares add up -----------------------------------------------------------

def ssm_share(lp, cfg, r, n_shares):
    """Share r of a Mamba block's parameters: its heads of z, x and dt, its
    group of B and C, the conv's channels and the norm's features of those,
    the rows of ``w_out`` they feed; the block's own norm whole."""
    h, p, g, n = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    hp, gn = h * p, g * n
    heads = np.arange(r * h // n_shares, (r + 1) * h // n_shares)
    feats = (heads[:, None] * p + np.arange(p)).ravel()
    group = (np.arange(r * g // n_shares, (r + 1) * g // n_shares)[:, None]
             * n + np.arange(n)).ravel()
    conv = np.concatenate([feats, hp + group, hp + gn + group])
    cols = np.concatenate([feats, hp + conv, 2 * hp + 2 * gn + heads])
    return {"ln": lp["ln"], "w_in": lp["w_in"][:, cols],
            "conv_w": lp["conv_w"][conv], "conv_b": lp["conv_b"][conv],
            "dt_bias": lp["dt_bias"][heads], "a_log": lp["a_log"][heads],
            "d_skip": lp["d_skip"][heads], "norm": lp["norm"][feats],
            "w_out": lp["w_out"][feats]}


def test_the_eight_head_shares_of_a_mamba_block_sum_to_the_block():
    lp, x = one_block(WHOLE, "ssm"), stream(WHOLE)
    with jax.default_matmul_precision("highest"):
        whole = tfm._ssm_mixer(WHOLE, lp, x)
        parts = sum(tfm._ssm_mixer(SHARE, ssm_share(lp, WHOLE, r, 8), x)
                    for r in range(8))
    np.testing.assert_allclose(parts, whole,
                               atol=1e-5 * float(jnp.abs(whole).max()))


def test_the_eight_head_shares_of_an_attention_block_sum_to_the_block():
    """Share r holds query heads 2r, 2r + 1 and the kv head they read,
    (2r) // 8: four shares hold a copy of each kv head."""
    lp, x = one_block(WHOLE, "attn"), stream(WHOLE)
    hd = WHOLE.head_dim

    def share(r):
        q = np.arange(2 * r * hd, (2 * r + 2) * hd)
        kv = np.arange((r // 4) * hd, (r // 4 + 1) * hd)
        return {"ln": lp["ln"], "wq": lp["wq"][:, q], "wk": lp["wk"][:, kv],
                "wv": lp["wv"][:, kv], "wo": lp["wo"][q]}

    with jax.default_matmul_precision("highest"):
        whole = tfm._gqa_mixer(WHOLE, lp, x)
        parts = sum(tfm._gqa_mixer(SHARE, share(r), x) for r in range(8))
    np.testing.assert_allclose(parts, whole,
                               atol=1e-5 * float(jnp.abs(whole).max()))


def dense_latent_moe(cfg, lp, x):
    """The uncut "E" layer by its formula, every expert for every token."""
    tok = np.asarray(tfm._rmsnorm(x, lp["ln"], cfg.norm_eps),
                     np.float64).reshape(-1, cfg.d_model)
    w = dense_router_weights(tok @ np.asarray(lp["gate"], np.float64),
                             np.asarray(lp["router_bias"], np.float64),
                             cfg.top_k, cfg.router_scale)
    u = tok @ np.asarray(lp["w_latent_in"], np.float64)
    hidden = np.maximum(np.einsum("td,edf->tef", u, lp["w_up"]), 0) ** 2
    routed = np.einsum("te,tef,efd->td", w, hidden, lp["w_down"])
    shared = (np.maximum(tok @ np.asarray(lp["shared_up"], np.float64), 0)
              ** 2 @ np.asarray(lp["shared_down"], np.float64))
    return (routed @ np.asarray(lp["w_latent_out"], np.float64) + shared,
            shared)


def dense_router_weights(logits, bias, top_k, scale):
    """Choice by s + b, weights s (without b) over the chosen's sum."""
    s = 1.0 / (1.0 + np.exp(-logits))
    kth = np.sort(s + bias, axis=-1)[:, -top_k][:, None]
    w = np.where(s + bias >= kth, s, 0.0)
    assert ((w > 0).sum(-1) == top_k).all()
    return w / w.sum(-1, keepdims=True) * scale


def test_the_64_expert_shares_sum_to_the_uncut_layer():
    """Share r holds experts 2r, 2r + 1 of 128 and routes over all of them
    (its experts put first, which a router does not notice); the routed
    parts of the 64 shares and the shared expert, counted once, are the
    whole layer.  Nothing is dropped (the buffer holds every pair)."""
    lp = one_block(WHOLE, "moe")
    lp["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (128,))
    # At this width the 0.02 initialisation leaves the routed part a
    # thousandth of the shared expert's: widen it until both count.
    lp.update(w_up=lp["w_up"] * 30.0, w_down=lp["w_down"] * 30.0,
              w_latent_in=lp["w_latent_in"] * 5.0)
    x = stream(WHOLE)
    want, shared = dense_latent_moe(WHOLE, lp, x)

    def share(r):
        mine = np.arange(2 * r, 2 * r + 2)
        order = np.concatenate([mine, np.delete(np.arange(128), mine)])
        return {**lp, "gate": lp["gate"][:, order],
                "router_bias": lp["router_bias"][order],
                "w_up": lp["w_up"][mine], "w_down": lp["w_down"][mine]}

    total, dropped = 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for r in range(64):
            y, stats = tfm._expert_mixer(SHARE, share(r), x)
            total = total + np.asarray(y, np.float64).reshape(want.shape)
            dropped += float(stats.dropped)
    assert dropped == 0
    got = total - 63 * shared
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())
    assert np.abs(want - shared).max() > 0.3 * np.abs(want).max()


# -- the router ------------------------------------------------------------------

def router_case(t=64, d=32, e=32, held=4, k=6):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    h = jax.random.normal(ks[0], (t, d))
    params = moe.GatedMoEParams(
        gate=jax.random.normal(ks[1], (d, e)) * 0.3, w_gate=None,
        w_up=jax.random.normal(ks[2], (held, d, 24)) * 0.2,
        w_down=jax.random.normal(ks[3], (held, 24, d)) * 0.2,
        bias=jax.random.normal(ks[4], (e,)) * 0.2)
    return h, params, k


def relu2(v):
    return jnp.square(jax.nn.relu(v))


def test_sigmoid_router_is_the_dense_formula():
    """Choice by score + bias, weights by the score alone, renormalised
    over the 6 chosen and scaled; the held experts' part of the result."""
    h, params, k = router_case()
    with jax.default_matmul_precision("highest"):
        out, stats = moe.dropless_moe(
            params, h, k, relu2, moe.Router("sigmoid", True, 2.5),
            buffer_factor=100.0)
    w = dense_router_weights(np.asarray(h @ params.gate, np.float64),
                             np.asarray(params.bias, np.float64), k, 2.5)
    hidden = np.maximum(np.einsum("td,edf->tef", h, params.w_up), 0) ** 2
    want = np.einsum("te,tef,efd->td", w[:, :4], hidden, params.w_down)
    np.testing.assert_allclose(out, want, atol=1e-5)
    # What the router chose, over all 32 experts, held or not.
    assert np.asarray(stats.counts).tolist() == (w > 0).sum(0).tolist()
    assert float(stats.dropped) == 0
    # The bias moves the choice and takes no gradient.
    g = jax.grad(lambda b: jnp.sum(moe.dropless_moe(
        params._replace(bias=b), h, k, relu2,
        moe.Router("sigmoid", True, 2.5))[0]))(params.bias)
    assert not np.asarray(g).any()
    unbiased, _ = moe.dropless_moe(params._replace(bias=None), h, k, relu2,
                                   moe.Router("sigmoid", True, 2.5))
    assert np.abs(np.asarray(unbiased) - want).max() > 1e-3


def test_held_experts_gradients_are_the_dense_formulas():
    h, params, k = router_case()
    router = moe.Router("sigmoid", True, 2.5)

    def system(h, gate, w_up, w_down):
        return jnp.sum(jnp.sin(moe.dropless_moe(
            params._replace(gate=gate, w_up=w_up, w_down=w_down), h, k,
            relu2, router, buffer_factor=100.0)[0]))

    def dense(h, gate, w_up, w_down):
        s = jax.nn.sigmoid(h @ gate)
        kth = jnp.sort(s + params.bias, axis=-1)[:, -k][:, None]
        w = jnp.where(s + params.bias >= kth, s, 0.0)
        w = w / w.sum(-1, keepdims=True) * 2.5
        y = jnp.einsum("tef,efd->ted",
                       relu2(jnp.einsum("td,edf->tef", h, w_up)), w_down)
        return jnp.sum(jnp.sin(jnp.einsum("te,ted->td", w[:, :4], y)))

    args = (h, params.gate, params.w_up, params.w_down)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(system, argnums=(0, 1, 2, 3))(*args)
        want = jax.grad(dense, argnums=(0, 1, 2, 3))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()))


def test_rows_beyond_the_buffer_are_dropped_and_counted():
    h, params, k = router_case()
    router = moe.Router("sigmoid", True, 2.5)
    _, roomy = moe.dropless_moe(params, h, k, relu2, router,
                                buffer_factor=100.0)
    routed_here = int(np.asarray(roomy.counts)[:4].sum())
    rows = moe.held_row_buffer(64, k, 4, 32, 0.5)
    assert rows == 24 < routed_here
    out, tight = moe.dropless_moe(params, h, k, relu2, router,
                                  buffer_factor=0.5)
    assert float(tight.dropped) == routed_here - rows
    assert np.isfinite(np.asarray(out)).all()
    assert moe.held_row_buffer(16384, 22, 8, 512, 4.0) == 22528


def test_softmax_router_is_unchanged_by_the_new_arguments():
    """The default ``Router`` with every expert held is the path OLMoE
    takes: the same numbers whether the arguments are given or not."""
    h, params, k = router_case(held=32)
    params = params._replace(bias=None)
    a, sa = moe.dropless_moe(params, h, k)
    b, sb = moe.dropless_moe(params, h, k, jax.nn.silu, moe.Router(),
                             router_x=h, buffer_factor=1.0)
    assert (np.asarray(a) == np.asarray(b)).all()
    assert float(sa.dropped) == float(sb.dropped) == 0
    with pytest.raises(ValueError, match="scoring"):
        moe.dropless_moe(params, h, k, router=moe.Router("tanh"))


# -- the model on the mesh -------------------------------------------------------

def loss_and_grads(cfg, shape, params=None):
    hvd.init()
    par = tfm.ParallelConfig(*shape)
    mesh = create_mesh(dict(zip(("dp", "pp", "mp"), shape)),
                       devices=jax.devices()[:int(np.prod(shape))])
    if params is None:
        params = tfm.init_params(jax.random.PRNGKey(0), cfg, par)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), cfg, BATCH)
    return jax.jit(jax.value_and_grad(tfm.make_loss_fn(cfg, par, mesh)))(
        params, *batch)


@pytest.mark.parametrize("shape", [(2, 1, 1), (4, 1, 1)])
def test_every_dp_layout_gives_one_devices_loss_and_gradients(shape):
    loss1, grads1 = loss_and_grads(CFG, (1, 1, 1))
    loss, grads = loss_and_grads(CFG, shape)
    assert float(loss) == pytest.approx(float(loss1), abs=2e-6)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()
                           / (np.abs(np.asarray(b)).max() + 1e-30)),
        grads, grads1)
    assert max(jax.tree_util.tree_leaves(errs)) < 1e-4, errs
    assert not np.asarray(grads["layers"]["moe"]["router_bias"]).any()


def test_pattern_stacks_parameters_by_kind_and_period():
    shapes = jax.eval_shape(lambda: tfm.init_params(
        jax.random.PRNGKey(0), CFG, tfm.ParallelConfig()))
    assert set(shapes) == {"embed", "final_norm", "layers", "lm_head"}
    assert tfm.pattern_counts(CFG) == {"ssm": 1, "moe": 1, "attn": 1}
    layers = shapes["layers"]
    assert set(layers) == {"ssm", "moe", "attn"}
    # (1 stage, 2 periods, 1 block of the kind a period, ...)
    assert layers["ssm"]["w_in"].shape == (1, 2, 1, 32, 2 * 8 + 2 * 8 + 2)
    assert layers["attn"]["wq"].shape == (1, 2, 1, 32, 16)
    assert layers["attn"]["wk"].shape == (1, 2, 1, 32, 8)
    assert layers["moe"]["gate"].shape == (1, 2, 1, 32, 128)
    assert layers["moe"]["w_up"].shape == (1, 2, 1, 2, 16, 24)
    specs = tfm.param_specs(CFG, tfm.ParallelConfig(dp=2))
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, specs,
                               is_leaf=lambda s: isinstance(s, tuple))
    ) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, shapes))
    two = CFG._replace(layer_pattern="EMEM*", n_layers=5)
    assert tfm.pattern_counts(two) == {"ssm": 2, "moe": 2, "attn": 1}


def test_mamba_initialisation_is_the_published_scheme():
    cfg = CFG._replace(ssm_heads=64, ssm_groups=1)
    lp = one_block(cfg, "ssm")
    dt = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert np.log(dt).std() > 0.8            # log-uniform, not bunched
    a = np.exp(np.asarray(lp["a_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 2.0
    assert (np.asarray(lp["d_skip"]) == 1).all()
    assert np.abs(np.asarray(lp["conv_w"])).max() <= 0.5


def skewed(params):
    """Every token chooses the held experts: the bias lifts them."""
    params = dict(params, layers=dict(params["layers"]))
    moe_p = dict(params["layers"]["moe"])
    moe_p["router_bias"] = moe_p["router_bias"].at[..., :2].add(10.0)
    params["layers"]["moe"] = moe_p
    return params


def test_a_skewed_router_overflows_the_buffer_and_the_count_says_so():
    hvd.init()
    par = tfm.ParallelConfig()
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])
    params = tfm.init_params(jax.random.PRNGKey(0), CFG, par)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), CFG, BATCH)
    routing = tfm.make_routing_fn(CFG, par, mesh)
    even = routing(params, *batch)
    assert float(even["dropped"]) == 0
    tokens = batch[0].size
    lopsided = routing(skewed(params), *batch)
    counts = np.asarray(lopsided["assignments"])
    assert counts.shape == (2, 1, 128)           # periods, E blocks, experts
    assert (counts[..., :2] == tokens).all()
    assert (counts.sum(-1) == tokens * CFG.top_k).all()
    assert (np.asarray(lopsided["held_rows"]) == 2 * tokens).all()
    buffer = moe.held_row_buffer(tokens, CFG.top_k, 2, 128, 4.0)
    assert float(lopsided["dropped"]) == 2 * (2 * tokens - buffer) > 0
    assert np.isfinite(float(lopsided["loss"]))


def test_balancer_moves_the_bias_until_every_expert_is_chosen_alike():
    """A router lopsided by a bias of +-0.2 a score (the busiest expert
    takes 7 x the mean; 80 tokens a mean expert, so 1.3 is noise): twelve
    rounds of the proportional rule more than halve that, forty-eight bring
    it under 1.5, the other parameters untouched;
    an unpatterned or softmax router has no bias to balance."""
    hvd.init()
    par = tfm.ParallelConfig()
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])
    params = tfm.init_params(jax.random.PRNGKey(0), CFG, par)
    moe_p = params["layers"]["moe"]
    # Logits as wide as at the published hidden size (0.02 x sqrt(4096)):
    # the rule's gain is in units of a score.
    moe_p["gate"] = moe_p["gate"] * (64.0 / np.sqrt(CFG.d_model))
    moe_p["router_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(5), moe_p["router_bias"].shape)
    cfg = CFG._replace(seq_len=256)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), cfg, BATCH)
    routing = tfm.make_routing_fn(cfg, par, mesh)
    before = np.asarray(routing(params, *batch)["load"])
    balanced = jax.jit(tfm.make_router_balancer(cfg, par, mesh))(
        params, *batch)
    after = np.asarray(routing(balanced, *batch)["load"])
    assert before.min() > 5.0 and after.max() < 3.0, (before, after)
    longer = jax.jit(tfm.make_router_balancer(cfg, par, mesh, rounds=48))(
        params, *batch)
    assert np.asarray(routing(longer, *batch)["load"]).max() < 1.5
    same = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.all(a == b)), balanced, params)
    assert not same["layers"]["moe"].pop("router_bias")
    assert all(jax.tree_util.tree_leaves(same))
    with pytest.raises(ValueError, match="correction bias"):
        tfm.make_router_balancer(CFG._replace(router_scoring="softmax"), par,
                                 mesh)


@pytest.mark.parametrize("par, item", [
    (tfm.ParallelConfig(mp=2), "M0"), (tfm.ParallelConfig(pp=2), "M0")])
def test_a_patterned_model_refuses_mp_and_pp(par, item):
    with pytest.raises(NotImplementedError, match=f"ROADMAP {item}"):
        tfm.init_params(jax.random.PRNGKey(0), CFG, par)
    with pytest.raises(NotImplementedError, match=f"ROADMAP {item}"):
        tfm.param_specs(CFG, par)


def test_a_share_holding_layer_refuses_mp_and_serving_refuses_both():
    uniform = tfm.TransformerConfig(
        vocab_size=128, d_model=32, n_heads=4, d_ff=16, n_layers=2,
        seq_len=32, n_experts=16, top_k=2, dropless=True, n_experts_held=4)
    shapes = jax.eval_shape(lambda: tfm.init_params(
        jax.random.PRNGKey(0), uniform, tfm.ParallelConfig(dp=2)))
    assert shapes["layers"]["w_up"].shape == (1, 2, 4, 32, 16)
    assert shapes["layers"]["gate"].shape == (1, 2, 32, 16)
    with pytest.raises(NotImplementedError, match="ROADMAP M2"):
        tfm.init_params(jax.random.PRNGKey(0), uniform,
                        tfm.ParallelConfig(mp=2))
    for cfg, named in [(uniform, "a share of the experts held"),
                       (CFG, "a layer_pattern")]:
        with pytest.raises(NotImplementedError, match="ROADMAP M1") as e:
            tfm._check_servable(cfg)
        assert named in str(e.value)
    with pytest.raises(ValueError, match="layer_pattern"):
        tfm.init_params(jax.random.PRNGKey(0),
                        uniform._replace(n_kv_heads=2), tfm.ParallelConfig())
    with pytest.raises(ValueError, match="multiple"):
        tfm.init_params(jax.random.PRNGKey(0), CFG._replace(n_layers=4),
                        tfm.ParallelConfig())


def test_the_new_scopes_name_their_operations():
    assert profiler.SSM_SCOPES == ("ssm", "ssm_conv", "ssm_scan")
    assert profiler.LATENT_MOE_SCOPES == ("moe_latent", "moe_shared")
    hvd.init()
    par = tfm.ParallelConfig()
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])
    params = tfm.init_params(jax.random.PRNGKey(0), CFG, par)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), CFG, BATCH)
    hlo = jax.jit(jax.grad(tfm.make_loss_fn(CFG, par, mesh))).lower(
        params, *batch).as_text(debug_info=True)
    for name in (profiler.SSM_SCOPES + profiler.LATENT_MOE_SCOPES
                 + profiler.MOE_SCOPES + ("attn", "mlp", "head", "embed")):
        assert f"hvd_{name}/" in hlo, name
    # The conv and the scan sit inside the block's scope.
    assert "hvd_ssm/hvd_ssm_scan/" in hlo and "hvd_ssm/hvd_ssm_conv/" in hlo
    assert "hvd_mlp/hvd_moe_shared/" in hlo


def test_defaults_leave_the_uniform_block_as_it_was():
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                d_ff=64, n_layers=2, seq_len=16)
    assert cfg.head_dim == 8 and cfg.layer_pattern is None
    shapes = jax.eval_shape(lambda: tfm.init_params(
        jax.random.PRNGKey(0), cfg, tfm.ParallelConfig()))
    assert set(shapes) == {"embed", "final_norm", "layers", "pos"}
    assert set(shapes["layers"]) == {"ln1", "ln2", "wqkv", "wo", "w1", "w2"}
    assert tfm.train_flops_per_seq(cfg) == 3.0 * 16 * (
        2 * (8 * 32 * 32 + 4 * 32 * 64) + 2 * 32 * 64) + 3.0 * 2 * 2 * 16 \
        * 16 * 32
