"""What SDAR-30B-A3B-Chat's block-diffusion training added to
``models/transformer.py`` (``diffusion_block``: the doubled sequence, the
wrapped positions, the mask, the weighted loss on the noised half and the
third batch array; ``head_qk_norm``), piece by piece against formulas
written out here and the benchmark's plain reference, the shares of a
deployment against the whole layer, and the built tree against the published
count."""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import loader                      # noqa: E402
from horovod_tpu.models import transformer as tfm  # noqa: E402
from horovod_tpu.parallel.mesh import create_mesh  # noqa: E402
from horovod_tpu.utils import profiler             # noqa: E402

REF = loader.load_code("reference", "sdar")
CELL = "sdar-30b-a3b-s4096-train-1chip"
# Two layers at a small size: 4 query heads on 2 kv heads of 8, 16 experts
# of which every one is held (WHOLE) or 2 (SHARE: one rank of 8).
WHOLE = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=12, n_layers=4, seq_len=16,
    n_experts=16, top_k=4, dtype=jnp.float32, dropless=True,
    tied_head=False, gated_experts=True, layer_pattern="*E",
    learned_positions=False, n_kv_heads=2, attn_head_dim=8, rope_theta=1e6,
    router_renormalise=True, head_qk_norm=True, diffusion_block=4,
    expert_buffer_factor=64.0)
SHARE = WHOLE._replace(n_experts_held=2)
PAR = tfm.ParallelConfig()
ARCH = dict(norm_eps=WHOLE.norm_eps, n_kv_heads=2, head_dim=8,
            rope_theta=1e6, top_k=4, block=4)


def one_device_mesh():
    return create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])


def seeded(cfg, key=0):
    """Parameters with norms and QK-norm scales off 1, so that a scale that
    is dropped or misplaced shows."""
    params = tfm.init_params(jax.random.PRNGKey(key), cfg, PAR)
    keys = iter(jax.random.split(jax.random.PRNGKey(key + 1), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.3 * jax.random.normal(next(keys), a.shape)
        if "norm" in jax.tree_util.keystr(path) or "ln" in
        jax.tree_util.keystr(path) else a * 8.0, params)


def to_reference(params):
    """The system's stacked tree as the reference's list of layers."""
    layers = params["layers"]
    names = {"attn": {k: k for k in ("ln", "wq", "wk", "wv", "q_norm",
                                     "k_norm", "wo")},
             "moe": {"ln": "ln", "gate": "router", "w_gate": "w1",
                     "w_up": "w3", "w_down": "w2"}}
    return {**{k: v for k, v in params.items() if k != "layers"},
            "layers": [{half: {names[kind][k]: v[0, p, 0]
                               for k, v in layers[kind].items()}
                        for half, kind in (("attn", "attn"), ("mlp", "moe"))}
                       for p in range(layers["attn"]["wq"].shape[1])]}


# -- (a) the doubled sequence is the definition ---------------------------------------

def plain_block_causal_nll(ref_params, ids, labels, block):
    """-log p(labels) at the positions of the LAST block of ``ids``, from a
    plain forward over ``ids`` alone at positions 0 .. n-1 under a
    block-causal mask (a position sees every position of its own block and
    of the blocks before it): no doubling, no second copy, written out here
    with nothing of the program or of the reference's attention."""
    n, eps = ids.shape[0], WHOLE.norm_eps
    at = np.arange(n)
    sees = (at[None, :] // block) <= (at[:, None] // block)
    half = 4
    angle = at[:, None] * 1e6 ** (-np.arange(half) / half)[None, :]
    cos, sin = np.cos(angle)[:, None, :], np.sin(angle)[:, None, :]

    def norm(x, g):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g

    def rot(t):
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    x = ref_params["embed"][ids]
    for lp in ref_params["layers"]:
        a, m = lp["attn"], lp["mlp"]
        h = norm(x, a["ln"])
        q = rot(norm((h @ a["wq"]).reshape(n, 4, 8), a["q_norm"]))
        k = rot(norm((h @ a["wk"]).reshape(n, 2, 8), a["k_norm"]))
        v = (h @ a["wv"]).reshape(n, 2, 8)
        k, v = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(8.0)
        p = jax.nn.softmax(jnp.where(sees[None], s, -jnp.inf), -1)
        x = x + jnp.einsum("hqk,khd->qhd", p, v).reshape(n, 32) @ a["wo"]
        h = norm(x, m["ln"])
        probs = jax.nn.softmax(h @ m["router"], -1)
        kth = jnp.sort(probs, -1)[:, -4][:, None]
        w = jnp.where(probs >= kth, probs, 0.0)
        w = w / w.sum(-1, keepdims=True)
        for e in range(m["w1"].shape[0]):
            x = x + w[:, e:e + 1] * (
                (jax.nn.silu(h @ m["w1"][e]) * (h @ m["w3"][e])) @ m["w2"][e])
    logits = norm(x, ref_params["final_norm"]) @ ref_params["lm_head"].T
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp[-block:], labels[:, None], -1)[:, 0]


def test_the_doubled_sequence_is_the_definition():
    """For every block b, the noised positions of b in the one 2L forward
    read what a plain block-causal forward over [clean blocks < b ; noised
    block b] reads: the system's loss with the weights on block b alone
    against the plain forward's log-likelihoods."""
    params = seeded(WHOLE)
    ref_params = to_reference(params)
    tokens, labels, _ = tfm.synthetic_batch(jax.random.PRNGKey(5), WHOLE, 1)
    length, block = WHOLE.seq_len, WHOLE.diffusion_block
    noised, clean = tokens[0, :length], tokens[0, length:]
    assert (clean == labels[0]).all() and (noised != clean).any()
    loss_of = jax.jit(tfm.make_loss_fn(WHOLE, PAR, one_device_mesh()))
    for b in range(length // block):
        lo, hi = b * block, (b + 1) * block
        on_block = jnp.zeros((1, length)).at[0, lo:hi].set(
            jnp.arange(1.0, block + 1))
        got = float(loss_of(params, tokens, labels, on_block)) * length
        nll = plain_block_causal_nll(
            ref_params, jnp.concatenate([clean[:lo], noised[lo:hi]]),
            labels[0, lo:hi], block)
        want = float(jnp.sum(nll * jnp.arange(1.0, block + 1)))
        assert got == pytest.approx(want, rel=2e-5), b


def test_the_loss_is_the_references_and_so_are_the_gradients():
    params = seeded(SHARE, key=3)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(7), SHARE, 3)
    got, g = jax.jit(jax.value_and_grad(tfm.make_loss_fn(
        SHARE, PAR, one_device_mesh())))(params, *batch)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p, *b: REF.loss(p, *b, **ARCH)))(to_reference(params), *batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(
            to_reference(g)), jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-3,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_loss_over_dp_is_a_sum_of_sums():
    """Two data-parallel ranks with unequal weights: the loss is the global
    weighted sum over B x L, what one device computes."""
    params = seeded(WHOLE, key=2)
    tokens, labels, weights = tfm.synthetic_batch(jax.random.PRNGKey(9),
                                                  WHOLE, 4)
    weights = weights.at[:2].multiply(3.0)
    one = tfm.make_loss_fn(WHOLE, PAR, one_device_mesh())(
        params, tokens, labels, weights)
    par2 = tfm.ParallelConfig(dp=2)
    mesh2 = create_mesh({"dp": 2, "pp": 1, "mp": 1},
                        devices=jax.devices()[:2])
    two = tfm.make_loss_fn(WHOLE, par2, mesh2)(params, tokens, labels,
                                               weights)
    assert float(two) == pytest.approx(float(one), rel=1e-6)


# -- (b) the attention block -----------------------------------------------------------

def test_per_head_qk_norm_and_wrapped_positions_against_the_reference():
    params = seeded(WHOLE, key=4)
    lp = {k: v[0, 1, 0] for k, v in params["layers"]["attn"].items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    got = tfm._gqa_mixer(WHOLE, lp, x)
    for b in range(2):
        want = REF.attention_block(
            REF.rmsnorm(x[b], lp["ln"], WHOLE.norm_eps), lp, n_kv_heads=2,
            head_dim=8, rope_theta=1e6, block=4, norm_eps=WHOLE.norm_eps)
        np.testing.assert_allclose(got[b], want, atol=2e-5, rtol=1e-4)
    # The scales are per feature of a head and shared by the heads: (hd,).
    assert lp["q_norm"].shape == lp["k_norm"].shape == (8,)
    # Without the norm, or with positions that run on through the clean
    # copy, the block reads otherwise.
    plain = tfm._gqa_mixer(WHOLE._replace(head_qk_norm=False), lp, x)
    assert np.abs(plain - got).max() > 1e-2
    hlo = jax.jit(lambda x: tfm._gqa_mixer(WHOLE, lp, x)).lower(x).as_text(
        debug_info=True)
    assert "hvd_attn_qknorm" in hlo and "hvd_attn_rope" in hlo
    assert "attn_qknorm" in profiler.ATTN_PART_SCOPES


# -- (c) the share sums to the layer ----------------------------------------------------

def test_the_eight_expert_shares_add_up_to_the_whole_expert_layer():
    """8 ranks of 2 experts: rank r numbers its own experts first (its
    router columns and weights rolled to the front); the ranks' parts add up
    to the uncut layer of the reference."""
    params = seeded(WHOLE, key=6)
    lp = {k: v[0, 0, 0] for k, v in params["layers"]["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32))
    tok = REF.rmsnorm(x, lp["ln"], WHOLE.norm_eps).reshape(-1, 32)
    want = REF.moe_block(tok, {"router": lp["gate"], "w1": lp["w_gate"],
                               "w3": lp["w_up"], "w2": lp["w_down"]},
                         top_k=4)
    total = 0.0
    for r in range(8):
        mine = slice(2 * r, 2 * r + 2)
        share = {**lp, "gate": jnp.roll(lp["gate"], -2 * r, axis=1),
                 **{k: lp[k][mine] for k in ("w_gate", "w_up", "w_down")}}
        y, stats = tfm._expert_mixer(SHARE, share, x)
        assert float(stats.dropped) == 0.0
        total = total + y.reshape(-1, 32)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)
    # A rank alone is not the layer: most of the routed part is elsewhere.
    assert np.abs(y.reshape(-1, 32) - want).max() > 1e-3


# -- (d) the published count --------------------------------------------------------------

def test_parameter_count_is_exact():
    cell = loader.load_cell(CELL)
    fam = loader.load_code("families", "sdar").Family(
        cell["config"], cell["traffic"]["mesh"])
    shapes = jax.eval_shape(fam.init_params, jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == 645_623_296 == cell["config"]["parameters"]
    attn = sum(int(np.prod(a.shape[2:])) for a in
               jax.tree_util.tree_leaves(shapes["layers"]["attn"]))
    moe = sum(int(np.prod(a.shape[2:])) for a in
              jax.tree_util.tree_leaves(shapes["layers"]["moe"]))
    assert (attn, moe) == (18_876_672, 75_761_664)
    assert shapes["layers"]["attn"]["wq"].shape[1] == 6
    assert shapes["embed"].shape == shapes["lm_head"].shape == (18992, 2048)


# -- (e) the noise ---------------------------------------------------------------------------

def test_the_noise_masks_a_block_at_its_own_rate():
    ids = jax.random.randint(jax.random.PRNGKey(0), (64, 4096), 0, 99)
    tokens, labels, weights = tfm.noised_batch(jax.random.PRNGKey(1), ids,
                                               64, mask_id=99)
    assert tokens.shape == (64, 8192) and tokens.dtype == jnp.int32
    assert weights.shape == (64, 4096) and weights.dtype == jnp.float32
    noised, clean = np.asarray(tokens[:, :4096]), np.asarray(tokens[:, 4096:])
    assert (clean == np.asarray(ids)).all() and (np.asarray(labels) == clean).all()
    assert (np.asarray(labels) != 99).all()          # a label is never MASK
    masked = noised == 99
    assert (noised[~masked] == clean[~masked]).all()
    w = np.asarray(weights)
    assert ((w > 0) == masked).all()
    # One t a block: every masked position of a block weighs the same 1 / t,
    # 1 <= 1 / t <= 1 / floor.
    by_block = w.reshape(64, 64, 64)
    some = by_block.max(-1) > 0                  # a block with a masked id
    t = 1.0 / np.where(some, by_block.max(-1), 1.0)
    assert ((by_block == 0) | np.isclose(by_block, by_block.max(
        -1, keepdims=True))).all()
    assert (t[some] >= 1e-3 - 1e-9).all() and (t[some] <= 1.0 + 1e-6).all()
    # The masked share of a block of 64 lies within five binomial standard
    # deviations of its t; t itself is uniform.
    share = masked.reshape(64, 64, 64).mean(-1)
    sd = np.sqrt(np.maximum(t * (1 - t), 1e-4) / 64)
    assert (np.abs(share - t)[some] <= 5 * sd[some] + 1 / 64).all()
    assert abs(t[some].mean() - 0.5) < 0.02
    # E[weights] = 1: the loss is a mean over the data tokens.
    assert abs(w.mean() - 1.0) < 0.02
    with pytest.raises(ValueError, match="whole blocks"):
        tfm.noised_batch(jax.random.PRNGKey(1), ids[:, :100], 64, 99)


def test_synthetic_batch_makes_the_three_arrays():
    tokens, labels, weights = tfm.synthetic_batch(jax.random.PRNGKey(0),
                                                  WHOLE, 5)
    assert tokens.shape == (5, 32) and labels.shape == weights.shape == (5, 16)
    assert int(labels.max()) < 63 and int(tokens.max()) == 63   # MASK: last id
    causal = WHOLE._replace(diffusion_block=None)
    assert len(tfm.synthetic_batch(jax.random.PRNGKey(0), causal, 5)) == 2


# -- the model: what it refuses, names, arithmetic -------------------------------------------

def test_what_a_diffusion_block_refuses():
    tfm._check_layout(WHOLE, PAR)
    for bad, par, error, match in [
            (WHOLE, tfm.ParallelConfig(mp=2), NotImplementedError,
             "doubled sequence"),
            (WHOLE, tfm.ParallelConfig(pp=2), NotImplementedError,
             "doubled sequence"),
            (WHOLE._replace(attn_mode="ring"), PAR, NotImplementedError,
             "attn_mode 'ring'"),
            (WHOLE._replace(layer_pattern="WE", attn_window=8), PAR,
             NotImplementedError, "sliding window"),
            (WHOLE._replace(diffusion_block=5), PAR, ValueError,
             "whole blocks"),
            (WHOLE._replace(rope_theta=None, learned_positions=True), PAR,
             NotImplementedError, "learned position table"),
            (WHOLE._replace(layer_pattern=None, n_kv_heads=None,
                            attn_head_dim=None, head_qk_norm=False), PAR,
             ValueError, "set layer_pattern"),
            (WHOLE._replace(layer_pattern=None, n_kv_heads=None,
                            attn_head_dim=None, diffusion_block=None), PAR,
             ValueError, "set layer_pattern")]:
        with pytest.raises(error, match=match):
            tfm._check_layout(bad, par)
    with pytest.raises(NotImplementedError, match="diffusion_block"):
        tfm._check_servable(WHOLE)
    # The third array goes with the field, and only with it.
    params = seeded(WHOLE)
    tokens, labels, weights = tfm.synthetic_batch(jax.random.PRNGKey(0),
                                                  WHOLE, 2)
    loss_of = tfm.make_loss_fn(WHOLE, PAR, one_device_mesh())
    with pytest.raises(ValueError, match="weights"):
        loss_of(params, tokens, labels)
    with pytest.raises(ValueError, match="2 x seq_len"):
        loss_of(params, tokens[:, :16], labels, weights)
    causal = WHOLE._replace(diffusion_block=None)
    with pytest.raises(ValueError, match="and no other"):
        tfm.make_loss_fn(causal, PAR, one_device_mesh())(
            params, labels, labels, weights)


def test_the_step_trains_routes_and_names_its_parts():
    mesh = one_device_mesh()
    params = seeded(SHARE, key=8)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(3), SHARE, 2)
    hlo = jax.jit(jax.grad(tfm.make_loss_fn(SHARE, PAR, mesh))).lower(
        params, *batch).as_text(debug_info=True)
    for name in ("attn_qknorm", "attn_rope", "moe_route", "head"):
        assert f"hvd_{name}" in hlo, name
    routing = tfm.make_routing_fn(SHARE, PAR, mesh)(params, *batch)
    # Both copies of a sequence are routed: 2 x 32 positions x top-4.
    assert routing["assignments"].shape == (2, 1, 16)
    assert float(routing["assignments"][0].sum()) == 2 * 32 * 4
    assert float(routing["dropped"]) == 0.0
    opt = optax.adamw(1e-2)
    step, shard = tfm.make_train_step(SHARE, PAR, mesh, opt)
    p = shard(params)
    state = opt.init(p)
    losses = []
    for _ in range(4):
        p, state, loss = step(p, state, *batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_flops_count_two_positions_a_token_and_the_live_pairs():
    d, s, hd, bk = 32, 16, 8, 4
    pairs = (s * s + s * bk) / (2 * s)            # a query, of 2 s queries
    attn = 2 * d * hd * (2 * 4 + 2 * 2) + 4 * pairs * 4 * hd
    moe = 2 * d * 16 + 4 * 2 / 16 * 6 * d * 12
    assert tfm.BLOCKS["*"].flops(SHARE) == pytest.approx(attn)
    assert tfm.BLOCKS["E"].flops(SHARE) == pytest.approx(moe)
    assert tfm.train_flops_per_seq(SHARE) == pytest.approx(
        3 * s * (2 * 2 * (attn + moe) + 2 * d * 64))
    # The benchmark's yardstick counts the same, from its own arithmetic.
    fam = loader.load_code("families", "sdar")
    c = {"d_model": d, "attn_head_dim": hd, "n_heads": 4, "n_kv_heads": 2,
         "n_experts": 16, "n_experts_held": 2, "top_k": 4, "d_ff": 12,
         "seq_len": s, "diffusion_block": bk, "n_layers": 4,
         "layer_pattern": "*E", "vocab_size": 64}
    assert fam.model_flops_per_token(c) * s == pytest.approx(
        tfm.train_flops_per_seq(SHARE))
