"""What a model of windowed and full attention layers adds to the training
path (``laguna``: sliding-window blocks with their own head count and rotary
base, rotary positions on half a head with YaRN-scaled frequencies, a
per-head output gate, a gated dense MLP block, blocks that lead the scanned
periods, a gated shared expert), piece by piece against formulas written
out here, the shares of a deployment against the whole layer of the
benchmark's plain reference, and what the new fields' defaults leave as it
was.  The whole model against the reference is
``tests/benchmark_tests/test_benchmark_laguna.py``."""

import hashlib
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import loader                       # noqa: E402
from horovod_tpu.models import transformer as tfm  # noqa: E402
from horovod_tpu.parallel.mesh import create_mesh  # noqa: E402
from horovod_tpu.utils import profiler             # noqa: E402

REF = loader.load_code("reference", "laguna")
YARN = (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
# A whole layer at a small size: 2 shares of 2 / 3 query heads on 1 kv head,
# 4 shares of 4 experts.
WHOLE = tfm.TransformerConfig(
    vocab_size=128, d_model=32, n_heads=4, d_ff=24, n_layers=4, seq_len=48,
    n_experts=16, top_k=3, dtype=jnp.float32, dropless=True,
    tied_head=False, gated_experts=True, layer_pattern="WE",
    leading_pattern="*D", learned_positions=False, n_kv_heads=2,
    attn_head_dim=8, rope_theta=500000.0, rope_fraction=0.5,
    rope_yarn=(128.0, 16, 32.0, 1.0, 1.4852), attn_window=8, window_heads=6,
    window_rope_theta=10000.0, attn_gate=True, dense_ff=40,
    router_renormalise=True, router_scale=2.5, shared_expert_ff=24,
    expert_buffer_factor=64.0)
SHARE = WHOLE._replace(n_heads=2, window_heads=3, n_kv_heads=1,
                       n_experts_held=4)
FULL_ROPE = (500000.0, 0.5, 128.0, 16, 32.0, 1.0, 1.4852)


def block(cfg, kind, leading=False, key=0):
    """One block's parameters of ``kind``, no stage, period or block axes."""
    layers = tfm.init_params(jax.random.PRNGKey(key), cfg,
                             tfm.ParallelConfig())["layers"]
    if leading:
        return {k: v[0, 0] for k, v in layers["leading"][kind].items()}
    return {k: v[0, 0, 0] for k, v in layers[kind].items()}


def stream(cfg, key=1, batch=2):
    return jax.random.normal(jax.random.PRNGKey(key),
                             (batch, cfg.seq_len, cfg.d_model))


# -- positions ---------------------------------------------------------------------

def test_yarn_frequencies_by_hand_for_64_rotary_features():
    """theta 500000, factor 128 over 8192 positions, beta 32 / 1: a
    frequency turns 8192 f / 2 pi times over the original context; the
    correction dimensions are floor(9.04) = 9 and ceil(17.49) = 18.  Up to 9
    the frequencies stay, from 18 they are divided by 128, between them
    (i - 9) / 9 of the way."""
    f = tfm._yarn_inv_freq(64, 500000.0, YARN)
    assert f.shape == (32,) and f.dtype == np.float32
    plain = [500000.0 ** (-i / 32) for i in range(32)]
    assert 64 * math.log(8192 / (32 * 2 * math.pi)) / (
        2 * math.log(500000.0)) == pytest.approx(9.04, abs=0.01)
    assert 64 * math.log(8192 / (2 * math.pi)) / (
        2 * math.log(500000.0)) == pytest.approx(17.49, abs=0.01)
    np.testing.assert_allclose(f[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(f[18:], np.array(plain[18:]) / 128, rtol=1e-6)
    assert f[0] == 1.0
    assert f[9] == pytest.approx(0.0249547, rel=1e-4)
    assert f[12] == pytest.approx(plain[12] * (2 / 3 + 1 / 3 / 128), rel=1e-6)
    assert f[12] == pytest.approx(4.88059e-3, rel=1e-4)
    assert f[18] == pytest.approx(4.8653e-6, rel=1e-3)
    # The reference's own copy of the formula, written apart.
    np.testing.assert_allclose(
        REF.yarn_frequencies(64, 500000.0, 128.0, 8192, 32.0, 1.0), f,
        rtol=1e-6)


def test_half_a_head_rotates_and_the_other_half_passes():
    """A head of 8 at rotary share 0.5: features 0-3 rotate as two pairs
    (0, 2) and (1, 3), rotate-half inside those four; 4-7 pass.  With YaRN
    cos and sin carry the attention factor."""
    t = jnp.arange(1.0, 9.0).reshape(1, 1, 1, 8) * jnp.ones((1, 3, 1, 1))
    pos = jnp.arange(3)
    out = np.asarray(tfm._rope(t, pos, 100.0, 0.5))
    f = [1.0, 100.0 ** -0.5]
    for p in range(3):
        a, b, c, d = 1.0, 2.0, 3.0, 4.0
        want = [a * math.cos(p * f[0]) - c * math.sin(p * f[0]),
                b * math.cos(p * f[1]) - d * math.sin(p * f[1]),
                c * math.cos(p * f[0]) + a * math.sin(p * f[0]),
                d * math.cos(p * f[1]) + b * math.sin(p * f[1]),
                5.0, 6.0, 7.0, 8.0]
        np.testing.assert_allclose(out[0, p, 0], want, rtol=1e-6)
    scaled = np.asarray(tfm._rope(t, pos, 100.0, 0.5,
                                  (1.0, 16, 32.0, 1.0, 1.5)))
    np.testing.assert_allclose(scaled[..., :4], 1.5 * out[..., :4], rtol=1e-6)
    np.testing.assert_allclose(scaled[..., 4:], out[..., 4:])
    # The whole head at one theta is what it always was (OLMoE's).
    whole = np.asarray(tfm._rope(t, pos, 100.0))
    half = 4
    inv = 1.0 / 100.0 ** (np.arange(half) / half)
    ang = np.arange(3)[:, None] * inv
    x = np.arange(1.0, 9.0)
    np.testing.assert_allclose(
        whole[0, :, 0], np.concatenate(
            [x[:half] * np.cos(ang) - x[half:] * np.sin(ang),
             x[half:] * np.cos(ang) + x[:half] * np.sin(ang)], -1), rtol=1e-5)


# -- the blocks against the reference's equations ------------------------------------

def reference_attention_block(cfg, lp, x, sliding):
    names = {"wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
             "w_head_gate": "wg"}
    rp = {names[k]: v for k, v in lp.items() if k in names}
    h = REF.rmsnorm(x, lp["ln"], cfg.norm_eps)
    return jnp.stack([REF.attention_block(
        h_b, rp, sliding=sliding, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, window=cfg.attn_window, full_rope=FULL_ROPE,
        sliding_theta=cfg.window_rope_theta) for h_b in h])


@pytest.mark.parametrize("kind", ["attn", "swa"])
def test_an_attention_block_is_the_references(kind):
    cfg = WHOLE
    lp = block(cfg, kind, leading=kind == "attn")
    lp = {**lp, "wq": lp["wq"] * 8.0, "wk": lp["wk"] * 8.0,
          "w_head_gate": lp["w_head_gate"] * 20.0}
    x = stream(cfg)
    got = tfm._gqa_mixer(cfg, lp, x, kind)
    want = reference_attention_block(cfg, lp, x, kind == "swa")
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)
    # The gate, the window and the rotation each matter at this size.
    for wrong in (cfg._replace(attn_gate=False),
                  cfg._replace(attn_window=7),
                  cfg._replace(attn_window=9),
                  cfg._replace(rope_fraction=1.0, window_rope_theta=500.0)):
        off = np.abs(tfm._gqa_mixer(wrong, lp, x, kind) - want).max()
        if kind == "attn" and wrong.attn_window != 8:
            assert off < 1e-5           # a full layer has no window
        else:
            assert off > 1e-3 * np.abs(want).max(), wrong


def test_the_two_head_shares_add_up_to_the_whole_attention_block():
    """Heads divided 2 ways: share r holds query heads r H/2 .. and key /
    value head r, its columns of Wq, Wk, Wv and the gate and its rows of
    Wo; the two shares' outputs add up to the uncut block of the
    reference, full and sliding."""
    for kind in ("attn", "swa"):
        lp = block(WHOLE, kind, leading=kind == "attn")
        lp = {**lp, "wq": lp["wq"] * 8.0, "wk": lp["wk"] * 8.0}
        x = stream(WHOLE)
        hq = tfm._row(kind).attention(WHOLE).heads
        hd, total = WHOLE.head_dim, 0.0
        for r in range(2):
            q = slice(r * hq // 2 * hd, (r + 1) * hq // 2 * hd)
            kv = slice(r * hd, (r + 1) * hd)
            share = {"ln": lp["ln"], "wq": lp["wq"][:, q],
                     "wk": lp["wk"][:, kv], "wv": lp["wv"][:, kv],
                     "w_head_gate": lp["w_head_gate"][
                         :, r * hq // 2:(r + 1) * hq // 2],
                     "wo": lp["wo"][q]}
            total = total + tfm._gqa_mixer(SHARE, share, x, kind)
        want = reference_attention_block(WHOLE, lp, x, kind == "swa")
        np.testing.assert_allclose(total, want, atol=2e-6, rtol=1e-4)


def test_the_expert_shares_add_up_to_the_whole_expert_layer():
    """4 ranks of 4 experts: rank r numbers its own experts first (its
    router columns and weights rolled to the front); the ranks' routed
    parts plus the shared expert, which every rank computes alike, counted
    once, add up to the uncut layer of the reference."""
    lp = block(WHOLE, "moe")
    lp = {**lp, "gate": lp["gate"] * 40.0}
    x = stream(WHOLE)
    tok = REF.rmsnorm(x, lp["ln"], WHOLE.norm_eps).reshape(-1, 32)
    whole = {"router": lp["gate"], "w1": lp["w_gate"], "w3": lp["w_up"],
             "w2": lp["w_down"], "s1": lp["shared_gate"],
             "s3": lp["shared_up"], "s2": lp["shared_down"]}
    want = REF.mlp_block(tok, whole, top_k=3, router_scale=2.5)
    shared = REF.swiglu(tok, whole["s1"], whole["s3"], whole["s2"])
    total = 0.0
    for r in range(4):
        mine = slice(4 * r, 4 * r + 4)
        share = {**lp, "gate": jnp.roll(lp["gate"], -4 * r, axis=1),
                 **{k: lp[k][mine] for k in ("w_gate", "w_up", "w_down")}}
        y, stats = tfm._expert_mixer(SHARE, share, x)
        assert float(stats.dropped) == 0.0
        total = total + y.reshape(-1, 32) - shared
    np.testing.assert_allclose(total + shared, want, atol=2e-6, rtol=1e-4)
    # A rank alone is not the layer: most of the routed part is elsewhere.
    assert np.abs(y.reshape(-1, 32) - want).max() > 1e-3


def test_a_dense_block_is_a_gated_mlp_under_its_own_name():
    lp = block(WHOLE, "dense", leading=True)
    x = stream(WHOLE)
    h = REF.rmsnorm(x, lp["ln"], WHOLE.norm_eps)
    np.testing.assert_allclose(
        tfm._dense_mixer(WHOLE, lp, x),
        REF.swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]),
        atol=1e-6, rtol=1e-4)
    hlo = jax.jit(lambda x: tfm._dense_mixer(WHOLE, lp, x)).lower(
        x).as_text(debug_info=True)
    assert "hvd_" + profiler.DENSE_MLP_SCOPE in hlo


# -- the model: layout, names, arithmetic ---------------------------------------------

def test_leading_blocks_sit_beside_the_stacked_ones_and_run_first():
    cfg = WHOLE._replace(n_layers=6, n_experts_held=4)
    par = tfm.ParallelConfig()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, par)
    layers = params["layers"]
    assert sorted(layers) == ["leading", "moe", "swa"]
    assert sorted(layers["leading"]) == ["attn", "dense"]
    assert layers["leading"]["attn"]["wq"].shape == (1, 1, 32, 32)
    assert layers["leading"]["dense"]["w_gate"].shape == (1, 1, 32, 40)
    assert layers["swa"]["wq"].shape == (1, 2, 1, 32, 48)       # 2 periods
    assert layers["swa"]["w_head_gate"].shape == (1, 2, 1, 32, 6)
    assert layers["moe"]["shared_gate"].shape == (1, 2, 1, 32, 24)
    assert "router_bias" not in layers["moe"]       # a softmax router
    assert tfm.pattern_counts(cfg) == {"moe": 1, "swa": 1}
    assert tfm.pattern_counts(cfg, leading=True) == {"attn": 1, "dense": 1}
    specs = tfm.param_specs(cfg, par)
    assert jax.tree_util.tree_structure(specs) == \
        jax.tree_util.tree_structure(params)
    # The model is the blocks applied in order, by hand.
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])
    tokens, labels = tfm.synthetic_batch(jax.random.PRNGKey(3), cfg, 2)
    loss = tfm.make_loss_fn(cfg, par, mesh)(params, tokens, labels)
    x = params["embed"][tokens]
    take = lambda tree, *ix: {k: v[ix] for k, v in tree.items()}  # noqa: E731
    x = x + tfm._gqa_mixer(cfg, take(layers["leading"]["attn"], 0, 0), x)
    x = x + tfm._dense_mixer(cfg, take(layers["leading"]["dense"], 0, 0), x)
    for p in range(2):
        x = x + tfm._gqa_mixer(cfg, take(layers["swa"], 0, p, 0), x, "swa")
        x = x + tfm._expert_mixer(cfg, take(layers["moe"], 0, p, 0), x)[0]
    logits = tfm._rmsnorm(x, params["final_norm"]) @ params["lm_head"].T
    ll = jnp.take_along_axis(jax.nn.log_softmax(logits), labels[..., None],
                             -1)
    assert float(loss) == pytest.approx(float(-ll.mean()), rel=1e-5)


def test_the_step_carries_the_new_scopes_and_the_routing_counter():
    cfg = WHOLE._replace(n_experts_held=4)
    par = tfm.ParallelConfig()
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, par)
    tokens, labels = tfm.synthetic_batch(jax.random.PRNGKey(3), cfg, 2)
    hlo = jax.jit(jax.grad(tfm.make_loss_fn(cfg, par, mesh))).lower(
        params, tokens, labels).as_text(debug_info=True)
    # (``ATTN_PART_SCOPES`` also names the per-head QK-norm, which this
    # model does not have.)
    assert set(profiler.ATTN_PART_SCOPES) >= {"attn_rope", "attn_gate"}
    for name in ("attn_rope", "attn_gate", profiler.DENSE_MLP_SCOPE,
                 "moe_shared", "moe_route"):
        assert f"hvd_{name}" in hlo, name
    routing = tfm.make_routing_fn(cfg, par, mesh)(params, tokens, labels)
    assert routing["assignments"].shape == (1, 1, 16)
    assert float(routing["assignments"].sum()) == 2 * 48 * 3
    assert routing["held_rows"].shape == (1, 1)
    assert float(routing["dropped"]) == 0.0


def test_flops_count_the_new_letters():
    cfg = WHOLE._replace(n_layers=6, n_experts_held=4)
    d, s, hd = 32, 48, 8
    attn = 2 * d * hd * (2 * 4 + 2 * 2) + 2 * d * 4 + 4 * (s / 2) * 4 * hd
    pairs = 8 * s - 8 * 7 / 2                   # the band of a sequence
    swa = 2 * d * hd * (2 * 6 + 2 * 2) + 2 * d * 6 + 4 * (pairs / s) * 6 * hd
    dense = 6 * d * 40
    moe = 2 * d * 16 + 6 * d * 24 + 3 * 4 / 16 * 6 * d * 24
    assert tfm.BLOCKS["*"].flops(cfg) == pytest.approx(attn)
    assert tfm.BLOCKS["W"].flops(cfg) == pytest.approx(swa)
    assert tfm.BLOCKS["D"].flops(cfg) == dense
    assert tfm.BLOCKS["E"].flops(cfg) == pytest.approx(moe)
    assert tfm.train_flops_per_seq(cfg) == pytest.approx(
        3 * s * (attn + dense + 2 * (swa + moe) + 2 * d * 128))


def test_what_a_pattern_refuses_and_what_it_no_longer_does():
    par = tfm.ParallelConfig()
    ok = WHOLE._replace(n_experts_held=4)
    tfm._check_layout(ok, par)      # rotary positions in a pattern: taken
    for bad, match in [
            (ok._replace(qk_norm=True), "no QK-norm"),
            (ok._replace(attn_window=None), "attn_window"),
            (ok._replace(dense_ff=0), "dense_ff"),
            (ok._replace(leading_pattern="ED"), "cannot lead"),
            (ok._replace(n_layers=5), "leading blocks"),
            (ok._replace(window_heads=5), "window_heads"),
            (ok._replace(rope_fraction=0.3), "rope_fraction"),
            (ok._replace(leading_pattern="*Q"), "letters")]:
        with pytest.raises((ValueError, NotImplementedError), match=match):
            tfm._check_layout(bad, par)
    with pytest.raises(ValueError, match="set layer_pattern"):
        tfm._check_layout(tfm.TransformerConfig(attn_gate=True), par)
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        tfm._check_servable(ok)


# -- what the defaults leave as it was -------------------------------------------------

def test_the_new_fields_default_to_the_block_as_it_was():
    cfg = tfm.TransformerConfig()
    assert (cfg.leading_pattern, cfg.attn_window, cfg.window_heads,
            cfg.window_rope_theta, cfg.rope_fraction, cfg.rope_yarn,
            cfg.attn_gate, cfg.dense_ff) == ("", None, None, None, 1.0, None,
                                             False, 0)


# Digests of each accepted cell's parameter tree (path, shape and type of
# every leaf) and of the flagship's differentiated loss as a jaxpr (every
# equation's primitive and result types, nested jaxprs included), taken on
# the commit before the fields were added (22f73fb); the jaxpr's at PR 51,
# which meant to change it (the fused q/k/v projection as three products).
TREES = {"flagship-s8192-train-1chip": "61a99fa2375f126d",
         "flagship-s8192-train-dp2mp2": "61a99fa2375f126d",
         "bert-base-s512-train-1chip": "7d65085dbd4da6b6",
         "olmoe-1b-7b-s4096-train-1chip": "547cc4186ae333b0",
         "nemotron-3-super-s8192-train-1chip": "70a0303ed399158c"}


@pytest.mark.parametrize("cell", sorted(TREES))
def test_an_accepted_cells_parameter_tree_is_what_it_was(cell):
    c = loader.load_cell(cell)
    fam = loader.load_code("families", c["config"]["family"]).Family(
        c["config"], c["traffic"]["mesh"])
    init = fam.init_params
    if isinstance(fam.cfg, tfm.TransformerConfig):     # not the balancer
        init = lambda k: tfm.init_params(k, fam.cfg, fam.par)  # noqa: E731
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    text = ";".join(f"{jax.tree_util.keystr(k)}:{v.shape}:{v.dtype}"
                    for k, v in jax.tree_util.tree_leaves_with_path(shapes))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == TREES[cell]


def test_the_flagships_jaxpr_and_a_patterns_seeded_values_are_what_they_were():
    cfg = tfm.TransformerConfig(vocab_size=128, d_model=32, n_heads=4,
                                d_ff=64, n_layers=2, seq_len=32)
    par = tfm.ParallelConfig()
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg, par),
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        tfm.make_loss_fn(cfg, par, mesh)))(params, tok, tok)
    seen = []                 # every equation's primitive and result types

    def walk(jp):
        for eqn in jp.eqns:
            seen.append(f"{eqn.primitive.name}:"
                        f"{[str(v.aval) for v in eqn.outvars]}")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert len(seen) == 510
    assert hashlib.sha256("\n".join(seen).encode()).hexdigest()[:16] == \
        "9e4947de6519a3dc"
    # The draws of a patterned model's blocks (the first 24 keys of the
    # stream) are the ones they were.
    pattern = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, d_ff=16, n_layers=3,
        seq_len=32, n_experts=8, top_k=2, dropless=True, tied_head=False,
        layer_pattern="EM*", learned_positions=False, n_kv_heads=2,
        attn_head_dim=8, ssm_heads=4, ssm_head_dim=8, ssm_state=8,
        ssm_chunk=8, router_scoring="sigmoid", n_experts_held=4,
        moe_latent=16, shared_expert_ff=24, expert_activation="relu2")
    p = tfm.init_params(jax.random.PRNGKey(7), pattern, par)
    assert float(sum(jnp.sum(jnp.abs(a)) for a in
                     jax.tree_util.tree_leaves(p))) == pytest.approx(
                         471.2646484375, rel=1e-6)
