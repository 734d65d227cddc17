"""What LFM2-24B-A2B added to ``models/transformer.py`` (the "C" block, a
gated short convolution; ``conv_taps``; the renormalised
router's ``router_renorm_eps``), ``ops/ssd.py`` (the convolution of a
product of two factors), ``parallel/moe.py`` (``_weigh``'s epsilon) and
``ops/flash_attention.py`` (dQ's transposes in pieces along a long
sequence), piece by piece against formulas written out here and the
benchmark's plain reference; the shares of a deployment against the whole
layer, the built tree against the published count, and the touched paths
against the parent's formulas, jaxpr for jaxpr."""

from __future__ import annotations

import json
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
from horovod_tpu.ops import flash_attention as fa  # noqa: E402
from horovod_tpu.ops import ssd                    # noqa: E402
from horovod_tpu.parallel import moe               # noqa: E402
from horovod_tpu.parallel.mesh import create_mesh  # noqa: E402
from horovod_tpu.utils import profiler             # noqa: E402

REF = loader.load_code("reference", "lfm2")
CELL = "lfm2-24b-a2b-s32768-train-1chip"
# The cell's ten blocks at a small size: 4 query heads on 2 kv heads of 8,
# 16 experts of which every one is held (WHOLE) or 2 (SHARE: one rank of 8).
WHOLE = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=12, n_layers=10, seq_len=16,
    n_experts=16, top_k=4, dtype=jnp.float32, dropless=True, norm_eps=1e-5,
    tied_head=True, gated_experts=True, leading_pattern="CD",
    layer_pattern="*ECECECE", learned_positions=False, n_kv_heads=2,
    attn_head_dim=8, rope_theta=1e6, head_qk_norm=True,
    router_scoring="sigmoid", router_renormalise=True,
    router_renorm_eps=1e-6, dense_ff=40, conv_taps=3,
    expert_buffer_factor=64.0)
SHARE = WHOLE._replace(n_experts_held=2)
PAR = tfm.ParallelConfig()
ARCH = dict(norm_eps=WHOLE.norm_eps, n_kv_heads=2, head_dim=8,
            rope_theta=1e6, top_k=4, renorm_eps=1e-6, router_scale=1.0)


def one_device_mesh():
    return create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])


def seeded(cfg, key=0):
    """Parameters with norms off 1 and a correction bias off 0, so that a
    scale or a bias that is dropped or misplaced shows."""
    params = tfm.init_params(jax.random.PRNGKey(key), cfg, PAR)
    keys = iter(jax.random.split(jax.random.PRNGKey(key + 1), 128))

    def moved(path, a):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            return 0.1 * jax.random.normal(next(keys), a.shape)
        if "norm" in name or "'ln'" in name:
            return a + 0.3 * jax.random.normal(next(keys), a.shape)
        return a if "conv_w" in name else a * 8.0

    return jax.tree_util.tree_map_with_path(moved, params)


def to_reference(cfg, params):
    """The system's stacked tree as the reference's list of layers (the
    benchmark family's mapping, on a bare configuration)."""
    fam = object.__new__(loader.load_code("families", "lfm2").Family)
    fam.tfm = tfm
    fam.c = {"leading_pattern": cfg.leading_pattern,
             "layer_pattern": cfg.layer_pattern, "n_layers": cfg.n_layers}
    return fam.to_reference(params)


# -- (a) the convolution block ----------------------------------------------------

def plain_conv_block(lp, h):
    """The block's equations written out with loops: nothing of the program
    or of the reference."""
    s, d = h.shape
    proj = np.asarray(h, np.float64) @ np.asarray(lp["w_in"], np.float64)
    b, c, u = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    g = b * u
    w = np.asarray(lp["conv_w"], np.float64)
    conv = np.zeros_like(g)
    for t in range(s):
        for j in range(w.shape[1]):
            if t - (w.shape[1] - 1) + j >= 0:
                conv[t] += w[:, j] * g[t - (w.shape[1] - 1) + j]
    return (c * conv) @ np.asarray(lp["w_out"], np.float64)


def test_the_conv_mixer_is_the_equations_and_the_reference():
    params = seeded(WHOLE, key=3)
    lp = {k: v[0, 0, 1] for k, v in params["layers"]["conv"].items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 32))
    got = tfm._conv_mixer(WHOLE, lp, x)
    for i in range(2):
        h = REF.rmsnorm(x[i], lp["ln"], WHOLE.norm_eps)
        np.testing.assert_allclose(got[i], plain_conv_block(lp, h),
                                   atol=2e-4, rtol=1e-4)
        np.testing.assert_allclose(
            got[i], REF.conv_block(h, {"w_in": lp["w_in"],
                                       "conv": lp["conv_w"],
                                       "w_out": lp["w_out"]}),
            atol=2e-5, rtol=1e-5)


def test_the_convolution_is_causal_bit_for_bit():
    """Changing token t + 1 leaves every position up to t bit-equal, in the
    block alone and through the whole model's logits-free stack."""
    params = seeded(WHOLE._replace(dtype=jnp.bfloat16), key=5)
    lp = {k: v[0, 0, 0] for k, v in params["layers"]["conv"].items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 16, 32), jnp.bfloat16)
    cfg = WHOLE._replace(dtype=jnp.bfloat16)
    base = tfm._conv_mixer(cfg, lp, x)
    for t in (0, 7, 14):
        other = x.at[:, t + 1].set(-x[:, t + 1] + 1.0)
        moved = tfm._conv_mixer(cfg, lp, other)
        assert (np.asarray(moved[:, :t + 1]) == np.asarray(
            base[:, :t + 1])).all(), t
        assert (np.asarray(moved[:, t + 1]) != np.asarray(
            base[:, t + 1])).any(), t
        # ... and reaches no further than its taps.
        assert (np.asarray(moved[:, t + 1 + cfg.conv_taps:]) == np.asarray(
            base[:, t + 1 + cfg.conv_taps:])).all(), t


def test_the_convolution_has_no_bias_and_a_config_that_asks_one_is_refused():
    """The published ``conv_bias`` is false and no configuration has it true:
    the "C" block has no such leaf and the family says so in words."""
    params = tfm.init_params(jax.random.PRNGKey(0), WHOLE, PAR)
    assert sorted(params["layers"]["conv"]) == ["conv_w", "ln", "w_in",
                                                "w_out"]
    config = loader.load_cell(CELL)["config"]
    assert config["conv_bias"] is False
    with pytest.raises(loader.BenchmarkError, match="conv_bias is true"):
        loader.load_code("families", "lfm2").Family(
            {**config, "conv_bias": True}, dict(dp=1, pp=1, mp=1))


def test_the_gated_convolution_is_the_convolution_of_the_product():
    """``gated_causal_conv1d(x, gate, w)`` against ``causal_conv1d`` of the
    product made first under a bias of 0 (fp32 factors, so that the two
    round alike); ``causal_conv1d`` itself is the parent's."""
    x, gate = (jax.random.normal(jax.random.PRNGKey(i), (2, 12, 6))
               for i in (0, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    np.testing.assert_allclose(ssd.gated_causal_conv1d(x, gate, w),
                               ssd.causal_conv1d(x * gate, w, jnp.zeros(6)),
                               atol=1e-6, rtol=1e-6)
    out = ssd.gated_causal_conv1d(x.astype(jnp.bfloat16),
                                  gate.astype(jnp.bfloat16), w)
    assert out.dtype == jnp.float32          # the caller rounds, once
    # The first position reads itself alone: the last tap.
    np.testing.assert_allclose(
        ssd.gated_causal_conv1d(x, gate, w)[:, 0],
        x[:, 0] * gate[:, 0] * w[:, -1], atol=1e-6, rtol=1e-6)


# -- (b) the router -----------------------------------------------------------------

def test_the_router_chooses_by_score_plus_bias_and_weighs_by_score():
    """With the bias and without it: the choice moves, the weights are the
    chosen scores over their sum + 1e-6 either way."""
    params = seeded(WHOLE, key=7)
    lp = {k: v[0, 0, 0] for k, v in params["layers"]["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, 32))
    tok = REF.rmsnorm(x, lp["ln"], WHOLE.norm_eps).reshape(-1, 32)
    s = np.asarray(jax.nn.sigmoid(tok @ lp["gate"]), np.float64)
    for bias in (np.asarray(lp["router_bias"], np.float64), np.zeros(16)):
        chosen = np.argsort(-(s + bias), axis=-1)[:, :4]
        w = np.zeros_like(s)
        np.put_along_axis(w, chosen, np.take_along_axis(s, chosen, -1), -1)
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
        want = sum(
            w[:, e:e + 1] * np.asarray(REF.swiglu(
                tok, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e]))
            for e in range(16))
        got, stats = tfm._expert_mixer(
            WHOLE, {**lp, "router_bias": jnp.asarray(bias, jnp.float32)}, x)
        np.testing.assert_allclose(got.reshape(-1, 32), want, atol=2e-4,
                                   rtol=1e-4)
        assert float(stats.counts.sum()) == 32 * 4
    with_bias = np.argsort(-(s + np.asarray(lp["router_bias"])), -1)[:, :4]
    without = np.argsort(-s, -1)[:, :4]
    assert (np.sort(with_bias) != np.sort(without)).any()


def test_weigh_takes_the_epsilon_and_at_zero_is_the_parents_division():
    p = jnp.asarray([[0.2, 0.0, 0.6, 0.0], [0.0, 0.5, 0.0, 0.5]])
    plain = moe.Router("sigmoid", True, 1.0)
    assert plain.renorm_eps == 0.0
    assert str(jax.make_jaxpr(lambda p: moe._weigh(p, plain))(p)) == str(
        jax.make_jaxpr(lambda p: p / jnp.sum(p, axis=-1, keepdims=True))(p))
    got = moe._weigh(p, moe.Router("sigmoid", True, 2.0, 1e-6))
    np.testing.assert_allclose(
        got, 2.0 * p / (p.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert float(got[0].sum()) < 2.0


# -- (c) the model is the reference ---------------------------------------------------

def test_the_loss_is_the_references_and_so_are_the_gradients():
    mesh = one_device_mesh()
    params = seeded(SHARE, key=9)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(3), SHARE, 2)
    loss, grads = jax.jit(jax.value_and_grad(
        tfm.make_loss_fn(SHARE, PAR, mesh)))(params, *batch)
    want, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: REF.loss(p, *batch, **ARCH)))(to_reference(SHARE, params))
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    got = to_reference(SHARE, grads)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2))),
        got, ref_grads)
    # 5 layers: 4 conv operators of 4 leaves, 1 attention of 7, 1 dense MLP
    # of 4, 4 expert MLPs of 5; the tied table, the final norm.
    assert len(jax.tree_util.tree_leaves(errs)) == 16 + 7 + 4 + 20 + 2
    assert max(jax.tree_util.tree_leaves(errs)) < 1e-4, errs


# -- (d) the share sums to the layer ---------------------------------------------------

def test_the_eight_expert_shares_add_up_to_the_whole_expert_layer():
    """8 ranks of 2 experts: rank r numbers its own experts first (its
    router columns, bias entries and weights rolled to the front); the
    ranks' parts add up to the uncut layer of the reference.  What every
    rank computes alike (the block's norm, the router) is inside each part
    and is not added twice: the parts are the experts' weighted outputs."""
    params = seeded(WHOLE, key=6)
    lp = {k: v[0, 0, 0] for k, v in params["layers"]["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32))
    tok = REF.rmsnorm(x, lp["ln"], WHOLE.norm_eps).reshape(-1, 32)
    want = REF.ffn_block(
        tok, {"router": jnp.concatenate([lp["gate"],
                                         lp["router_bias"][None]], 0),
              "w1": lp["w_gate"], "w3": lp["w_up"], "w2": lp["w_down"]},
        top_k=4, renorm_eps=1e-6, router_scale=1.0)
    total = 0.0
    for r in range(8):
        mine = slice(2 * r, 2 * r + 2)
        share = {**lp, "gate": jnp.roll(lp["gate"], -2 * r, axis=1),
                 "router_bias": jnp.roll(lp["router_bias"], -2 * r),
                 **{k: lp[k][mine] for k in ("w_gate", "w_up", "w_down")}}
        y, stats = tfm._expert_mixer(SHARE, share, x)
        assert float(stats.dropped) == 0.0
        total = total + y.reshape(-1, 32)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)
    # A rank alone is not the layer: most of the routed part is elsewhere.
    assert np.abs(y.reshape(-1, 32) - want).max() > 1e-3


# -- (e) the published count -------------------------------------------------------------

def test_parameter_count_is_exact():
    cell = loader.load_cell(CELL)
    fam = loader.load_code("families", "lfm2").Family(
        cell["config"], cell["traffic"]["mesh"])
    shapes = jax.eval_shape(
        lambda k: tfm.init_params(k, fam.cfg, fam.par), jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == 469_285_248 == cell["config"]["parameters"]

    def block(leaves):
        return sum(int(np.prod(a.shape[-2:] if a.ndim > 4 else a.shape[3:]))
                   for a in jax.tree_util.tree_leaves(leaves))

    layers = shapes["layers"]
    per = {kind: sum(int(np.prod(a.shape[3:])) for a in
                     jax.tree_util.tree_leaves(layers[kind]))
           for kind in ("conv", "attn", "moe")}
    lead = {kind: sum(int(np.prod(a.shape[2:])) for a in
                      jax.tree_util.tree_leaves(layers["leading"][kind]))
            for kind in ("conv", "dense")}
    # The block's norm counted with it: 2048 more than ISSUE 41's rows.
    assert per == {"conv": 16_783_360 + 2048, "attn": 10_485_888 + 2048,
                   "moe": 75_628_608 + 2048}
    assert lead == {"conv": 16_783_360 + 2048, "dense": 72_351_744 + 2048}
    assert layers["conv"]["w_in"].shape == (1, 1, 3, 2048, 6144)
    assert layers["conv"]["conv_w"].shape == (1, 1, 3, 2048, 3)
    assert layers["moe"]["w_up"].shape == (1, 1, 4, 8, 2048, 1536)
    assert layers["moe"]["gate"].shape == (1, 1, 4, 2048, 64)
    assert shapes["embed"].shape == (8192, 2048) and "lm_head" not in shapes


# -- (f) the layout's rules, the step, its names and its FLOPs --------------------------

def test_what_a_conv_block_asks_for_and_refuses():
    with pytest.raises(ValueError, match="conv_taps goes with it"):
        tfm.init_params(jax.random.PRNGKey(0),
                        WHOLE._replace(conv_taps=0), PAR)
    with pytest.raises(ValueError, match="head_qk_norm and conv_taps are a "
                                         "patterned model's"):
        tfm.init_params(jax.random.PRNGKey(0), tfm.TransformerConfig(
            conv_taps=3), PAR)
    with pytest.raises(NotImplementedError, match='a convolution \\("C"\\)'):
        tfm.init_params(jax.random.PRNGKey(0), WHOLE._replace(
            diffusion_block=4, router_scoring="softmax"), PAR)
    # M0, not M7, is the item that shards a patterned model's mixers.
    with pytest.raises(NotImplementedError, match="ROADMAP M0"):
        tfm.param_specs(WHOLE, tfm.ParallelConfig(mp=2))
    assert tfm.BLOCK_KINDS["C"] == ("conv", "conv")
    assert profiler.CONV_SCOPES == ("conv", "conv_gate")
    assert '``C``' in tfm.__doc__


def test_the_step_trains_routes_and_names_its_parts():
    mesh = one_device_mesh()
    params = seeded(SHARE, key=8)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(3), SHARE, 2)
    hlo = jax.jit(jax.grad(tfm.make_loss_fn(SHARE, PAR, mesh))).lower(
        params, *batch).as_text(debug_info=True)
    for name in ("conv", "conv_gate", "attn_qknorm", "attn_rope",
                 "mlp_dense", "moe_route", "head"):
        assert f"hvd_{name}" in hlo, name
    # The gate path sits inside the block, forward and backward.
    assert "hvd_conv/hvd_conv_gate" in hlo
    assert "transpose(jvp(hvd_conv))/hvd_conv_gate" in hlo \
        or "transpose(jvp" in hlo
    routing = tfm.make_routing_fn(SHARE, PAR, mesh)(params, *batch)
    assert routing["assignments"].shape == (1, 4, 16)
    assert float(routing["assignments"][0, 0].sum()) == 2 * 16 * 4
    assert float(routing["dropped"]) == 0.0
    assert routing["held_rows"].shape == (1, 4)
    balanced = tfm.make_router_balancer(SHARE, PAR, mesh)(params, *batch)
    assert balanced["layers"]["moe"]["router_bias"].shape == (1, 1, 4, 16)
    opt = optax.adamw(1e-2)
    step, shard = tfm.make_train_step(SHARE, PAR, mesh, opt)
    p = shard(params)
    state = opt.init(p)
    losses = []
    for _ in range(4):
        p, state, loss = step(p, state, *batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_flops_count_the_two_projections_and_the_taps():
    d = WHOLE.d_model
    assert tfm.BLOCKS["C"].flops(WHOLE) == 8 * d * d + 2 * 3 * d
    blocks = "CD" + "*ECECECE"
    assert tfm.train_flops_per_seq(WHOLE) == 3.0 * WHOLE.seq_len * (
        2.0 * d * WHOLE.vocab_size
        + sum(tfm.BLOCKS[c].flops(WHOLE) for c in blocks))


# -- (g) dQ's transposes in pieces ---------------------------------------------------------

@pytest.mark.parametrize("sq, d, itemsize, want", [
    (8192, 64, 2, 1),      # the flagship's call
    (8192, 128, 2, 1),     # Laguna's, OLMoE's, Nemotron's, SDAR's: 8 MiB
    (4096, 128, 2, 1), (512, 64, 2, 1),
    (32768, 64, 2, 4),     # LFM2's: whole it would ask for 24 MiB
    (32768, 128, 2, 4), (16384, 64, 2, 2)])
def test_dq_pieces_are_one_wherever_a_head_fitted(sq, d, itemsize, want):
    assert fa._dq_pieces(sq // 1024 if sq >= 1024 else 1,
                         min(sq, 1024), d, itemsize) == want


def test_the_flash_backward_in_pieces_is_the_whole_ones(monkeypatch):
    """The same call with dQ's transposes cut along the sequence (what
    32,768 queries take) and whole: bit-equal gradients."""
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(kk, (1, 512, 2, 64), jnp.bfloat16)
                  for kk in key)

    def grads():
        f = lambda q, k, v: fa.flash_attention(          # noqa: E731
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
        return jax.vjp(f, q, k, v)[1](g)

    whole = grads()
    monkeypatch.setattr(fa, "_dq_pieces", lambda nq, *a: 2)
    pieces = grads()
    for a, b in zip(whole, pieces):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# -- (h) the accepted cells' programs are the parent's ----------------------------

# ``tests/step_digests.json``: sha256 of two cells' train steps as JAX traces
# them at their published sizes, recorded by ``benchmark/tools/step_jaxpr.py
# --record`` from the checkout of the commit before the "C" block and equal
# on the tree with it (PR 41): the two cells that share the most touched
# code (the pattern stage function, the leading blocks, the held experts and
# ``_weigh``; the sigmoid router).  A later change that means to alter one
# of these programs records the file again with that one command; one that
# does not has altered it by accident.  Under another JAX than the record's
# the texts are not comparable: skipped until it is recorded again.
RECORD = Path(__file__).resolve().parent / "step_digests.json"
PARENTS_STEPS = json.loads(RECORD.read_text())


@pytest.fixture(scope="module")
def step_digests():
    import os
    import subprocess
    if PARENTS_STEPS["jax"] != jax.__version__:
        pytest.skip(f"{RECORD.name} was recorded under jax "
                    f"{PARENTS_STEPS['jax']}: record it again "
                    "(benchmark/tools/step_jaxpr.py --record)")
    root = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, str(root / "benchmark/tools/step_jaxpr.py"),
         "--cells", ",".join(PARENTS_STEPS["steps"])],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=root,
        capture_output=True, text=True, timeout=170, check=True).stdout
    return {r["cell"]: r["sha256"] for r in map(json.loads,
                                                out.splitlines())}


@pytest.mark.parametrize("cell", sorted(PARENTS_STEPS["steps"]))
def test_a_cell_without_the_new_letter_traces_to_the_parents_step(
        step_digests, cell):
    assert step_digests[cell] == PARENTS_STEPS["steps"][cell], (
        f"{cell}'s train step is not the recorded one: if that is meant, "
        "record it again (benchmark/tools/step_jaxpr.py --cells ... "
        f"--record tests/{RECORD.name})")
