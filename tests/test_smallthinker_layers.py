"""What SmallThinker-21BA3B added to ``models/transformer.py``: a block that
reads the stream as the block before it received it (``BlockKind.before``,
``router_before_attention``: an "E" block's router on its layer's input,
ahead of the attention), ReLU-gated experts, and a pattern whose "*" blocks
carry no position at all beside "W" blocks that rotate — piece by piece
against equations written out here and the benchmark's plain reference; the
controls the comparison must see; the shares of a deployment against the
whole layer; the built tree against the published count."""

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

REF = loader.load_code("reference", "smallthinker")
FAMILY = loader.load_code("families", "smallthinker")
CONTROLS = loader.load_code("tools", "smallthinker_controls")
CELL = "smallthinker-21b-a3b-s16384-train-1chip"
# The cell's eight blocks at a small size: 4 query heads on 2 kv heads of 8,
# a window of 5 under 16 positions, 16 experts of which every one is held
# (WHOLE) or 4 (SHARE: one rank of 4), 3 a token.
WHOLE = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=12, n_layers=8, seq_len=16,
    n_experts=16, top_k=3, dtype=jnp.float32, dropless=True, norm_eps=1e-6,
    tied_head=False, gated_experts=True, expert_activation="relu",
    layer_pattern="*EWEWEWE", learned_positions=False, rope_theta=None,
    n_kv_heads=2, attn_head_dim=8, attn_window=5, window_rope_theta=1.5e6,
    router_renormalise=True, router_before_attention=True,
    expert_buffer_factor=64.0)
SHARE = WHOLE._replace(n_experts_held=4)
PAR = tfm.ParallelConfig()
ARCH = dict(layer_types=("full", "sliding", "sliding", "sliding"),
            norm_eps=WHOLE.norm_eps, n_kv_heads=2, head_dim=8, window=5,
            rope_theta=1.5e6, top_k=3)


def mesh_of(dp: int = 1):
    return create_mesh({"dp": dp, "pp": 1, "mp": 1},
                       devices=jax.devices()[:dp])


def seeded(cfg, key=0):
    """Parameters with norms off 1 and weights large enough that no two
    router logits tie in fp32, so that a scale that is dropped shows and no
    choice hangs on a last bit."""
    params = tfm.init_params(jax.random.PRNGKey(key), cfg, PAR)
    keys = iter(jax.random.split(jax.random.PRNGKey(key + 1), 128))

    def moved(path, a):
        name = jax.tree_util.keystr(path)
        if "norm" in name or "'ln'" in name:
            return a + 0.3 * jax.random.normal(next(keys), a.shape)
        return a * 8.0

    return jax.tree_util.tree_map_with_path(moved, params)


def to_reference(cfg, params):
    """The system's stacked tree as the reference's list of layers (the
    benchmark family's mapping, on a bare configuration)."""
    fam = object.__new__(FAMILY.Family)
    fam.tfm = tfm
    fam.c = {"layer_pattern": cfg.layer_pattern, "n_layers": cfg.n_layers}
    return fam.to_reference(params)


def rel_l2(got, want):
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2))),
        got, want)
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(errs)}


def system(cfg, params, batch, dp=1):
    return jax.jit(jax.value_and_grad(
        tfm.make_loss_fn(cfg, PAR._replace(dp=dp), mesh_of(dp))))(
            params, *batch)


def reference(cfg, params, batch, **changed):
    return jax.jit(jax.value_and_grad(
        lambda p: REF.loss(p, *batch, **{**ARCH, **changed})))(
            to_reference(cfg, params))


# -- (a) the layer is its equations --------------------------------------------------

def plain_layer(ap, mp, x, *, sliding: bool, held: int):
    """One layer on one sequence with loops, in float64: nothing of the
    program or of the reference.  x: (S, d)."""
    f = lambda a: np.asarray(a, np.float64)                   # noqa: E731
    x = f(x)
    s, hd, hkv, window, top_k = x.shape[0], 8, 2, 5, 3

    def rms(t, g):
        return t / np.sqrt((t * t).mean(-1, keepdims=True) + 1e-6) * f(g)

    r = x @ f(mp["gate"])                 # the layer's INPUT, no norm
    h = rms(x, ap["ln"])
    q = (h @ f(ap["wq"])).reshape(s, -1, hd)
    k = (h @ f(ap["wk"])).reshape(s, hkv, hd)
    v = (h @ f(ap["wv"])).reshape(s, hkv, hd)
    if sliding:
        half = hd // 2
        inv = 1.5e6 ** (-np.arange(half) / half)

        def rotated(t):
            out = np.empty_like(t)
            for pos in range(s):
                c, sn = np.cos(pos * inv), np.sin(pos * inv)
                t1, t2 = t[pos, :, :half], t[pos, :, half:]
                out[pos] = np.concatenate([t1 * c - t2 * sn,
                                           t2 * c + t1 * sn], -1)
            return out
        q, k = rotated(q), rotated(k)
    a = np.zeros_like(q)
    for t in range(s):
        first = max(0, t - window + 1) if sliding else 0
        for i in range(q.shape[1]):
            kv = i // (q.shape[1] // hkv)
            sc = np.array([q[t, i] @ k[j, kv] for j in range(first, t + 1)])
            p = np.exp((sc - sc.max()) / np.sqrt(hd))
            p /= p.sum()
            a[t, i] = sum(p[n] * v[first + n, kv] for n in range(len(p)))
    y = x + a.reshape(s, -1) @ f(ap["wo"])
    m = rms(y, mp["ln"])
    out = y.copy()
    for t in range(s):
        chosen = np.argsort(-r[t])[:top_k]
        w = np.exp(r[t, chosen] - r[t, chosen].max())
        w /= w.sum()
        for w_e, e in zip(w, chosen):
            if e < held:
                gate = np.maximum(m[t] @ f(mp["w_gate"][e]), 0.0)
                out[t] += w_e * (gate * (m[t] @ f(mp["w_up"][e]))) @ f(
                    mp["w_down"][e])
    return out


@pytest.mark.parametrize("letter, kind", [("*", "attn"), ("W", "swa")])
def test_a_layer_is_the_equations_written_out(letter, kind):
    """A full layer without positions and a windowed one that rotates, each
    with its experts routed on the layer's input, through the stage
    function itself."""
    cfg = SHARE._replace(n_layers=2, layer_pattern=letter + "E")
    params = seeded(cfg, key=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 32))
    stage = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    got, stats = tfm._make_pattern_stage_fn(cfg)(stage, x)
    ap = {k: v[0, 0] for k, v in stage[kind].items()}
    mp = {k: v[0, 0] for k, v in stage["moe"].items()}
    for i in range(2):
        np.testing.assert_allclose(
            got[i], plain_layer(ap, mp, x[i], sliding=letter == "W", held=4),
            atol=2e-4, rtol=2e-4)
    assert float(stats.counts.sum()) == 2 * 16 * 3
    assert float(stats.dropped.sum()) == 0.0


def test_a_full_block_knows_no_position_and_a_windowed_one_does():
    """With no position encoding at all, causal attention's output at the
    last position does not change when the positions before it change
    places; under rotation it does."""
    params = seeded(WHOLE, key=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 16, 32))
    order = np.r_[np.random.default_rng(0).permutation(15), 15]
    for kind, moves in (("attn", False), ("swa", True)):
        lp = {k: v[0, 0, 0] for k, v in params["layers"][kind].items()}
        cfg = WHOLE._replace(attn_window=16)    # every key in the window
        last = tfm._gqa_mixer(cfg, lp, x, kind=kind)[0, -1]
        shuffled = tfm._gqa_mixer(cfg, lp, x[:, order], kind=kind)[0, -1]
        same = np.allclose(last, shuffled, atol=1e-4, rtol=1e-4)
        assert same != moves, kind


def test_the_rotation_sits_in_the_windowed_blocks_and_not_in_the_full_one():
    params = seeded(WHOLE, key=5)
    x = jnp.ones((1, 16, 32))
    texts = {}
    for kind in ("attn", "swa"):
        lp = {k: v[0, 0, 0] for k, v in params["layers"][kind].items()}
        texts[kind] = jax.jit(
            lambda lp, x, kind=kind: tfm._gqa_mixer(WHOLE, lp, x, kind=kind)
        ).lower(lp, x).as_text(debug_info=True)
    assert "hvd_attn_rope" in texts["swa"]
    assert "hvd_attn_rope" not in texts["attn"]
    assert "cosine" in texts["swa"] and "cosine" not in texts["attn"]
    assert "pos" not in tfm.init_params(jax.random.PRNGKey(0), WHOLE, PAR)
    whole = jax.jit(jax.grad(tfm.make_loss_fn(SHARE, PAR, mesh_of()))).lower(
        seeded(SHARE), *tfm.synthetic_batch(jax.random.PRNGKey(3), SHARE, 1)
    ).as_text(debug_info=True)
    for name in ("attn_rope", "moe_route", "moe_dispatch", "moe_experts",
                 "head"):
        assert f"hvd_{name}" in whole, name
    for name in ("attn_gate", "attn_qknorm", "mlp_dense", "moe_shared"):
        assert f"hvd_{name}" not in whole, name


# -- (b) the router reads the layer's input --------------------------------------------

def test_the_router_reads_the_layers_input_ahead_of_the_attention():
    """One layer: with the field set, another ``wo`` in the attention block
    leaves the experts' assignments as they were, to the pair; unset (the
    router on its own block's normed input, past the attention) it moves
    them."""
    cfg = SHARE._replace(n_layers=2, layer_pattern="*E")
    params = seeded(cfg, key=7)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(3), cfg, 2)
    attn = params["layers"]["attn"]
    other = {**params, "layers": {**params["layers"], "attn": {
        **attn, "wo": -3.0 * attn["wo"][..., ::-1, :]}}}
    moved = {}
    for ahead in (True, False):
        c = cfg._replace(router_before_attention=ahead)
        routing = tfm.make_routing_fn(c, PAR, mesh_of())
        a, b = (np.asarray(routing(p, *batch)["assignments"])
                for p in (params, other))
        assert a.sum() == b.sum() == 2 * 16 * 3
        moved[ahead] = (a != b).any()
    assert moved == {True: False, False: True}
    # The experts still read the stream after the attention: the loss moves.
    loss = tfm.make_loss_fn(cfg, PAR, mesh_of())
    assert float(loss(params, *batch)) != float(loss(other, *batch))


def test_the_expert_mixer_routes_on_the_second_operand():
    """``_expert_mixer(..., before)``: logits from ``before`` as it is, the
    experts on the normed ``x``; without it both from the normed ``x``."""
    params = seeded(WHOLE, key=8)
    lp = {k: v[0, 0, 0] for k, v in params["layers"]["moe"].items()}
    x, before = (jax.random.normal(jax.random.PRNGKey(i), (2, 16, 32))
                 for i in (1, 2))
    m = REF.rmsnorm(x, lp["ln"], WHOLE.norm_eps).reshape(-1, 32)
    for operand, args in ((before.reshape(-1, 32), (before,)), (m, ())):
        want = REF.experts(m, REF.route(operand @ lp["gate"], 3),
                           lp["w_gate"], lp["w_up"], lp["w_down"])
        got, _ = tfm._expert_mixer(WHOLE, lp, x, *args)
        np.testing.assert_allclose(got.reshape(-1, 32), want, atol=2e-5,
                                   rtol=1e-4)


def test_relu_gates_the_experts():
    assert tfm._ACTIVATIONS["relu"] is jax.nn.relu
    assert tfm._expert_activation(WHOLE) is jax.nn.relu
    u = jnp.linspace(-2.0, 2.0, 9)
    np.testing.assert_array_equal(REF.gate_activation(u), jax.nn.relu(u))


# -- (c) the model is the reference ------------------------------------------------------

@pytest.mark.parametrize("dp", [1, 2])
def test_the_loss_is_the_references_and_so_are_the_gradients(dp):
    params = seeded(SHARE, key=9)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(3), SHARE, 2)
    loss, grads = system(SHARE, params, batch, dp)
    want, ref_grads = reference(SHARE, params, batch)
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    errs = rel_l2(to_reference(SHARE, grads), ref_grads)
    # 4 layers of an attention's 5 leaves and an expert MLP's 5; the
    # embedding, the final norm, the head.
    assert len(errs) == 4 * 10 + 3
    assert max(errs.values()) < 1e-5, errs


FAULTS = {
    "window_one_key_short": "a window off by one key",
    "window_one_key_long": "a window off by one key, the other way",
    "full_layers_rotated": "a rotated full layer",
    "silu_for_relu": "SiLU for ReLU",
    "router_reads_the_normed_stream_after_attention":
        "the router on the post-attention normed stream",
    "matmuls_in_bf16": "bf16 operands in the reference's matmuls",
}


@pytest.fixture(scope="module")
def two_layers():
    """A full layer and a windowed one with their experts: (configuration,
    parameters, batch, the system's loss and gradients)."""
    cfg = SHARE._replace(n_layers=4, layer_pattern="*EWE")
    params = seeded(cfg, key=9)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(3), cfg, 2)
    return cfg, params, batch, system(cfg, params, batch)


@pytest.mark.parametrize("control", sorted(FAULTS))
def test_in_fp32_every_control_fails(two_layers, control):
    """Where nothing rounds (system = reference to 1e-5), a reference with
    one thing wrong is far from the system, by the loss or by a leaf."""
    cfg, params, batch, (loss, grads) = two_layers
    kinds = dict(layer_types=("full", "sliding"))
    with CONTROLS.patched(REF, control):
        want, ref_grads = reference(cfg, params, batch, **kinds)
    errs = rel_l2(to_reference(cfg, grads), ref_grads)
    assert (max(errs.values()) > 1e-3
            or abs(float(loss) - float(want)) > 1e-3), (
        FAULTS[control], float(loss) - float(want), max(errs.values()))


def test_the_controls_tool_leaves_the_reference_as_it_was():
    names = ("attention", "positioned", "gate_activation", "router_operand",
             "matmul")
    before = {k: getattr(REF, k) for k in names}
    for name in CONTROLS.CONTROLS:
        with CONTROLS.patched(REF, name):
            pass
    assert {k: getattr(REF, k) for k in names} == before
    assert set(FAULTS) | {"none", "matmuls_in_e4m3", "matmuls_in_e5m2"} == set(
        CONTROLS.CONTROLS)
    assert CONTROLS.CELL == CELL


def test_the_carried_operand_under_remat_gives_the_plain_gradients():
    """The stream a block hands on to the block after next goes through
    that block's checkpoint as an input: the loss of ``remat=True`` is that
    of ``remat=False`` to the last bit and the gradients to fp32's round-off
    — not to the last bit, as they are without the carried operand (read
    once, PR 46: 3e-7 of a leaf's largest entry with it, 0 without): the
    attention block's input now takes three cotangents (the residual's, the
    mixer's, the router's), and a checkpoint adds the first two inside
    itself where the plain backward adds them in the equations' order."""
    params = seeded(SHARE, key=11)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(5), SHARE, 2)
    (l0, g0), (l1, g1) = (system(SHARE._replace(remat=r), params, batch)
                          for r in (True, False))
    assert float(l0) == float(l1)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g0),
                            jax.tree_util.tree_leaves(g1)):
        assert float(jnp.abs(a - b).max()) <= 2e-6 * float(
            jnp.abs(b).max()), jax.tree_util.keystr(path)
    # The router's operand takes a gradient: the attention block's input
    # gets the router's cotangent besides its own two.
    assert float(jnp.abs(g0["layers"]["moe"]["gate"]).max()) > 0


# -- (d) the shares sum to the layer ---------------------------------------------------

def test_the_four_expert_shares_add_up_to_the_whole_expert_layer():
    """4 ranks of 4 experts (0-3, 4-7, 8-11, 12-15): rank r numbers its own
    experts first (its router columns and weights rolled to the front); the
    ranks' parts add up to the uncut layer of the reference.  What every
    rank computes alike (the attention, the residual, the block's norm, the
    router) is counted once: the parts are the experts' weighted outputs."""
    params = seeded(WHOLE, key=6)
    lp = {k: v[0, 0, 0] for k, v in params["layers"]["moe"].items()}
    y, x = (jax.random.normal(jax.random.PRNGKey(i), (2, 32, 32))
            for i in (2, 12))
    m = REF.rmsnorm(y, lp["ln"], WHOLE.norm_eps).reshape(-1, 32)
    want = REF.experts(m, REF.route(x.reshape(-1, 32) @ lp["gate"], 3),
                       lp["w_gate"], lp["w_up"], lp["w_down"])
    total = 0.0
    for r in range(4):
        mine = slice(4 * r, 4 * r + 4)
        share = {**lp, "gate": jnp.roll(lp["gate"], -4 * r, axis=1),
                 **{k: lp[k][mine] for k in ("w_gate", "w_up", "w_down")}}
        part, stats = tfm._expert_mixer(SHARE, share, y, x)
        assert float(stats.dropped) == 0.0
        total = total + part.reshape(-1, 32)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)
    # A rank alone is not the layer: most of the routed part is elsewhere.
    assert np.abs(part.reshape(-1, 32) - want).max() > 1e-3


# -- (e) the published count -------------------------------------------------------------

def test_parameter_count_is_exact():
    cell = loader.load_cell(CELL)
    fam = FAMILY.Family(cell["config"], cell["traffic"]["mesh"])
    shapes = jax.eval_shape(
        lambda k: tfm.init_params(k, fam.cfg, fam.par), jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == 559_290_880 == cell["config"]["parameters"]
    layers = shapes["layers"]
    per = {kind: sum(int(np.prod(a.shape[3:])) for a in
                     jax.tree_util.tree_leaves(layers[kind]))
           for kind in ("attn", "swa", "moe")}
    assert per == {"attn": 20_974_080, "swa": 20_974_080,
                   "moe": 94_538_240 - 2_560 + 2_560}
    assert 4 * (20_974_080 + 94_538_240) == 462_049_280
    assert layers["swa"]["wq"].shape == (1, 1, 3, 2560, 3584)
    assert layers["attn"]["wk"].shape == (1, 1, 1, 2560, 512)
    assert layers["moe"]["w_up"].shape == (1, 1, 4, 16, 2560, 768)
    assert layers["moe"]["gate"].shape == (1, 1, 4, 2560, 64)
    assert shapes["embed"].shape == shapes["lm_head"].shape == (18992, 2560)
    assert "pos" not in shapes and "leading" not in layers
    # The family's seeded weights are the same tree.
    assert jax.tree_util.tree_structure(jax.eval_shape(
        fam.init_params, jax.random.PRNGKey(0))) == \
        jax.tree_util.tree_structure(shapes)


# -- (f) the layout's rules, the step and its FLOPs --------------------------------------

def test_what_the_carried_operand_asks_for_and_refuses():
    with pytest.raises(ValueError, match="cannot open the model or the period"):
        tfm.init_params(jax.random.PRNGKey(0),
                        SHARE._replace(n_layers=2, layer_pattern="E*"), PAR)
    # Without the field such a period stands, as it did.
    tfm.param_specs(SHARE._replace(n_layers=2, layer_pattern="E*",
                                   router_before_attention=False), PAR)
    with pytest.raises(ValueError, match="router_before_attention, .* are "
                                         "a patterned model's"):
        tfm.init_params(jax.random.PRNGKey(0), tfm.TransformerConfig(
            router_before_attention=True), PAR)
    with pytest.raises(NotImplementedError, match="ROADMAP M0"):
        tfm.param_specs(SHARE, tfm.ParallelConfig(mp=2))
    assert tfm.BLOCKS["E"].before(SHARE)
    assert not tfm.BLOCKS["E"].before(
        SHARE._replace(router_before_attention=False))
    assert not any(row.before(SHARE) for c, row in tfm.BLOCKS.items()
                   if c != "E")
    assert "router_before_attention" in tfm.__doc__


def test_the_step_trains_and_routes():
    mesh = mesh_of()
    params = seeded(SHARE, key=8)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(3), SHARE, 2)
    routing = tfm.make_routing_fn(SHARE, PAR, mesh)(params, *batch)
    assert routing["assignments"].shape == (1, 4, 16)
    assert float(routing["assignments"][0, 0].sum()) == 2 * 16 * 3
    assert float(routing["dropped"]) == 0.0
    assert routing["held_rows"].shape == (1, 4)
    opt = optax.adamw(1e-2)
    step, shard = tfm.make_train_step(SHARE, PAR, mesh, opt)
    p = shard(params)
    state = opt.init(p)
    losses = []
    for _ in range(4):
        p, state, loss = step(p, state, *batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_flops_count_the_band_and_the_causal_half():
    d, s, hd = WHOLE.d_model, WHOLE.seq_len, 8
    proj = 2 * d * hd * (2 * 4 + 2 * 2)
    assert tfm.BLOCKS["*"].flops(WHOLE) == proj + 4 * (s / 2) * 4 * hd
    band = 5 - 5 * 4 / (2 * s)
    assert tfm.BLOCKS["W"].flops(WHOLE) == proj + 4 * band * 4 * hd
    assert tfm.BLOCKS["E"].flops(SHARE) == (
        2 * d * 16 + 3 * 4 / 16 * 6 * d * 12)
    assert tfm.train_flops_per_seq(SHARE) == 3.0 * s * (
        2.0 * d * 64 + sum(tfm.BLOCKS[c].flops(SHARE) for c in "*EWEWEWE"))
