"""Learned sparse attention in the program: the exact choice of the keys
(``ops/sparse_index.py``), the flash kernels of a selected call
(``ops/flash_attention.py``, in the Pallas interpreter), rotary positions
from three streams, the two gradient paths of an "S" block, and what
``_check_layout`` refuses.  The family against its plain reference is
``tests/benchmark_tests/test_benchmark_keye_vl.py``'s."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from horovod_tpu.metrics.registry import registry
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import sparse_index as si
from horovod_tpu.parallel import ring_attention as ra

from _flash_kernels import quick


def reference_choice(scores, topk):
    """(T, S) bool by a sort: row t's ``topk`` largest of its first t + 1
    scores, of equals the later."""
    scores = np.asarray(scores)
    out = np.zeros(scores.shape, bool)
    for t, row in enumerate(scores):
        order = sorted(range(t + 1), key=lambda s: (-row[s], -s))
        out[t, order[:topk]] = True
    return out


# -- the choice ---------------------------------------------------------------------

@pytest.mark.parametrize("s, topk", [(8, 16), (16, 16), (64, 16), (64, 1)])
@pytest.mark.parametrize("scores_of", ["normal", "few_values", "zeros"])
def test_select_tile_is_the_sort_with_ties_to_the_later_key(s, topk,
                                                            scores_of):
    rng = np.random.default_rng(s + topk)
    scores = {"normal": rng.normal(size=(s, s)),
              # Many exact ties at the threshold, of both signs of zero.
              "few_values": rng.integers(-2, 3, (s, s)) * 0.5 * rng.choice(
                  [1.0, -1.0], (s, s)),
              "zeros": np.zeros((s, s))}[scores_of].astype(np.float32)
    chosen = si.select_tile(jnp.asarray(scores), 0, topk)
    np.testing.assert_array_equal(np.asarray(chosen),
                                  reference_choice(scores, topk))
    assert int(jnp.max(jnp.sum(chosen, -1))) == min(topk, s)


def test_a_planted_tie_at_the_threshold_goes_to_the_later_key():
    # Query 5 of 6 keeps 3 of its keys: 9 > 7 = 7 = 7 > 1 > 0.
    scores = jnp.asarray([[0.0] * 6] * 5 + [[7.0, 9.0, 7.0, 1.0, 7.0, 0.0]])
    chosen = np.asarray(si.select_tile(scores, 0, 3))
    assert chosen[5].tolist() == [False, True, True, False, True, False]
    # ... and a tile that starts at query 11 ranks that row twice over:
    # both nines, then of six sevens the last.
    late = np.asarray(si.select_tile(jnp.tile(scores[5:], (1, 2)), 11, 3))
    assert np.flatnonzero(late[0]).tolist() == [1, 7, 10]


def test_ordered_keys_keep_the_floats_order():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, jnp.inf],
                    jnp.float32)
    keys = np.asarray(si._ordered(x)).astype(np.int64)
    assert (np.diff(keys) >= 0).all() and keys[3] == keys[4]
    assert (np.diff(np.delete(keys, 3)) > 0).all() and keys.min() > 0


def indexer_operands(key, b, s, j, di):
    kq, kk, kw = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, s, j, di)),
            jax.random.normal(kk, (b, s, di)),
            jax.random.normal(kw, (b, s, j)) / math.sqrt(j * di))


@pytest.mark.parametrize("s, tile", [(32, 512), (128, 32)])
def test_select_is_the_choice_of_the_scores_tile_by_tile(s, tile,
                                                         monkeypatch):
    monkeypatch.setattr(si, "Q_TILE", tile)
    qi, ki, w = indexer_operands(jax.random.PRNGKey(s), 2, s, 3, 8)
    visible_t = si.select(qi, ki, w, 16)
    assert visible_t.shape == (2, s, s) and visible_t.dtype == jnp.int8
    for b in range(2):
        scores = si.tile_scores(qi[b], w[b], ki[b])
        np.testing.assert_array_equal(np.asarray(visible_t[b]).T != 0,
                                      reference_choice(scores, 16))


# -- the indexer's loss -----------------------------------------------------------------

def dense_index_loss(qi, ki, w, q, k, visible_t, scale):
    """The loss from dense arrays, softmax statistics of its own."""
    g = q.shape[2] // k.shape[2]
    visible = jnp.swapaxes(visible_t, 1, 2) != 0            # (B, Sq, Sk)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, g, 2)) * scale
    p = jax.nn.softmax(jnp.where(visible[:, None], scores, -jnp.inf), -1)
    pbar = jax.lax.stop_gradient(jnp.mean(p, 1))
    index = jnp.einsum("bjqk,bqj->bqk", jax.nn.relu(
        jnp.einsum("bqjd,bkd->bjqk", qi, ki)), w)
    logq = jax.nn.log_softmax(jnp.where(visible, index, -jnp.inf), -1)
    kl = jnp.where(visible, jax.scipy.special.xlogy(pbar, pbar)
                   - pbar * jnp.where(visible, logq, 0.0), 0.0)
    return jnp.sum(kl) / (q.shape[0] * q.shape[1])


@pytest.mark.parametrize("tile, chunk", [(512, 2048), (16, 32), (32, 16)])
def test_index_loss_and_its_gradients_are_the_dense_ones(tile, chunk,
                                                         monkeypatch):
    """Whole, and a tile of queries against its causal chunks of keys."""
    monkeypatch.setattr(si, "Q_TILE", tile)
    monkeypatch.setattr(si, "K_CHUNK", chunk)
    b, s, h, hkv, d = 2, 64, 4, 2, 16
    qi, ki, w = indexer_operands(jax.random.PRNGKey(1), b, s, 3, 8)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, s, hkv, d))
    v = jax.random.normal(kv, (b, s, h, d))
    visible_t = si.select(qi, ki, w, 16)
    _, lse = ra.selected_attention(q, jnp.repeat(k, h // hkv, 2), v,
                                   visible_t, use_flash=False)

    def tiled(qi, ki, w):
        return si.index_loss(qi, ki, w, q, k, lse, visible_t, d ** -0.5)

    def dense(qi, ki, w):
        return dense_index_loss(qi, ki, w, q, k, visible_t, d ** -0.5)

    got, got_grads = jax.value_and_grad(tiled, (0, 1, 2))(qi, ki, w)
    want, want_grads = jax.value_and_grad(dense, (0, 1, 2))(qi, ki, w)
    assert float(got) == pytest.approx(float(want), rel=1e-5) and got > 0
    for a, e in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, e, rtol=1e-4, atol=1e-7)


# -- the kernels ------------------------------------------------------------------------

@pytest.mark.parametrize("h, d", [(2, 128), (4, 64)])
def test_selected_kernels_are_reference_attention_under_the_mask(h, d):
    b, s = 2, 256
    ks = jax.random.split(jax.random.PRNGKey(d), 5)
    q, k, v, g = (jax.random.normal(kk, (b, s, h, d)) for kk in ks[:4])
    visible = jax.random.uniform(ks[4], (b, s, s)) < 0.3       # (B, Sq, Sk)
    visible = visible.at[:, :, 0].set(True)
    # A query with one chosen key, and one whose own key is not chosen.
    visible = visible.at[:, 5, :].set(False).at[:, 5, 3].set(True)
    visible = visible.at[:, 200, 200].set(False)
    visible_t = jnp.swapaxes(visible, 1, 2).astype(jnp.int8)

    def both(q, k, v):
        (out, lse), pull = jax.vjp(
            lambda *a: fa.flash_attention(
                *a, causal=True, visible_t=visible_t, interpret=True,
                block_q=128, block_k=128), q, k, v)
        want, want_pull = jax.vjp(
            lambda *a: ra.reference_attention(*a, causal=True,
                                              visible=visible), q, k, v)
        _, xla_lse = fa._xla_attention_with_lse(
            q, k, v, True, d ** -0.5, 0, 0, visible_t=visible_t)
        return (out, want, lse, xla_lse, pull((g, jnp.zeros_like(lse))),
                want_pull(g))

    out, want, lse, xla_lse, grads, want_grads = quick(both, q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-6)
    np.testing.assert_allclose(lse, xla_lse, atol=2e-6)
    for a, e in zip(grads, want_grads):
        np.testing.assert_allclose(a, e, atol=2e-5)
    # Query 5 sees key 3 alone: its output is that value.
    np.testing.assert_allclose(out[:, 5], v[:, 3], atol=1e-6)


def test_a_selected_call_counts_its_kernels_and_walks_the_causal_list():
    def count(name, **labels):
        return registry().counter(name, "", **labels).value

    kernels = ("hvd_flash_fwd_sel", "hvd_flash_bwd_dkv_sel",
               "hvd_flash_bwd_dq_sel")
    before = {k: count("hvd_sparse_attention_built_total", kernel=k)
              for k in kernels}
    live = count("hvd_flash_tiles_built_total", kernel=kernels[0],
                 state="live")
    q = jnp.zeros((1, 512, 2, 64))
    visible_t = jnp.ones((1, 512, 512), jnp.int8)
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(fa.flash_attention(
        q, q, q, causal=True, visible_t=visible_t, interpret=True,
        block_q=128, block_k=128)[0])))(q))
    for k in kernels:
        assert f"name={k}" in text
        assert count("hvd_sparse_attention_built_total",
                     kernel=k) == before[k] + 1
    # The triangle of 4 x 4 tiles, two heads.
    assert count("hvd_flash_tiles_built_total", kernel=kernels[0],
                 state="live") == live + 2 * 10
    # A call without a selection traces to what it did.
    plain = str(jax.make_jaxpr(lambda q: fa.flash_attention(
        q, q, q, causal=True, interpret=True))(q))
    assert "_sel" not in plain and "name=hvd_flash_fwd" in plain


def test_a_selected_call_refuses_what_it_is_exclusive_with():
    q = jnp.zeros((1, 256, 2, 64))
    visible_t = jnp.ones((1, 256, 256), jnp.int8)
    for kw in ({"window": 64}, {"diffusion_block": 4}, {"causal": False},
               {"q_offset": 256}):
        with pytest.raises(ValueError, match="selected call"):
            fa.flash_attention(q, q, q, visible_t=visible_t,
                               **{"causal": True, **kw})
    with pytest.raises(ValueError, match="int8 visibility"):
        fa.flash_attention(q, q, q, visible_t=visible_t.astype(jnp.int32))


# -- the positions ----------------------------------------------------------------------

def test_rope_streams_is_the_per_element_rotation_and_rope_on_equal_streams():
    mb, s, h, hd, theta, sections = 2, 12, 3, 16, 1e4, (2, 3, 3)
    rng = np.random.default_rng(0)
    t = rng.normal(size=(mb, s, h, hd)).astype(np.float32)
    positions = rng.integers(0, 50, (mb, 3, s)).astype(np.int32)
    got = np.asarray(tfm._rope_streams(jnp.asarray(t), jnp.asarray(positions),
                                       theta, sections))
    half = hd // 2
    stream = [c for c, n in enumerate(sections) for _ in range(n)]
    for b in range(mb):
        for p in range(s):
            for i in range(half):
                angle = positions[b, stream[i], p] * theta ** (-2 * i / hd)
                x, y = t[b, p, :, i], t[b, p, :, i + half]
                np.testing.assert_allclose(
                    got[b, p, :, i], x * math.cos(angle) - y * math.sin(angle),
                    atol=2e-5)
                np.testing.assert_allclose(
                    got[b, p, :, i + half],
                    y * math.cos(angle) + x * math.sin(angle), atol=2e-5)
    at = jnp.arange(s, dtype=jnp.int32)
    equal = jnp.broadcast_to(at, (mb, 3, s))
    np.testing.assert_allclose(
        tfm._rope_streams(jnp.asarray(t), equal, theta, sections),
        tfm._rope(jnp.asarray(t), at, theta), atol=1e-6)
    with pytest.raises(ValueError, match="do not cover"):
        tfm._rope_streams(jnp.asarray(t), equal, theta, (2, 3, 4))


# -- the block ----------------------------------------------------------------------------

SMALL = dict(vocab_size=64, d_model=32, n_heads=4, d_ff=16, n_layers=4,
             seq_len=64, n_experts=8, top_k=2, dtype=jnp.float32,
             dropless=True, gated_experts=True, tied_head=False,
             layer_pattern="SE", learned_positions=False, n_kv_heads=2,
             attn_head_dim=16, rope_theta=1e4, head_qk_norm=True,
             router_renormalise=True, n_experts_held=4, index_heads=2,
             index_head_dim=8, index_topk=16, rope_sections=(2, 2, 4))
INDEXER = ("index_wq", "index_wk", "index_ww", "index_k_norm", "index_k_bias")


def one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "pp", "mp"))


@pytest.fixture(scope="module")
def gradients():
    """{index_loss_coef: (loss, gradients)} of one batch."""
    out = {}
    for coef in (1.0, 0.0):
        cfg = tfm.TransformerConfig(**SMALL, index_loss_coef=coef)
        par = tfm.ParallelConfig()
        params = tfm.init_params(jax.random.PRNGKey(0), cfg, par)
        batch = tfm.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
        out[coef] = quick(jax.value_and_grad(tfm.make_loss_fn(
            cfg, par, one_device_mesh())), params, *batch)
    return out


def test_the_indexers_leaves_learn_from_their_loss_and_no_other_leaf_does(
        gradients):
    (loss, grads), (lm_loss, lm_grads) = gradients[1.0], gradients[0.0]
    assert float(loss) > float(lm_loss) > 0        # L = L_LM + L_I, L_I > 0
    sel, lm_sel = grads["layers"]["sel"], lm_grads["layers"]["sel"]
    assert set(INDEXER) < set(sel)
    for name in INDEXER:
        # L_LM gives the indexer exactly nothing; L_I trains every array.
        assert not np.asarray(lm_sel[name]).any(), name
        assert (np.abs(np.asarray(sel[name])).reshape(2, -1).max(-1) > 0).all()
    # And L_I gives every other leaf exactly nothing.
    rest, lm_rest = ({**g, "layers": {
        **g["layers"], "sel": {k: v for k, v in g["layers"]["sel"].items()
                               if k not in INDEXER}}}
        for g in (grads, lm_grads))
    jax.tree_util.tree_map(np.testing.assert_array_equal, rest, lm_rest)


def test_train_step_moves_the_indexer_and_the_flops_count_the_chosen_pairs():
    import optax
    cfg = tfm.TransformerConfig(**SMALL)
    par = tfm.ParallelConfig()
    step, shard = tfm.make_train_step(cfg, par, one_device_mesh(),
                                      optax.sgd(0.1))
    params = shard(tfm.init_params(jax.random.PRNGKey(0), cfg, par))
    before = jax.tree_util.tree_map(np.asarray, params["layers"]["sel"])
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
    assert batch[2].shape == (2, 3, 64) and tfm.batch_extras(cfg) == (
        "positions",)
    params, _, loss = step(params, optax.sgd(0.1).init(params), *batch)
    assert np.isfinite(float(loss))
    for name in INDEXER:
        assert (np.asarray(params["layers"]["sel"][name])
                != before[name]).any(), name
    d, s, k = 32, 64, 16
    pairs = (k * (k + 1) / 2 + (s - k) * k) / s
    attention = (2 * d * 16 * (2 * 4 + 2 * 2) + 2 * d * (2 * 8 + 8 + 2)
                 + 2 * 2 * 8 * (s + 1) / 2 + 4 * pairs * 4 * 16)
    experts = 2 * d * 8 + 2 * 4 / 8 * 6 * d * 16
    assert tfm.train_flops_per_seq(cfg) == pytest.approx(
        3 * s * (2 * d * 64 + 2 * (attention + experts)))


def test_the_loss_takes_the_position_streams_and_only_with_such_blocks():
    cfg = tfm.TransformerConfig(**SMALL)
    par = tfm.ParallelConfig()
    mesh = one_device_mesh()
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    cfg, par))
    tokens, labels, positions = tfm.synthetic_batch(jax.random.PRNGKey(1),
                                                    cfg, 2)
    with pytest.raises(ValueError, match="position streams"):
        jax.eval_shape(tfm.make_loss_fn(cfg, par, mesh), params, tokens,
                       labels)
    plain = cfg._replace(layer_pattern="*E", index_heads=0, index_topk=0,
                         rope_sections=None)
    with pytest.raises(ValueError):
        jax.eval_shape(tfm.make_loss_fn(plain, par, mesh), jax.eval_shape(
            lambda: tfm.init_params(jax.random.PRNGKey(0), plain, par)),
            tokens, labels, positions)
    with pytest.raises(ValueError, match="one microbatch"):
        jax.eval_shape(tfm.make_loss_fn(
            cfg, par._replace(n_microbatches=2), mesh), params, tokens,
            labels, positions)


@pytest.mark.parametrize("change, par, error, words", [
    ({}, dict(mp=2), NotImplementedError, "M11"),
    ({}, dict(pp=2), NotImplementedError, "M11"),
    ({"attn_mode": "ring"}, {}, NotImplementedError, "ROADMAP M11"),
    ({"attn_mode": "ulysses"}, {}, NotImplementedError, "other shards"),
    ({"diffusion_block": 4}, {}, NotImplementedError, "learned selection"),
    ({"leading_pattern": "S", "n_layers": 5}, {}, NotImplementedError,
     "cannot lead"),
    ({"index_topk": 0}, {}, ValueError, "index_topk"),
    ({"rope_sections": (2, 2, 2)}, {}, ValueError, "half of head_dim"),
    ({"rope_sections": (1, 3, 4)}, {}, ValueError, "scale whole"),
    ({"rope_theta": None}, {}, ValueError, "rope_theta"),
])
def test_check_layout_refuses(change, par, error, words):
    cfg = tfm.TransformerConfig(**{**SMALL, **change})
    with pytest.raises(error, match=words):
        tfm._check_layout(cfg, tfm.ParallelConfig(**par))


def test_the_fields_are_a_patterned_models():
    with pytest.raises(ValueError, match="set layer_pattern"):
        tfm._check_layout(tfm.TransformerConfig(index_topk=4),
                          tfm.ParallelConfig())
    tfm._check_layout(tfm.TransformerConfig(**SMALL), tfm.ParallelConfig())
