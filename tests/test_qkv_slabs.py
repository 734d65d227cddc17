"""The fused q/k/v projection as the step traces it: three (B, S, H·D)
products of the stored ``wqkv``'s [q | k | v] reordering
(``parallel/tensor_parallel.qkv_slabs``).

The stored layout is the contract — (d, heads * 3 * head_dim), heads
outermost, q, k, v inside a head, so an ``mp`` shard is whole heads and a
checkpoint written before the reordering loads as it did — so every case
holds the layer to the head-by-head form written out here in plain
``jax.numpy`` on the stored, unsharded weights: the output, and the gradient
with respect to the stored ``wqkv``.  And no activation of the traced layer
holds q, k and v interleaved: the shapes are read from the jaxpr.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.compat import shard_map
from horovod_tpu.models import bert
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import tensor_parallel as tp
from horovod_tpu.parallel.mesh import create_mesh

HEADS, BATCH, SEQ = 2, 2, 16


def _interleaved_qkv(h, wqkv, heads):
    """The parent's form: one product, reshaped (B, S, heads, 3, hd)."""
    qkv = jnp.einsum("bsd,de->bse", h, wqkv)
    qkv = qkv.reshape(qkv.shape[:2] + (heads, 3, -1))
    return qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]


def _plain_attention(q, k, v, causal):
    """softmax(q kᵀ / sqrt(hd)) v a head; q, k, v: (B, S, heads, hd)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        keep = jnp.tril(jnp.ones(s.shape[-2:], bool))
        s = jnp.where(keep, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(o.shape[:2] + (-1,))


def _plain_transformer_block(cfg, lp, x):
    h = tfm._rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = _interleaved_qkv(h, lp["wqkv"], cfg.n_heads)
    return _plain_attention(q, k, v, causal=True) @ lp["wo"]


def _plain_bert_layer(cfg, lp, x):
    q, k, v = _interleaved_qkv(bert._layernorm(x, lp["ln1"]), lp["wqkv"],
                               cfg.n_heads)
    x = x + _plain_attention(q, k, v, causal=False) @ lp["wo"]
    u = jax.nn.gelu(bert._layernorm(x, lp["ln2"]) @ lp["w1"])
    return x + u @ lp["w2"]


def _loss(fn):
    return lambda lp, x: jnp.sum(jnp.sin(fn(lp, x)))


def _transformer_layer(head_dim, mp, attn_mode):
    """(the block over an ``mp`` mesh as a function of the stored, whole
    weights; the plain form; the weights; the input)."""
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=HEADS * head_dim, n_heads=HEADS, d_ff=64,
        n_layers=1, seq_len=SEQ, attn_mode=attn_mode, dtype=jnp.float32)
    par = tfm.ParallelConfig(dp=1, pp=1, mp=mp)
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": mp},
                       devices=jax.devices()[:mp])
    layers = jax.tree_util.tree_map(
        lambda a: a[0, 0],
        tfm.init_params(jax.random.PRNGKey(1), cfg, par)["layers"])
    specs = jax.tree_util.tree_map(
        lambda s: P(*s[2:]), tfm.param_specs(cfg, par)["layers"],
        is_leaf=lambda s: isinstance(s, P))
    ours = shard_map(
        lambda lp, x: tfm._attention_block(cfg, lp, x), mesh=mesh,
        in_specs=(specs, P(None, "mp")), out_specs=P(None, "mp"),
        check_vma=False)
    x = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, cfg.d_model))
    return ours, lambda lp, x: _plain_transformer_block(cfg, lp, x), layers, x


def _bert_layer(head_dim, mp):
    cfg = bert.BertConfig(vocab_size=64, d_model=HEADS * head_dim,
                          n_heads=HEADS, d_ff=64, n_layers=1, seq_len=SEQ,
                          dtype=jnp.float32)
    layers = jax.tree_util.tree_map(
        lambda a: a[0],
        bert.init_params(jax.random.PRNGKey(1), cfg)["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, cfg.d_model))
    plain = lambda lp, x: _plain_bert_layer(cfg, lp, x)     # noqa: E731
    if mp == 1:
        return (lambda lp, x: bert._encoder_layer(cfg, lp, x, sharded=False),
                plain, layers, x)
    mesh = create_mesh({"dp": 1, "mp": mp}, devices=jax.devices()[:mp])
    specs = jax.tree_util.tree_map(
        lambda s: P(*s[1:]), bert.param_specs(cfg)["layers"],
        is_leaf=lambda s: isinstance(s, P))
    ours = shard_map(
        lambda lp, x: bert._encoder_layer(cfg, lp, x, sharded=True),
        mesh=mesh, in_specs=(specs, P()), out_specs=P(), check_vma=False)
    return ours, plain, layers, x


def _same_function_of_the_stored_weights(ours, plain, layers, x):
    got = jax.jit(jax.value_and_grad(_loss(ours), argnums=(0, 1)))(layers, x)
    want = jax.jit(jax.value_and_grad(_loss(plain), argnums=(0, 1)))(
        layers, x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    assert got[1][0]["wqkv"].shape == layers["wqkv"].shape
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("attn_mode", ["megatron", "ring", "ulysses"])
@pytest.mark.parametrize("mp", [1, 2])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_the_attention_block_is_the_head_by_head_form_of_the_stored_wqkv(
        head_dim, mp, attn_mode):
    hvd.init()
    _same_function_of_the_stored_weights(
        *_transformer_layer(head_dim, mp, attn_mode))


@pytest.mark.parametrize("mp", [1, 2])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_berts_layer_is_the_head_by_head_form_of_the_stored_wqkv(
        head_dim, mp):
    hvd.init()
    _same_function_of_the_stored_weights(*_bert_layer(head_dim, mp))


def _shapes(jaxpr):
    """The shape of every array a jaxpr holds, its sub-programs' too."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            if hasattr(var.aval, "shape"):
                yield tuple(var.aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


def _interleaved(fn, layers, x, head_dim):
    """The shapes that end in (3, head_dim) — q, k, v side by side inside a
    head — among the arrays of ``fn``'s forward and backward, but for the
    view of the weight itself, (d_in, local heads, 3, head_dim), which is
    reordered at the weight's size."""
    jaxpr = jax.make_jaxpr(jax.value_and_grad(_loss(fn), argnums=(0, 1)))(
        layers, x).jaxpr
    d_in = layers["wqkv"].shape[0]
    return {s for s in _shapes(jaxpr)
            if s[-2:] == (3, head_dim)
            and not (len(s) == 4 and s[0] == d_in)}


@pytest.mark.parametrize("layer, mp, attn_mode", [
    ("transformer", 1, "megatron"), ("transformer", 2, "megatron"),
    ("transformer", 2, "ring"), ("transformer", 2, "ulysses"),
    ("bert", 1, None), ("bert", 2, None)])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_no_activation_of_the_traced_layer_interleaves_q_k_v(
        head_dim, layer, mp, attn_mode):
    """A later edit that brings (…, heads, 3, head_dim) back into the step
    fails here, on the CPU: XLA writes that product S-minor on the chip and
    every consumer that wants rows pays a transposing copy (PERF.md section
    6, PR 51).  The plain form above is what such an edit looks like."""
    hvd.init()
    ours, plain, layers, x = (
        _bert_layer(head_dim, mp) if layer == "bert"
        else _transformer_layer(head_dim, mp, attn_mode))
    assert _interleaved(ours, layers, x, head_dim) == set()
    assert (BATCH, SEQ, HEADS, 3, head_dim) in _interleaved(
        plain, layers, x, head_dim)


@pytest.mark.parametrize("head_dim, heads", [(64, 2), (128, 3), (16, 4)])
def test_a_slab_holds_its_projections_columns_head_by_head(head_dim, heads):
    d_in = 8
    stored = jnp.arange(d_in * heads * 3 * head_dim, dtype=jnp.float32
                        ).reshape(d_in, heads * 3 * head_dim)
    slabs = tp.qkv_slabs(stored, head_dim)
    assert [s.shape for s in slabs] == [(d_in, heads * head_dim)] * 3
    by_head = np.asarray(stored).reshape(d_in, heads, 3, head_dim)
    for i, slab in enumerate(slabs):
        np.testing.assert_array_equal(
            np.asarray(slab).reshape(d_in, heads, head_dim), by_head[:, :, i])


@pytest.mark.parametrize("mp", [1, 2, 4])
def test_a_gather_with_several_weights_gives_each_weights_product(mp):
    """One ring brings the rows for all: each product is the single
    weight's call's, rows in sequence order."""
    hvd.init()
    mesh = create_mesh({"mp": mp}, devices=jax.devices()[:mp])
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8 * mp, 16))
    ws = [jax.random.normal(jax.random.PRNGKey(i), (16, 8)) for i in (1, 2, 3)]

    def sharded(fn):
        return jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(P(None, "mp"), P()),
            out_specs=P(), check_vma=False))

    several = sharded(lambda x, ws: tp.gather_column_parallel(x, ws, "mp"))
    single = sharded(lambda x, ws: tuple(
        tp.gather_column_parallel(x, w, "mp") for w in ws))
    got, want = several(x, ws), single(x, ws)
    assert isinstance(got, tuple) and len(got) == 3
    for a, b, w in zip(got, want, ws):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("attn_mode, wqkv_spec", [
    ("megatron", P("pp", None, None, "mp")), ("ring", P("pp")),
    ("ulysses", P("pp"))])
def test_the_transformers_stored_wqkv_and_its_spec_are_the_parents(
        attn_mode, wqkv_spec):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=128, n_heads=2,
                                d_ff=64, n_layers=2, seq_len=SEQ,
                                attn_mode=attn_mode)
    par = tfm.ParallelConfig(dp=1, pp=1, mp=2)
    layers = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg, par))["layers"]
    assert layers["wqkv"].shape == (1, 2, 128, 3 * 2 * 64)
    assert layers["wo"].shape == (1, 2, 2 * 64, 128)
    assert tfm.param_specs(cfg, par)["layers"]["wqkv"] == wqkv_spec


def test_berts_stored_wqkv_and_its_spec_are_the_parents():
    cfg = bert.BertConfig(vocab_size=64, d_model=128, n_heads=2, d_ff=64,
                          n_layers=2, seq_len=SEQ)
    layers = jax.eval_shape(
        lambda: bert.init_params(jax.random.PRNGKey(0), cfg))["layers"]
    assert layers["wqkv"].shape == (2, 128, 3 * 2 * 64)
    assert layers["wo"].shape == (2, 2 * 64, 128)
    assert bert.param_specs(cfg)["layers"]["wqkv"] == P(None, None, "mp")
