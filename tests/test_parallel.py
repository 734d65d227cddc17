"""Parallelism-layer numerics: ring attention vs. dense attention, pipeline
vs. serial stages (forward and backward), tensor-parallel matmul and
vocab-parallel cross-entropy vs. unsharded references, MoE vs. a dense
per-token oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from horovod_tpu.compat import shard_map

import horovod_tpu as hvd
from horovod_tpu.metrics.registry import registry
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import moe as moe_lib
from horovod_tpu.parallel import pipeline as pp_lib
from horovod_tpu.parallel import ring_attention as ra
from horovod_tpu.parallel import tensor_parallel as tp
from horovod_tpu.parallel.mesh import create_mesh


def _mesh(**shape):
    hvd.init()
    return create_mesh(shape)


# ---------------------------------------------------------------------------
# ring attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    mesh = _mesh(dp=2, sp=4)
    B, S, H, D = 2, 32, 2, 8
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), dtype=jnp.float32)
    k = jax.random.normal(kk, (B, S, H, D), dtype=jnp.float32)
    v = jax.random.normal(kv, (B, S, H, D), dtype=jnp.float32)

    def fn(q, k, v):
        return ra.ring_attention(q, k, v, axis_name="sp", causal=causal)

    spec = P("dp", "sp")
    out = jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False))(q, k, v)
    expected = ra.full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_grad_matches_dense():
    mesh = _mesh(sp=8)
    B, S, H, D = 1, 16, 2, 4
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(ki, (B, S, H, D))
               for ki in jax.random.split(key, 3))

    def ring_loss(q, k, v):
        def inner(q, k, v):
            o = ra.ring_attention(q, k, v, axis_name="sp", causal=True)
            return jax.lax.psum(jnp.sum(o ** 2), "sp")[None]
        spec = P(None, "sp")
        out = shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=P("sp"), check_vma=False)(q, k, v)
        return out.sum() / 8.0

    def dense_loss(q, k, v):
        return jnp.sum(ra.full_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# pipeline
# ---------------------------------------------------------------------------

def test_pipeline_matches_serial_forward():
    mesh = _mesh(pp=4)
    n_micro, mb, d = 8, 2, 4
    key = jax.random.PRNGKey(2)
    # Stage s: x -> tanh(x @ W_s); serial reference composes all 4.
    ws = jax.random.normal(key, (4, d, d)) * 0.5
    x = jax.random.normal(jax.random.PRNGKey(3), (n_micro, mb, d))

    def stage_fn(w, a):
        return jnp.tanh(a @ w)

    def fn(w_stage, xs):
        out = pp_lib.pipeline_apply(stage_fn, w_stage[0], xs, axis_name="pp")
        mask = pp_lib.last_stage_mask("pp")
        return jax.lax.psum(out * mask, "pp")[None]

    out = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(P("pp"), P(None)),
        out_specs=P("pp"), check_vma=False))(ws, x)
    # All pp members return the same psum'd result; take member 0.
    result = np.asarray(out[0])

    serial = x
    for s in range(4):
        serial = stage_fn(ws[s], serial)
    np.testing.assert_allclose(result, np.asarray(serial), rtol=1e-5,
                               atol=1e-6)


def test_pipeline_backward_matches_serial():
    mesh = _mesh(pp=4)
    n_micro, mb, d = 4, 2, 4
    ws = jax.random.normal(jax.random.PRNGKey(4), (4, d, d)) * 0.5
    x = jax.random.normal(jax.random.PRNGKey(5), (n_micro, mb, d))

    def stage_fn(w, a):
        return jnp.tanh(a @ w)

    def pipe_loss(ws, x):
        def inner(w_stage, xs):
            out = pp_lib.pipeline_apply(stage_fn, w_stage[0], xs,
                                        axis_name="pp")
            mask = pp_lib.last_stage_mask("pp")
            return jax.lax.psum(jnp.sum(out ** 2) * mask, "pp")[None]
        out = shard_map(inner, mesh=mesh, in_specs=(P("pp"), P(None)),
                        out_specs=P("pp"), check_vma=False)(ws, x)
        return out.sum() / 4.0

    def serial_loss(ws, x):
        a = x
        for s in range(4):
            a = stage_fn(ws[s], a)
        return jnp.sum(a ** 2)

    g_pipe = jax.jit(jax.grad(pipe_loss))(ws, x)
    g_serial = jax.grad(serial_loss)(ws, x)
    np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_serial),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# tensor parallel
# ---------------------------------------------------------------------------

def test_column_then_row_parallel_matches_dense():
    mesh = _mesh(tp=8)
    d_in, d_mid, d_out, b = 8, 16, 8, 4
    key = jax.random.PRNGKey(6)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (b, d_in))
    w1 = jax.random.normal(k2, (d_in, d_mid))
    w2 = jax.random.normal(k3, (d_mid, d_out))

    def fn(x, w1s, w2s):
        h = tp.column_parallel(x, w1s)          # (b, d_mid/8)
        h = jax.nn.relu(h)
        return tp.row_parallel(h, w2s, "tp")[None]

    out = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(P(None), P(None, "tp"), P("tp", None)),
        out_specs=P("tp"), check_vma=False))(x, w1, w2)
    expected = jax.nn.relu(x @ w1) @ w2
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_vocab_parallel_cross_entropy():
    mesh = _mesh(tp=8)
    b, d, v = 4, 8, 32
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (b, d))
    emb = jax.random.normal(jax.random.PRNGKey(8), (v, d))
    labels = jnp.array([0, 5, 17, 31])

    def fn(x, emb_s, labels):
        logits = tp.vocab_parallel_logits(x, emb_s, "tp")
        return tp.vocab_parallel_cross_entropy(logits, labels, v // 8,
                                               "tp")[None]

    out = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(P(None), P("tp", None), P(None)),
        out_specs=P("tp"), check_vma=False))(x, emb, labels)
    full_logits = x @ emb.T
    log_probs = jax.nn.log_softmax(full_logits)
    expected = -jnp.take_along_axis(log_probs, labels[:, None], axis=1)[:, 0]
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


# The ring forms of the sequence-parallel gather -> matmul and matmul ->
# scatter (``gather_column_parallel[_ring]``, ``row_parallel(...,
# scatter_sequence=True)``) against the plain collectives they replace.

# The four-chip flagship cell's reference check: |loss difference| and the
# worst gradient leaf's relative L2 (benchmark/reference/flagship.py).
CELL_LOSS_TOL, CELL_GRAD_REL_L2 = 2e-3, 0.03


def _plain_gather_matmul(x, w):
    return jnp.einsum("...i,io->...o",
                      jax.lax.all_gather(x, "mp", axis=1, tiled=True), w)


def _plain_matmul_scatter(u, w):
    return jax.lax.psum_scatter(jnp.einsum("...i,io->...o", u, w), "mp",
                                scatter_dimension=1, tiled=True)


def _causal_mean(h):
    """Stands where attention stands: row s reads rows 0 .. s, so a chunk
    out of sequence order changes every row behind it."""
    n = jnp.arange(1, h.shape[1] + 1, dtype=jnp.float32)[None, :, None]
    return (jnp.cumsum(h.astype(jnp.float32), axis=1) / n).astype(h.dtype)


# (the block between the gather and the scatter, the width of a member's
# shard of the first matmul): attention-shaped, rows in sequence order
# through a narrow product; MLP-shaped, tokenwise through a wide one.
TP_SHAPES = {"attention": 96, "mlp": 640}


def _tp_block(shape, ring):
    if shape == "attention":
        def block(act, w_a, w_b):
            h = (tp.gather_column_parallel(act, w_a, "mp") if ring
                 else _plain_gather_matmul(act, w_a))
            h = _causal_mean(h)
            return (tp.row_parallel(h, w_b, "mp", scatter_sequence=True)
                    if ring else _plain_matmul_scatter(h, w_b))
    else:
        def block(act, w_a, w_b):
            if not ring:
                return _plain_matmul_scatter(
                    jax.nn.gelu(_plain_gather_matmul(act, w_a)), w_b)
            u = jax.tree_util.tree_map(
                jax.nn.gelu, tp.gather_column_parallel_ring(act, w_a, "mp"))
            return tp.row_parallel(u, w_b, "mp", scatter_sequence=True)
    return block


def _tp_stack_loss_and_grads(mesh, shape, ring, x, w_a, w_b):
    """Loss and gradients through two scanned, checkpointed layers of
    ``act + block(act)``, as the trainer stacks its layers."""
    block = _tp_block(shape, ring)

    def stack(x, w_a, w_b):
        def layer(act, w):
            return act + block(act, *w), None
        return jax.lax.scan(jax.checkpoint(layer), x, (w_a, w_b))[0]

    fn = shard_map(stack, mesh=mesh,
                   in_specs=(P(None, "mp"), P(None, None, "mp"),
                             P(None, "mp")),
                   out_specs=P(None, "mp"), check_vma=False)

    def loss(x, w_a, w_b):
        return jnp.mean(jnp.sin(fn(x, w_a, w_b).astype(jnp.float32)))

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(x, w_a, w_b)


def _rel_l2(a, b):
    a, b = (np.asarray(t, np.float32) for t in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("shape", sorted(TP_SHAPES))
@pytest.mark.parametrize("mp, s_loc", [(1, 256), (2, 256), (4, 256),
                                       (8, 256), (2, 258)])
def test_ring_gather_and_scatter_match_the_plain_collectives(
        mp, s_loc, shape, dtype):
    """Values and gradients, through a scanned and checkpointed two-layer
    stack, of the ring forms against all_gather -> einsum and einsum ->
    psum_scatter.  258 rows a chunk: four pieces of 64 and 65."""
    hvd.init()
    mesh = create_mesh({"mp": mp}, devices=jax.devices()[:mp])
    mb, d, mid = 2, 512, TP_SHAPES[shape] * mp
    kx, ka, kb = jax.random.split(jax.random.PRNGKey(mp + s_loc), 3)
    x = jax.random.normal(kx, (mb, mp * s_loc, d), dtype)
    w_a = (jax.random.normal(ka, (2, d, mid)) * d ** -0.5).astype(dtype)
    w_b = (jax.random.normal(kb, (2, mid, d)) * mid ** -0.5).astype(dtype)
    ring_loss, ring_grads = _tp_stack_loss_and_grads(
        mesh, shape, True, x, w_a, w_b)
    loss, grads = _tp_stack_loss_and_grads(mesh, shape, False, x, w_a, w_b)
    if mp == 1:                       # the plain collectives themselves
        assert float(ring_loss) == float(loss)
        for ring_g, g in zip(ring_grads, grads):
            np.testing.assert_array_equal(np.asarray(ring_g), np.asarray(g))
        return
    loss_tol, grad_tol = ((1e-6, 1e-5) if dtype == jnp.float32
                          else (CELL_LOSS_TOL, CELL_GRAD_REL_L2))
    assert abs(float(ring_loss) - float(loss)) <= loss_tol
    for ring_g, g in zip(ring_grads, grads):
        assert ring_g.dtype == g.dtype
        assert _rel_l2(ring_g, g) <= grad_tol


def test_ring_pieces_come_from_the_shapes():
    """The four-chip flagship cell's four matmuls (10 x 4096 rows a chunk,
    bf16) are all slower on the wire than on the MXU: four pieces each.  A
    gather in front of a product wide enough to hide it is not cut (its
    pieces are copies), a scatter behind one always in two (the add); a
    chunk too light for the wire is left whole, and a cut that does not
    divide leaves pieces a row apart."""
    d, item = 1024, 2

    def count(s_loc, width_moved, k, n, fused_add, lead=10):
        rows = lead * s_loc
        return tp._pieces(s_loc, rows * width_moved * item,
                          2.0 * rows * k * n, fused_add)

    assert len(count(4096, d, d, 1536, False)) == 4           # -> wqkv
    assert len(count(4096, d, d, 2048, False)) == 4           # -> w1
    assert len(count(4096, d, 512, d, True)) == 4             # wo ->
    assert len(count(4096, d, 2048, d, True)) == 4            # w2 ->
    assert len(count(4096, d, d, 4096, False)) == 1     # the matmul hides it
    assert len(count(4096, d, d, 2816, False)) == 2
    assert len(count(4096, d, 8192, d, True)) == 2      # an add needs two
    assert count(16, 32, 32, 32, True, lead=1) == ((0, 16),)
    assert count(258, 512, 512, 96, False, lead=4) == (
        (0, 64), (64, 129), (129, 193), (193, 258))


def _parent_attention_block(cfg, lp, x):
    """``models/transformer._attention_block`` (megatron mode) with the
    plain collectives that stood before the ring: gather -> the q, k and v
    einsums -> attention -> einsum -> scatter."""
    hd = cfg.head_dim
    hnorm = tfm._rmsnorm(x, lp["ln1"], cfg.norm_eps)
    slabs = tp.qkv_slabs(lp["wqkv"].astype(x.dtype), hd)
    hg = tp.gather_sequence(hnorm, "mp", dim=1)
    mb, s_full = hg.shape[:2]
    qkv = [tp.column_parallel(hg, w) for w in slabs]
    q, k, v = (t.reshape(mb, s_full, -1, hd) for t in qkv)
    q, k = tfm._position_qk(cfg, lp, q, k, jnp.arange(s_full), "mp")
    o = ra.full_attention(q, k, v, causal=True)
    o = o.reshape(mb, s_full, -1)
    partial = jnp.einsum("...i,io->...o", o, lp["wo"].astype(x.dtype))
    return jax.lax.psum_scatter(partial, "mp", scatter_dimension=1,
                                tiled=True)


def _parent_mlp_block(cfg, lp, x):
    hnorm = tfm._rmsnorm(x, lp["ln2"], cfg.norm_eps)
    hg = tp.gather_sequence(hnorm, "mp", dim=1)
    u = jax.nn.gelu(tp.column_parallel(hg, lp["w1"].astype(x.dtype)))
    partial = jnp.einsum("...i,io->...o", u, lp["w2"].astype(x.dtype))
    return jax.lax.psum_scatter(partial, "mp", scatter_dimension=1,
                                tiled=True)


def _ring_counts():
    return {form: registry().counter("hvd_tp_ring_matmuls_built_total",
                                     form=form).value
            for form in ("gather", "scatter")}


def _one_layer(mp):
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=64, n_heads=4, d_ff=128, n_layers=1,
        seq_len=32)       # bf16 compute on fp32 weights: the casts are in
    par = tfm.ParallelConfig(dp=1, pp=1, mp=mp)
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": mp},
                       devices=jax.devices()[:mp])
    layers = tfm.init_params(jax.random.PRNGKey(3), cfg, par)["layers"]
    specs = tfm.param_specs(cfg, par)["layers"]
    x = jax.random.normal(jax.random.PRNGKey(4),
                          (2, cfg.seq_len, cfg.d_model), cfg.dtype)

    def sharded(block):
        def on_device(lp, x):
            lp = jax.tree_util.tree_map(lambda a: a[0, 0], lp)
            out = block(cfg, lp, x)
            return out[0] if isinstance(out, tuple) else out

        fn = shard_map(on_device, mesh=mesh,
                       in_specs=(specs, P(None, "mp")),
                       out_specs=P(None, "mp"), check_vma=False)
        return lambda lp, x: jnp.sum(jnp.sin(fn(lp, x)))

    return sharded, layers, x


@pytest.mark.parametrize("block, parent", [
    ("_attention_block", _parent_attention_block),
    ("_mlp_block", _parent_mlp_block)])
def test_at_mp_1_a_block_is_the_plain_gather_einsum_scatter(block, parent):
    """One member: the jaxpr is the parent's — no ring, no piece written in
    place — and outputs and gradients are its to the bit."""
    hvd.init()
    sharded, layers, x = _one_layer(mp=1)
    before = _ring_counts()
    ours = jax.value_and_grad(sharded(getattr(tfm, block)), argnums=(0, 1))
    theirs = jax.value_and_grad(sharded(parent), argnums=(0, 1))
    jaxpr = str(jax.make_jaxpr(ours)(layers, x))
    assert "ppermute" not in jaxpr and "dynamic_update_slice" not in jaxpr
    assert jaxpr == str(jax.make_jaxpr(theirs)(layers, x))
    assert _ring_counts() == before
    got, want = jax.jit(ours)(layers, x), jax.jit(theirs)(layers, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_ring_counter_reads_what_was_built():
    """Trace-time: a layer at mp = 1 builds no ring matmul, at mp = 2 two
    gathers and two scatters (and their like again under AD's own trace is
    not counted: the transposes are not new calls)."""
    hvd.init()

    def trace(mp):
        sharded, layers, x = _one_layer(mp)
        before = _ring_counts()
        for block in (tfm._attention_block, tfm._mlp_block):
            jax.make_jaxpr(sharded(block))(layers, x)
        return {form: n - before[form]
                for form, n in _ring_counts().items()}

    assert trace(1) == {"gather": 0, "scatter": 0}
    assert trace(2) == {"gather": 2, "scatter": 2}


# ---------------------------------------------------------------------------
# MoE expert parallel
# ---------------------------------------------------------------------------

def test_moe_matches_dense_oracle():
    mesh = _mesh(ep=4)
    t, d, ff = 16, 8, 16
    n_local, ep_size = 1, 4
    n_experts = n_local * ep_size
    params = moe_lib.init_moe_params(jax.random.PRNGKey(9), d, ff,
                                     n_experts, n_experts)  # full copy
    x = jax.random.normal(jax.random.PRNGKey(10), (t, d))

    def fn(gate, w_in, w_out, x):
        local = moe_lib.MoEParams(gate=gate, w_in=w_in, w_out=w_out)
        # capacity_factor large → no token dropped → must equal the oracle.
        return moe_lib.moe_layer(local, x, "ep", capacity_factor=4.0)[None]

    out = jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=(P(None), P("ep"), P("ep"), P(None)),
        out_specs=P("ep"), check_vma=False))(
            params.gate, params.w_in, params.w_out, x)

    # Dense oracle: each token through its argmax expert, weighted by prob.
    logits = np.asarray(x @ params.gate)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    idx = probs.argmax(-1)
    expected = np.zeros((t, d), dtype=np.float32)
    for i in range(t):
        e = idx[i]
        h = np.asarray(jax.nn.gelu(
            jnp.asarray(np.asarray(x)[i] @ np.asarray(params.w_in[e]))))
        expected[i] = probs[i, e] * (h @ np.asarray(params.w_out[e]))
    np.testing.assert_allclose(np.asarray(out[0]), expected, rtol=1e-3,
                               atol=1e-4)


def test_moe_capacity_drops_tokens():
    """With capacity 1 and many tokens per expert, dropped tokens produce
    zero output (residual passthrough is the caller's job)."""
    mesh = _mesh(ep=4)
    t, d, ff = 8, 4, 8
    params = moe_lib.init_moe_params(jax.random.PRNGKey(11), d, ff, 4, 4)
    # Steer all tokens to expert 0 via a huge gate column.
    gate = params.gate.at[:, 0].set(100.0)
    x = jnp.ones((t, d))

    def fn(gate, w_in, w_out, x):
        local = moe_lib.MoEParams(gate=gate, w_in=w_in, w_out=w_out)
        return moe_lib.moe_layer(local, x, "ep", capacity_factor=0.5)[None]

    out = jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=(P(None), P("ep"), P("ep"), P(None)),
        out_specs=P("ep"), check_vma=False))(
            gate, params.w_in, params.w_out, x)
    out = np.asarray(out[0])
    # capacity = ceil(8/4*0.5) = 1 → exactly 1 token kept, 7 dropped (zeros).
    nonzero_rows = (np.abs(out).sum(axis=1) > 1e-6).sum()
    assert nonzero_rows == 1


# ---------------------------------------------------------------------------
# Ulysses sequence parallelism (parallel/ulysses.py)
# ---------------------------------------------------------------------------

def test_ulysses_matches_reference():
    import numpy as np
    from horovod_tpu.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.parallel import ring_attention as ra
    from horovod_tpu.parallel.ulysses import ulysses_attention

    devs = jax.devices()[:4]
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(np.array(devs), ("sp",))
    B, S, H, D = 1, 128, 4, 16
    q, k, v = [jax.random.normal(kk, (B, S, H, D), dtype=jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3)]
    ref = ra.reference_attention(q, k, v, causal=True)

    f = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False)
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

    # Differentiable: gradients match the unsharded oracle.
    g1 = jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(
        lambda q, k, v: jnp.sum(
            ra.reference_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


def test_ulysses_rejects_indivisible_heads():
    import numpy as np
    from horovod_tpu.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.parallel.ulysses import ulysses_attention

    devs = jax.devices()[:4]
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(np.array(devs), ("sp",))
    q = jnp.zeros((1, 64, 3, 8))  # 3 heads, sp=4
    with pytest.raises(ValueError, match="divisible"):
        shard_map(
            lambda q: ulysses_attention(q, q, q, "sp"),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False)(q)
