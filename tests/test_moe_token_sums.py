"""The held experts' rows are summed into their tokens over the buffer's live
rows (``parallel/moe.py`` ``_token_sums``), not over every (token, held
expert) pair, and gathered over them too (``_live_prefix``,
``_combine_rows_bwd``), not over the buffer's padding; the sums scatter
nothing: the live rows are taken in token order and added in VMEM by the
kernel ``hvd_moe_token_sum`` (``ops/token_sum.py``), which runs here in the
Pallas interpreter.  Values and both gradients of the held path against the
plain pair-space formulas written out here, the cases a row-space walk can get
wrong (no row, a full run, live rows over several tiles and chunks of the
kernel's walk, a buffer that is no multiple of the chunk, an overflowing
buffer, an empty one), the sums against the gathered sum at the cells' widths
(the kernel alone: ``tests/test_token_sum_kernel.py``), the families' models
with the helpers swapped for the parent's forms, and the shape of the traced
step: no value with a row of width d for
every (token, held expert) or (token, choice) pair, no scatter-add of such
rows, no gather of the whole buffer and no fp32 array of its size, one sum, one
kernel and one gather a site a held layer."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.metrics.registry import registry
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import token_sum
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.mesh import create_mesh

PAR = tfm.ParallelConfig()


# -- the plain formulas ----------------------------------------------------------

def pair_space_routing(chosen, row_buffer):
    """Which pairs have a row, with no sort: pair (t, e) is the
    (chosen pairs of the experts before e + chosen tokens before t of e)-th
    row, and it is kept while that is inside the buffer."""
    counts = jnp.sum(chosen, axis=0)
    before = jnp.cumsum(counts) - counts
    rank = before[None, :] + jnp.cumsum(chosen, axis=0) - chosen
    kept = chosen & (rank < row_buffer)
    return kept, jnp.where(kept, rank, 0), int(chosen.sum() - kept.sum())


def plain_held_experts(params, x, weights, kept, activation):
    """Every held expert on every token, the kept pairs weighed and summed:
    nothing of the buffer."""
    def up(w):
        return jnp.einsum("td,edf->tef", x, w)
    hidden = activation(up(params.w_up)) if params.w_gate is None else (
        activation(up(params.w_gate)) * up(params.w_up))
    y = jnp.einsum("tef,efd->ted", hidden, params.w_down)
    return jnp.sum(jnp.where(kept[..., None], y, 0) * weights[..., None],
                   axis=1)


def pair_space_sums(held):
    """``_token_sums`` as PR 40's parent took it: one gathered row for every
    (token, held expert) pair, masked, weighed and summed over the ``held``
    experts.  The pairs are found again from the rows listed by token: a
    token's rows are a run, and a run is no longer than the experts held."""
    def sums(z, weights, order, n_live, tokens, site):
        moe._token_sums_built(site)
        rows = z.shape[0]
        start = jnp.searchsorted(order.token, jnp.arange(tokens))
        q = start[:, None] + jnp.arange(held)[None, :]          # (T, held)
        at = jnp.minimum(q, rows - 1)
        live = (q < rows) & (order.token[at] == jnp.arange(tokens)[:, None])
        picked = jnp.where(live[..., None], z[order.row[at]], 0)
        picked = picked.astype(jnp.float32)
        if weights is not None:
            picked = picked * jnp.where(live, weights[jnp.minimum(
                order.pair[at], weights.size - 1)], 0)[..., None]
        return jnp.sum(picked, axis=1)
    return sums


def force_tiling(monkeypatch, tile, chunk):
    """The kernel's walk at ``tile`` tokens a tile and ``chunk`` rows a chunk
    at most (``ops/token_sum.py`` takes both from the shapes: 256 and 256
    where the arrays are that large)."""
    monkeypatch.setattr(token_sum, "_TILE", tile)
    monkeypatch.setattr(token_sum, "_CHUNK", chunk)


# -- the held path, values and gradients ----------------------------------------

def held_case(t, d, n_held, top_k, n_experts, key=0, gated=True, d_ff=8):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    params = moe.GatedMoEParams(
        gate=None,
        w_gate=(jax.random.normal(ks[0], (n_held, d, d_ff)) / np.sqrt(d)
                if gated else None),
        w_up=jax.random.normal(ks[1], (n_held, d, d_ff)) / np.sqrt(d),
        w_down=jax.random.normal(ks[2], (n_held, d_ff, d)) / np.sqrt(d_ff))
    x = jax.random.normal(ks[3], (t, d))
    weights = jax.random.uniform(ks[4], (t, n_held), minval=0.1)
    chosen = jax.random.uniform(ks[5], (t, n_held)) < top_k / n_experts
    return params, x, weights, chosen


def check_held_path(params, x, weights, chosen, row_buffer, top_k,
                    activation=jax.nn.silu):
    """Values, the dropped count and the gradients in x, the weights and the
    experts against the plain formula; returns the path's output."""
    kept, _, dropped = pair_space_routing(chosen, row_buffer)

    def ours(x, weights, w_up, w_down):
        out, lost = moe._held_experts(
            params._replace(w_up=w_up, w_down=w_down), x, weights, chosen,
            activation, row_buffer)
        return jnp.sum(jnp.sin(out)), (out, lost)

    def plain(x, weights, w_up, w_down):
        out = plain_held_experts(params._replace(w_up=w_up, w_down=w_down),
                                 x, weights, kept, activation)
        return jnp.sum(jnp.sin(out)), out

    args = (x, weights, params.w_up, params.w_down)
    with jax.default_matmul_precision("highest"):
        (_, (out, lost)), got = jax.jit(jax.value_and_grad(
            ours, (0, 1, 2, 3), has_aux=True))(*args)
        (_, want_out), want = jax.jit(jax.value_and_grad(
            plain, (0, 1, 2, 3), has_aux=True))(*args)
    assert float(lost) == dropped
    np.testing.assert_allclose(out, want_out, atol=2e-5, rtol=1e-5)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5 * max(
            1.0, float(jnp.abs(w).max())), rtol=1e-4)
    return out


@pytest.mark.parametrize("n_held", [4, 8, 16])
@pytest.mark.parametrize("d", [8, 1024, 2048, 3072])
def test_the_held_path_is_the_pair_space_formula(d, n_held):
    """Rows narrower than, as wide as and wider than the 2048 at which the
    parent changed forms, 4, 8 and 16 experts held: one path."""
    top_k, n_experts = 8, 8 * n_held
    params, x, weights, chosen = held_case(24, d, n_held, top_k, n_experts,
                                           key=d + n_held)
    check_held_path(params, x, weights, chosen, moe.held_row_buffer(
        24, top_k, n_held, n_experts, 4.0), top_k)


def test_a_token_with_no_row_and_a_token_with_every_row_it_can_have():
    t, d, n_held, top_k = 16, 8, 8, 3
    params, x, weights, chosen = held_case(t, d, n_held, top_k, 32, key=1)
    chosen = chosen.at[0].set(False).at[1].set(False)
    chosen = chosen.at[1, jnp.array([1, 4, 7])].set(True)   # min(top_k, held)
    chosen = jnp.where(jnp.sum(chosen, axis=1, keepdims=True) > top_k,
                       False, chosen)
    out = check_held_path(params, x, weights, chosen, 64, top_k)
    assert not np.asarray(out[0]).any()
    assert np.asarray(out[1]).any()


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("rows", [128, 100])
def test_the_live_rows_span_several_tiles_and_chunks(monkeypatch, gated,
                                                     rows):
    """Tiles of 8 tokens and chunks of 8 rows under 40-odd live ones: the
    rows of a tile lie in two or three chunks and a chunk's rows in two
    tiles, the live rows end inside a chunk and inside a tile's run, and a
    buffer of 100 rows is no multiple of the chunk (the list is padded to
    104).  (Was the loop's ``..._span_several_trips_of_the_loop``.)"""
    force_tiling(monkeypatch, 8, 8)
    t, d, n_held, top_k = 32, 8, 4, 4
    params, x, weights, chosen = held_case(t, d, n_held, top_k, 12, key=2,
                                           gated=gated)
    per_token = np.asarray(chosen.sum(1))
    assert per_token.max() == 4 and per_token.sum() > 16
    assert per_token.sum() % 8
    check_held_path(params, x, weights, chosen, rows, top_k)


def test_a_full_buffer_that_is_no_multiple_of_the_chunk(monkeypatch):
    """Every row live, the longest prefix taken, and the list's last chunk
    half padding: each row is added once."""
    force_tiling(monkeypatch, 8, 16)
    t, d, n_held, top_k = 32, 8, 4, 4
    params, x, weights, chosen = held_case(t, d, n_held, top_k, 8, key=6)
    assert int(chosen.sum()) > 40
    check_held_path(params, x, weights, chosen, 40, top_k)


def test_a_buffer_that_overflows_drops_the_rows_the_parent_dropped():
    """The buffer's last rows go first: the pairs past it in expert order
    (the last experts' last tokens) are dropped, counted, and the kept ones
    summed as before."""
    t, d, n_held, top_k = 32, 8, 4, 4
    params, x, weights, chosen = held_case(t, d, n_held, top_k, 8, key=3)
    rows = 24
    kept, _, dropped = pair_space_routing(chosen, rows)
    assert dropped > 8 and int(kept.sum()) == rows
    assert bool(kept[:, 0].sum() == chosen[:, 0].sum())     # expert 0 whole
    assert int(kept[:, -1].sum()) == 0                      # the last lost
    check_held_path(params, x, weights, chosen, rows, top_k)


def test_a_buffer_with_no_live_row_gives_zeros():
    t, d, n_held, top_k = 16, 8, 4, 4
    params, x, weights, chosen = held_case(t, d, n_held, top_k, 8, key=4)
    out = check_held_path(params, x, weights, jnp.zeros_like(chosen), 32,
                          top_k)
    assert not np.asarray(out).any()


# -- the helper alone -------------------------------------------------------------

def routing_of(chosen, row_buffer):
    """The routing integers as ``_held_experts`` makes them."""
    t, n_held = chosen.shape
    key = jnp.where(chosen, jnp.arange(n_held), n_held)
    pair_of_row = jnp.argsort(key.reshape(-1), stable=True)
    row_of_pair = jnp.argsort(pair_of_row).reshape(t, n_held)
    pair_of_row = pair_of_row[:row_buffer]
    n_live = jnp.minimum(jnp.sum(chosen), row_buffer).astype(jnp.int32)
    row_used = jnp.arange(row_buffer) < n_live
    kept = chosen & (row_of_pair < row_buffer)
    row_of_pair = jnp.minimum(row_of_pair, row_buffer - 1)
    return n_live, row_of_pair, kept, pair_of_row, row_used


def order_of(pair_of_row, n_live, t, held):
    """The live rows listed by token, as ``_held_experts`` lists them."""
    return moe._token_order(pair_of_row, n_live, held, t)


@pytest.mark.parametrize("scaled", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [8, 1024, 2048, 2560, 3072])
def test_token_sums_is_the_gathered_sum(monkeypatch, d, scaled, dtype):
    """Against ``sum_e where(kept, z[row_of_pair]) * w`` with ``jnp``
    gathers: fp32 sums of the same terms, at a width that is no multiple of
    128 lanes and at the five cells' widths; the kernel in the Pallas
    interpreter, tiles of 16 tokens and chunks of 16 rows.  A token's rows
    are neighbours in the list, an expert after another, and are added as one
    fp32 product a chunk (a last bit may differ from a sum taken in
    sequence)."""
    force_tiling(monkeypatch, 16, 16)
    t, n_held, rows = 48, 8, 96
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    chosen = jax.random.uniform(ks[0], (t, n_held)) < 0.2
    n_live, row_of_pair, kept, pair_of_row, row_used = routing_of(chosen,
                                                                  rows)
    z = jax.random.normal(ks[1], (rows, d)).astype(dtype)
    w = jax.random.uniform(ks[2], (t, n_held))
    picked = jnp.where(kept[..., None], z[row_of_pair], 0).astype(jnp.float32)
    if scaled:
        picked = picked * w[..., None]
    want = jnp.sum(picked, axis=1)
    got = jax.jit(lambda z: moe._token_sums(
        z, w.reshape(-1) if scaled else None,
        order_of(pair_of_row, n_live, t, n_held), n_live, t, "combine"))(z)
    assert got.dtype == jnp.float32 and got.shape == (t, d)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)


# rows of the buffer, P(pair chosen), rows a trip: live rows in one trip; over
# several, the last part padding; a full buffer that is no multiple of the
# chunk; a buffer that overflows; a buffer with no live row; a buffer a
# quarter live, as the cells' are (its shortest prefix holds them)
WALKS = {"one_trip": (48, 0.3, 512), "several_trips": (100, 0.3, 8),
         "full_no_multiple": (40, 0.9, 16), "overflow": (24, 0.5, 8),
         "empty": (40, 0.0, 8), "quarter_live": (128, 0.25, 4)}


def walk_case(name, t=32, held=4):
    rows, p, chunk = WALKS[name]
    chosen = jax.random.uniform(jax.random.PRNGKey(9), (t, held)) < p
    live = min(int(chosen.sum()), rows)
    assert {"one_trip": 0 < live < rows, "several_trips": 3 * chunk < live
            < rows and live % chunk, "full_no_multiple": live == rows
            and rows % chunk, "overflow": int(chosen.sum()) > rows,
            "empty": live == 0, "quarter_live": 4 * chunk < live
            <= rows * 5 // 16}[name]
    return chosen, rows, chunk


@pytest.mark.parametrize("walk", sorted(WALKS))
def test_the_combines_backward_is_ads(monkeypatch, walk):
    """The combine's hand-written backward (a loop over the live prefix that
    writes ``dy`` and sets ``dweights``) against AD of the same sum written
    in pair space with a plain gather, on ``dy`` and ``dw``; the buffer's
    padding gets a ``dy`` of zeros.  (Was ``test_sdar_layers.py::
    test_the_held_paths_gradients_are_ads``.)"""
    t, held, d = 32, 4, 8
    chosen, rows, chunk = walk_case(walk, t, held)
    monkeypatch.setattr(moe, "_GATHER_CHUNK", chunk)
    force_tiling(monkeypatch, 8, chunk)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    n_live, row_of_pair, kept, pair_of_row, row_used = routing_of(chosen,
                                                                  rows)
    order = order_of(pair_of_row, n_live, t, held)
    y = jax.random.normal(keys[0], (rows, d))
    w = jax.random.uniform(keys[1], (t, held))
    g = jax.random.normal(keys[2], (t, d))

    def plain(y, w):
        picked = jnp.where(kept[..., None], y[row_of_pair], 0)
        return jnp.sum(jnp.sum(picked * w[..., None], axis=1) * g)

    def ours(y, w):
        return jnp.sum(moe._combine_rows(y, w, pair_of_row, n_live, order)
                       * g)

    np.testing.assert_allclose(ours(y, w), plain(y, w), rtol=1e-6)
    got, want = jax.grad(ours, (0, 1))(y, w), jax.grad(plain, (0, 1))(y, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)
    assert not np.asarray(got[0])[int(n_live):].any()
    assert not np.asarray(got[1])[~np.asarray(kept)].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_held_rows_is_the_gather_in_the_live_prefix_and_zeros_past_it(walk,
                                                                       dtype):
    """``x[token_of_row]`` to the bit in rows 0 .. ``n_live`` - 1, zeros in
    the padding (the parent had other tokens' rows there, which ``row_used``
    masked): whichever of the static prefixes holds the live rows."""
    t, held, d = 32, 4, 8
    chosen, rows, _ = walk_case(walk, t, held)
    n_live, _, _, pair_of_row, row_used = routing_of(chosen, rows)
    x = jax.random.normal(jax.random.PRNGKey(3), (t, d)).astype(dtype)
    token_of_row = pair_of_row // held
    got = jax.jit(moe._held_rows)(x, token_of_row, n_live, order_of(
        pair_of_row, n_live, t, held))
    assert got.shape == (rows, d) and got.dtype == dtype
    np.testing.assert_array_equal(
        got, jnp.where(row_used[:, None], x[token_of_row], 0))


@pytest.mark.parametrize("share, branch", [
    (0.0, 0), (0.25, 0), (0.375, 0), (0.39, 1), (0.5, 1), (0.52, 2),
    (1.0, 2)])
def test_the_gather_takes_the_shortest_prefix_that_holds_the_live_rows(
        monkeypatch, share, branch):
    """A buffer of 64 rows has the prefixes 24 (5/16 of it, up to a multiple
    of 8), 32 and 64: one gather of that many rows a branch, and the branch
    taken is the shortest that holds ``n_live``, at and either side of each
    boundary."""
    rows, t, d = 64, 16, 8
    x = jnp.arange(1, t * d + 1, dtype=jnp.float32).reshape(t, d)
    token_of_row = jnp.arange(rows) % t
    n_live = jnp.int32(round(share * rows))
    jaxpr = jax.make_jaxpr(moe._live_prefix)(x, token_of_row, n_live)
    cond, = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert [[v.aval.shape for e in b.eqns for v in e.outvars
             if e.primitive.name == "gather"]
            for b in cond.params["branches"]] == [[(24, d)], [(32, d)],
                                                  [(64, d)]]
    taken, switch = [], jax.lax.switch
    monkeypatch.setattr(jax.lax, "switch", lambda i, branches, *a: (
        taken.append(int(i)), switch(i, branches, *a))[1])
    got = moe._live_prefix(x, token_of_row, n_live)         # eager: concrete
    assert taken == [branch]
    np.testing.assert_array_equal(got, jnp.where(
        (jnp.arange(rows) < n_live)[:, None], x[token_of_row], 0))


def test_the_dispatchs_backward_is_ads():
    t, held, d, rows = 24, 4, 8, 40
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    chosen = jax.random.uniform(keys[0], (t, held)) < 0.4
    n_live, _, _, pair_of_row, row_used = routing_of(chosen, rows)
    x = jax.random.normal(keys[1], (t, d))
    # The padding's cotangent is whatever the grouped matmuls' transposes
    # leave there: it goes nowhere.
    g = jax.random.normal(keys[2], (rows, d))
    token_of_row = pair_of_row // held
    order = order_of(pair_of_row, n_live, t, held)

    def ours(x):
        return jnp.sum(moe._held_rows(x, token_of_row, n_live, order) * g)

    def plain(x):
        return jnp.sum(x[token_of_row] * jnp.where(row_used[:, None], g, 0))

    np.testing.assert_allclose(jax.grad(ours)(x), jax.grad(plain)(x),
                               atol=1e-6, rtol=1e-5)


# -- the five families' models -----------------------------------------------------

SDAR = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=12, n_layers=4, seq_len=16,
    n_experts=64, top_k=4, dtype=jnp.float32, dropless=True,
    tied_head=False, gated_experts=True, layer_pattern="*E",
    learned_positions=False, n_kv_heads=2, attn_head_dim=8, rope_theta=1e6,
    router_renormalise=True, head_qk_norm=True, diffusion_block=4,
    expert_buffer_factor=4.0, n_experts_held=4)
LAGUNA = tfm.TransformerConfig(
    vocab_size=128, d_model=32, n_heads=2, d_ff=24, n_layers=4, seq_len=48,
    n_experts=16, top_k=3, dtype=jnp.float32, dropless=True,
    tied_head=False, gated_experts=True, layer_pattern="WE",
    leading_pattern="*D", learned_positions=False, n_kv_heads=1,
    attn_head_dim=8, rope_theta=500000.0, rope_fraction=0.5,
    rope_yarn=(128.0, 16, 32.0, 1.0, 1.4852), attn_window=8, window_heads=3,
    window_rope_theta=10000.0, attn_gate=True, dense_ff=40,
    router_renormalise=True, router_scale=2.5, shared_expert_ff=24,
    expert_buffer_factor=4.0, n_experts_held=4)
NEMOTRON = tfm.TransformerConfig(
    vocab_size=128, d_model=32, n_heads=2, d_ff=24, n_layers=3, seq_len=48,
    n_experts=128, top_k=10, dtype=jnp.float32, remat=True, norm_eps=1e-5,
    dropless=True, tied_head=False, layer_pattern="EM*",
    learned_positions=False, n_kv_heads=1, attn_head_dim=8, ssm_heads=2,
    ssm_head_dim=4, ssm_groups=1, ssm_state=8, ssm_chunk=16,
    router_scoring="sigmoid", router_renormalise=True, router_scale=2.5,
    moe_latent=16, shared_expert_ff=40, expert_activation="relu2",
    expert_buffer_factor=4.0, n_experts_held=8)
OLMOE = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=12, n_layers=1, seq_len=16,
    n_experts=8, top_k=2, dtype=jnp.float32, dropless=True, tied_head=False,
    gated_experts=True, learned_positions=False, rope_theta=10000.0)
LFM2 = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=12, n_layers=10, seq_len=16,
    n_experts=24, top_k=4, dtype=jnp.float32, dropless=True, norm_eps=1e-5,
    tied_head=True, gated_experts=True, leading_pattern="CD",
    layer_pattern="*ECECECE", learned_positions=False, n_kv_heads=2,
    attn_head_dim=8, rope_theta=1e6, head_qk_norm=True,
    router_scoring="sigmoid", router_renormalise=True,
    router_renorm_eps=1e-6, dense_ff=40, conv_taps=3,
    expert_buffer_factor=4.0, n_experts_held=4)
SMALLTHINKER = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=20, n_layers=8, seq_len=16,
    n_experts=24, top_k=3, dtype=jnp.float32, dropless=True, norm_eps=1e-6,
    tied_head=False, gated_experts=True, expert_activation="relu",
    layer_pattern="*EWEWEWE", learned_positions=False, rope_theta=None,
    n_kv_heads=2, attn_head_dim=8, attn_window=5, window_rope_theta=1.5e6,
    router_renormalise=True, router_before_attention=True,
    expert_buffer_factor=4.0, n_experts_held=4)
FAMILIES = {"sdar": SDAR, "laguna": LAGUNA, "nemotron": NEMOTRON,
            "lfm2": LFM2, "smallthinker": SMALLTHINKER}


def one_device_mesh():
    return create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])


def loss_and_grads(cfg):
    params = tfm.init_params(jax.random.PRNGKey(5), cfg, PAR)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(11), cfg, 2)
    # traced anew a call: the helper is looked up at trace time
    return jax.jit(jax.value_and_grad(tfm.make_loss_fn(
        cfg, PAR, one_device_mesh())))(params, *batch)


def whole_buffer_gather(x, token_of_row, n_live):
    """``_live_prefix`` as the parent had it: every row of the buffer
    gathered, the padding's left to ``row_used``."""
    return x[token_of_row]


def pair_space_combine(y, weights, pair_of_row, n_live, order=None):
    """``_combine_rows`` as the plain sum over a token's kept pairs, one
    gathered row a (token, held expert) pair, for AD to differentiate: no
    hand-written backward (and no use for the rows' order by token)."""
    t, held = weights.shape
    rows = y.shape[0]
    at = jnp.where(jnp.arange(rows) < n_live, pair_of_row, t * held)
    row_of_pair = jnp.zeros((t * held,), jnp.int32).at[at].set(
        jnp.arange(rows), mode="drop").reshape(t, held)
    kept = jnp.zeros((t * held,), bool).at[at].set(
        True, mode="drop").reshape(t, held)
    picked = jnp.where(kept[..., None], y[row_of_pair], 0)
    return jnp.sum(picked.astype(jnp.float32) * weights[..., None], axis=1)


@jax.custom_vjp
def parents_combine_rows(y, weights, pair_of_row, n_live, order):
    return pair_space_combine(y, weights, pair_of_row, n_live)


def parents_combine_rows_fwd(y, weights, pair_of_row, n_live, order):
    return (pair_space_combine(y, weights, pair_of_row, n_live),
            (y, weights, pair_of_row, n_live))


def parents_combine_rows_bwd(res, g):
    """The parent's backward: ``g`` gathered for every row of the buffer
    into an (R, d) fp32 array, then the products and the dots over all R."""
    y, weights, pair_of_row, n_live = res
    g_rows = g[pair_of_row // weights.shape[1]]                  # (R, d) fp32
    live = jnp.arange(y.shape[0]) < n_live
    w_rows = jnp.where(live, weights.reshape(-1)[pair_of_row], 0)
    dots = jnp.sum(g_rows * y.astype(jnp.float32), axis=-1)
    dw = jnp.zeros((weights.size,), jnp.float32).at[
        jnp.where(live, pair_of_row, weights.size)].set(dots, mode="drop")
    return ((g_rows * w_rows[:, None]).astype(y.dtype),
            dw.reshape(weights.shape), None, None, None)


parents_combine_rows.defvjp(parents_combine_rows_fwd,
                            parents_combine_rows_bwd)


@pytest.mark.parametrize("family", sorted(set(FAMILIES) - {"smallthinker"}))
def test_a_familys_loss_and_gradients_are_the_pair_space_forms(monkeypatch,
                                                               family):
    """The whole model with the held path as shipped, and with the parents'
    forms in its three helpers' places: ``_token_sums`` as a gather of every
    (token, held expert) pair, ``_live_prefix`` as a gather of every row of
    the buffer, and the combine as the pair-space sum that AD differentiates.
    (Was ``test_sdar_layers.py::
    test_both_forms_of_the_held_paths_combine_give_one_answer``, for the one
    family and the combine's two backward forms.)"""
    row_space = loss_and_grads(FAMILIES[family])
    monkeypatch.setattr(moe, "_token_sums", pair_space_sums(
        FAMILIES[family].n_experts_held))
    monkeypatch.setattr(moe, "_live_prefix", whole_buffer_gather)
    monkeypatch.setattr(moe, "_combine_rows", pair_space_combine)
    pair_space = loss_and_grads(FAMILIES[family])
    assert np.isfinite(float(row_space[0]))
    for a, b in zip(jax.tree_util.tree_leaves(pair_space),
                    jax.tree_util.tree_leaves(row_space)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)


# -- the shape of the traced step ---------------------------------------------------

def every_value(jaxpr):
    """Every variable of a jaxpr, of its sub-jaxprs and of the custom-VJP
    rules it carries (a ``custom_vjp_call``'s forward and backward are
    functions until they are traced: traced here by differentiating)."""
    for eqn in jaxpr.eqns:
        yield from eqn.outvars
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from every_value(sub)
    yield from jaxpr.invars


def widest_rows(jaxpr, width):
    """Elements of the largest value made of rows ``width`` wide (the
    router's one-hot of (T, k, E) is larger than any and is no row)."""
    return max(int(np.prod(v.aval.shape)) for v in every_value(jaxpr.jaxpr)
               if getattr(v.aval, "shape", ())[-1:] == (width,))


SUMS = ("hvd_moe_token_sums_built_total",
        "held experts' sums of buffer rows into their tokens traced, by site",
        ("combine", "dispatch_bwd"))
KERNELS = ("hvd_moe_token_sum_kernels_built_total",
           "held experts' token sums traced as the Pallas kernel, by site",
           ("combine", "dispatch_bwd"))
GATHERS = ("hvd_moe_live_gathers_built_total",
           "held experts' row gathers over the buffer's live prefix traced, "
           "by site", ("rows", "combine_bwd"))


def built(site, counter=SUMS):
    return registry().counter(counter[0], counter[1], site=site).value


def built_by_site(counter):
    return {site: built(site, counter) for site in counter[2]}


def expert_layer(cfg):
    """value_and_grad of one "E" block (an MLP block for OLMoE's) in its
    input and its parameters."""
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, PAR)["layers"]
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, cfg.seq_len, cfg.d_model)).astype(cfg.dtype)
    if cfg.layer_pattern is None:
        lp = {k: v[0, 0] for k, v in params.items()}
        fn = tfm._mlp_block
    else:
        lp = {k: v[0, 0, 0] for k, v in params["moe"].items()}
        fn = tfm._expert_mixer
    return jax.make_jaxpr(jax.value_and_grad(
        lambda lp, x: jnp.sum(jnp.sin(fn(cfg, lp, x)[0])), (0, 1)))(lp, x)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_no_value_has_a_row_for_every_token_and_held_expert(monkeypatch,
                                                            family):
    """Walked through value_and_grad of one expert layer: forward, the
    custom-VJP rules' backward, the loops' and the kernels' bodies.  The
    pair-space arrays PR 40's parent had, (T, held, d) and its flat (T x held,
    d), would be the largest values of the layer made of rows d wide, and a
    gather into (token, choice) space would make (T x top_k, d); none reaches
    either size, in any family (one path for every row width: no combine is
    exempt) — the second where the buffer itself is smaller than that
    (Laguna's preset, as SmallThinker's cell, holds a quarter of the experts:
    4 x the mean is then a row a (token, choice) pair).  The rows are added
    by the kernel, in chunks of 8 here so that the list is the buffer's
    length, and nothing scatter-adds a row d wide."""
    force_tiling(monkeypatch, 8, 8)
    cfg = FAMILIES[family]
    before = built_by_site(SUMS)
    jaxpr = expert_layer(cfg)
    t = 2 * cfg.seq_len                       # the layer's own input
    width = cfg.moe_latent or cfg.d_model
    rows = moe.held_row_buffer(t, cfg.top_k, cfg.n_experts_held,
                               cfg.n_experts, cfg.expert_buffer_factor)
    assert (rows < t * cfg.top_k) == (family != "laguna")
    pair_space = t * width * min(
        [cfg.n_experts_held] + [cfg.top_k] * (rows < t * cfg.top_k))
    largest = widest_rows(jaxpr, width)
    assert 0 < largest < pair_space, (largest, pair_space)
    assert str(jaxpr).count(f"name={token_sum.KERNEL}") >= 2
    assert not [eqn for eqn, _ in eqns_and_where(jaxpr.jaxpr)
                if eqn.primitive.name == "scatter-add"
                and eqn.outvars[0].aval.shape[-1:] == (width,)]
    assert {site: built(site) - n for site, n in before.items()} == {
        "combine": 1, "dispatch_bwd": 1}


def test_the_walk_would_see_the_parents_pair_space_gather(monkeypatch):
    """The same walk over the same layer with the parent's form in the
    helper's place finds a value of the pair-space size: the structural test
    can fail."""
    cfg = SDAR
    monkeypatch.setattr(moe, "_token_sums",
                        pair_space_sums(cfg.n_experts_held))
    jaxpr = expert_layer(cfg)
    pair_space = 2 * cfg.seq_len * cfg.n_experts_held * cfg.d_model
    assert widest_rows(jaxpr, cfg.d_model) >= pair_space


@pytest.mark.parametrize("counter", [SUMS, KERNELS, GATHERS],
                         ids=["sums", "kernels", "gathers"])
def test_a_layer_that_holds_every_expert_builds_no_token_sum(counter):
    """No buffer, no live prefix: 0 / 0 of each counter."""
    before = built_by_site(counter)
    jaxpr = expert_layer(OLMOE)
    assert "ragged_dot" in str(jaxpr)
    assert built_by_site(counter) == before


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_held_layer_builds_one_live_gather_a_site(family):
    """``hvd_moe_live_gathers_built_total``: one ``rows`` for the traced
    forward of a held layer and one ``combine_bwd`` for its traced
    backward."""
    before = built_by_site(GATHERS)
    expert_layer(FAMILIES[family])
    assert {site: n - before[site] for site, n in built_by_site(
        GATHERS).items()} == {"rows": 1, "combine_bwd": 1}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_held_layers_sums_are_the_kernel_once_a_site(family):
    """``hvd_moe_token_sum_kernels_built_total``: one ``combine`` for the
    traced forward of a held layer and one ``dispatch_bwd`` for its traced
    backward, as ``hvd_moe_token_sums_built_total`` counts the sums: every
    sum is the kernel."""
    before = built_by_site(KERNELS), built_by_site(SUMS)
    expert_layer(FAMILIES[family])
    for counter, was in zip((KERNELS, SUMS), before):
        assert {site: n - was[site] for site, n in built_by_site(
            counter).items()} == {"combine": 1, "dispatch_bwd": 1}


# -- no gather of the whole buffer, no fp32 array of its size ----------------------

def eqns_and_where(jaxpr, inside=()):
    """Every equation of a jaxpr and of its sub-jaxprs, with the ``while``s
    and ``cond``s it lies inside."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns_and_where(sub, inside + (
                (eqn.primitive.name,) if eqn.primitive.name in (
                    "while", "cond") else ()))


def whole_buffer_values(cfg):
    """Walked through value_and_grad of one expert layer in bf16, 8 rows a
    trip of the loops: (the gathers that yield the buffer's (R, width) in
    no loop's body and no branch of a ``cond`` — the switch's last branch,
    for a buffer over half full, is such a gather — and the fp32 values of
    R x width elements, wherever they lie)."""
    t = 2 * cfg.seq_len
    width = cfg.moe_latent or cfg.d_model
    rows = moe.held_row_buffer(t, cfg.top_k, cfg.n_experts_held,
                               cfg.n_experts, cfg.expert_buffer_factor)
    # or an array of the tokens', of the pairs', of the router's or of the
    # experts' hidden width would pass for the buffer's
    assert rows not in (t, t * cfg.n_experts_held, cfg.n_experts)
    assert width != cfg.d_ff
    jaxpr = expert_layer(cfg._replace(dtype=jnp.bfloat16))
    gathers, fp32 = [], []
    for eqn, inside in eqns_and_where(jaxpr.jaxpr):
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            if (eqn.primitive.name == "gather" and not inside
                    and shape == (rows, width)):
                gathers.append(eqn)
            if (int(np.prod(shape)) == rows * width and shape[-1:] == (width,)
                    and v.aval.dtype == jnp.float32):
                fp32.append(eqn)
    return gathers, fp32


# SDAR's small preset holds 4 of 64 at top-4: its buffer would have as many
# rows as the layer has tokens (and at 8 held as the router has experts).
WALKED = dict(FAMILIES, sdar=SDAR._replace(n_experts_held=12))


@pytest.fixture
def eight_rows_a_trip(monkeypatch):
    force_tiling(monkeypatch, 8, 8)
    monkeypatch.setattr(moe, "_GATHER_CHUNK", 8)


@pytest.mark.parametrize("family", sorted(WALKED))
def test_no_gather_yields_the_whole_buffer_and_no_fp32_array_has_its_size(
        eight_rows_a_trip, family):
    """The three gathers walk the live prefix: the forward's (and the
    recompute's) is of a static prefix inside a ``cond``'s branch, the
    combine's backward's of a chunk inside a loop's body, in fp32 a chunk at
    a time."""
    gathers, fp32 = whole_buffer_values(WALKED[family])
    assert not gathers, gathers
    assert not fp32, fp32


@pytest.mark.parametrize("helper, parents, makes_fp32", [
    ("_live_prefix", whole_buffer_gather, False),
    ("_combine_rows", parents_combine_rows, True)])
def test_the_walk_would_see_the_parents_whole_buffer_gathers(
        monkeypatch, eight_rows_a_trip, helper, parents, makes_fp32):
    """The same walk with the parent's form put back finds its gather of
    every row of the buffer, and for the combine's backward the (R, d) fp32
    array it made: the structural test can fail."""
    monkeypatch.setattr(moe, helper, parents)
    gathers, fp32 = whole_buffer_values(WALKED["lfm2"])
    assert gathers
    assert bool(fp32) == makes_fp32
