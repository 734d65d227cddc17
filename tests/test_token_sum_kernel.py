"""The Pallas kernel ``hvd_moe_token_sum`` alone (``ops/token_sum.py``), in the
interpreter: rows listed by token are summed into their tokens as a
scatter-added sum of the same fp32 products would, at the walk's tile and
chunk edges, under a walk made for a longer list, with a weight bf16 cannot
hold, and the walk's own invariants.  (``tests/test_moe_token_sums.py`` holds
the held path that calls it.)"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import token_sum


# tokens, rows of the list, live rows, tokens a tile, rows a chunk: the live
# rows end inside a chunk and inside a tile's run; on a chunk's edge; a tile
# whose rows fill three chunks; one tile and one chunk; tokens that are no
# multiple of the tile and rows that are none of the chunk (both padded); a
# list with no live row; a list all live
EDGES = {"inside_both": (64, 96, 45, 8, 16), "chunk_edge": (64, 96, 48, 8, 16),
         "tile_over_chunks": (32, 128, 100, 32, 8), "one_step": (16, 24, 9,
         16, 24), "padded": (50, 37, 30, 16, 8), "empty": (64, 96, 0, 8, 16),
         "all_live": (64, 96, 96, 16, 16)}


def listed_rows(t, n, n_live, d, dtype, key=0, most=4):
    """``n_live`` rows listed by token, at most ``most`` a token, zeros and
    the key ``t`` past them."""
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    token = jnp.sort(jnp.repeat(jnp.arange(t), most)[
        jax.random.permutation(ks[0], t * most)[:n_live]])
    token = jnp.concatenate([token, jnp.full((n - n_live,), t)])
    live = jnp.arange(n) < n_live
    rows = jnp.where(live[:, None], jax.random.normal(ks[1], (n, d)), 0)
    scale = jax.random.uniform(ks[2], (n,), minval=0.5, maxval=2.0)
    return rows.astype(dtype), token.astype(jnp.int32), scale


def segment_sums(rows, token, scale, t):
    z = rows.astype(jnp.float32)
    if scale is not None:
        z = z * scale[:, None]
    return jax.ops.segment_sum(z, token, num_segments=t + 1)[:t]


@pytest.mark.parametrize("scaled", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("edge", sorted(EDGES))
def test_the_kernel_alone_at_its_tile_and_chunk_edges(edge, dtype, scaled):
    """``ops/token_sum.token_sum`` in the interpreter against a scatter-added
    sum of the same fp32 products; a token with no row is zeros, the padding
    goes nowhere.  (The cases that held the deleted loop's trips.)"""
    t, n, n_live, tile, chunk = EDGES[edge]
    rows, token, scale = listed_rows(t, n, n_live, 8, dtype, key=n_live)
    scale = scale if scaled else None
    got = jax.jit(lambda rows, token: token_sum.token_sum(
        rows, token, scale, token_sum.walk(token, t, tile, chunk), t,
        tile=tile, chunk=chunk, interpret=True))(rows, token)
    assert got.shape == (t, 8) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, segment_sums(rows, token, scale, t),
                               atol=1e-6, rtol=0)
    no_row = np.setdiff1d(np.arange(t), np.asarray(token))
    assert not np.asarray(got)[no_row].any()


@pytest.mark.parametrize("prefix", [48, 64, 96])
def test_a_walk_of_the_whole_list_serves_a_prefix_that_holds_the_live_rows(
        prefix):
    """``_token_sums`` lays the walk out once, for the whole list, and each of
    its static prefixes runs the kernel under it: 45 live rows of 96 in
    chunks of 16, a prefix of three, four or all six chunks."""
    t, n, n_live, tile, chunk = EDGES["inside_both"]
    rows, token, scale = listed_rows(t, n, n_live, 8, jnp.bfloat16)
    got = token_sum.token_sum(
        rows[:prefix], token[:prefix], scale[:prefix],
        token_sum.walk(token, t, tile, chunk), t, tile=tile, chunk=chunk,
        interpret=True)
    np.testing.assert_allclose(got, segment_sums(rows, token, scale, t),
                               atol=1e-6, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_weight_that_bf16_cannot_hold_comes_through_exactly(dtype):
    """1 + 2^-12 times rows of a few bits: the products are exact in fp32 and
    so is the kernel's sum, where one bf16 pass of the weights would give the
    rows times 1."""
    t, n, d = 16, 32, 128
    token = jnp.arange(n, dtype=jnp.int32) // 2
    rows = (jnp.arange(n * d).reshape(n, d) % 7 + 1).astype(dtype)
    scale = jnp.full((n,), 1 + 2.0 ** -12, jnp.float32)
    got = token_sum.token_sum(rows, token, scale, token_sum.walk(
        token, t, 8, 16), t, tile=8, chunk=16, interpret=True)
    want = (rows.astype(jnp.float32) * scale[:, None]).reshape(
        t, 2, d).sum(1)
    np.testing.assert_array_equal(got, want)
    assert np.asarray(got != rows.astype(jnp.float32).reshape(
        t, 2, d).sum(1)).all()


def test_the_weights_parts_add_up_to_them():
    """``_three_bf16``: the three bf16 arrays the kernel multiplies bf16 rows
    by add up to the fp32 weights to the bit."""
    w = jax.random.uniform(jax.random.PRNGKey(0), (64, 64), minval=-4,
                           maxval=4) * 2.0 ** jnp.arange(-20, 44)
    hi, mid, lo = token_sum._three_bf16(w)
    assert {p.dtype for p in (hi, mid, lo)} == {jnp.dtype(jnp.bfloat16)}
    np.testing.assert_array_equal(
        hi.astype(jnp.float32) + mid.astype(jnp.float32)
        + lo.astype(jnp.float32), w)


@pytest.mark.parametrize("edge", sorted(EDGES))
def test_the_walk_visits_every_tile_once_and_every_live_chunk(edge):
    """``walk``: tile after tile without a gap, a tile's steps on the
    chunks that hold its rows and none on a chunk past the live rows, no
    more steps than the grid has, and the dead steps on the last live step's
    blocks."""
    t, n, n_live, tile, chunk = EDGES[edge]
    _, token, _ = listed_rows(t, n, n_live, 8, jnp.float32, key=n_live)
    n_tiles, n_chunks = -(-t // tile), -(-n // chunk)
    walk, live = token_sum.walk(token, t, tile, chunk)
    walk, live = np.asarray(walk), int(live[0])
    assert walk[1].max() <= max(n_live - 1, 0) // chunk
    assert walk.shape == (2, n_tiles + n_chunks - 1) and live <= walk.shape[1]
    tiles, chunks = walk[0, :live], walk[1, :live]
    assert sorted(set(tiles)) == list(range(n_tiles))
    assert (np.diff(tiles) >= 0).all() and (np.diff(chunks) >= 0).all()
    for j, tok in enumerate(np.asarray(token)):
        if tok < t:
            assert ((tiles == tok // tile) & (chunks == j // chunk)).sum() == 1
    assert (walk[:, live:] == walk[:, live - 1:live]).all()
