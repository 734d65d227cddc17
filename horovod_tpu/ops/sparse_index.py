"""Learned sparse attention's indexer: the scores by which a query ranks its
keys, the exact choice of the ``topk`` highest, and the loss that trains the
scorer (DeepSeek-V3.2-Exp's lightning indexer and its sparse training stage).

With ``qi`` (B, S, J, Di) the indexer's J query heads, ``ki`` (B, S, Di) its
one key head and ``w`` (B, S, J) fp32 a weight a query and head, the score of
key s for query t is

    I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s]),      s <= t,

the dots accumulated in fp32 from the operands' own type, everything after
them fp32.  ``select`` gives each query the ``topk`` keys at or before it
with the highest score (all of them while there are no more than ``topk``),
ties to the later key: exactly the ``topk`` largest of the scores computed
here, no approximation and no choice by block.  ``index_loss`` is
``mean_{b, t} KL(pbar[t, .] || softmax_{s in S_t} I[t, s])`` where ``pbar``
is the mean over the main attention's heads of their probabilities over the
chosen keys, taken from the attention's saved ``lse`` and held constant.

Nothing (S, S) is made here but the result of ``select``, an int8 array a
layer, key-major (B, S keys, S queries) as the flash kernels' transposed
score tile is (``ops/flash_attention.flash_attention_selected``): the scores
are made, ranked and dropped a tile of ``Q_TILE`` queries at a time, (Q_TILE,
S) fp32; the loss and its backward take such a tile against its causal keys
``K_CHUNK`` at a time, and no chunk past the tile's last query.

The choice is exact without a sort.  A row's fp32 scores are mapped to
unsigned integers of the same order (``_ordered``); the ``topk``-th largest
is built bit by bit from the top, 32 counts of ``key >= candidate`` over the
row (``_kth_largest``); the keys above it are chosen and, of those equal to
it, the latest, as many as are still missing, whose first index is built the
same way from counts (``_latest``: only where a tile has such a tie at all).
``lax.top_k`` at 2,048 of 16,384 is a sort of every row.

Under a layer checkpoint the selection runs once: the visibility array and
the loss's row statistic carry names (``SAVED_VISIBLE``, ``SAVED_INDEX_LSE``)
that ``parallel/ring_attention.checkpoint_keeping_attention`` saves, so the
backward's recompute of the layer remakes neither.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..utils.profiler import INDEX_SCOPES, scope

# ``checkpoint_name``s: the int8 (B, S, S) visibility of a layer, and the
# (B, 2, S) fp32 row statistics of the indexer's loss (the log-normaliser of
# its scores over the chosen keys, the sum of the heads' mean probability).
SAVED_VISIBLE = "sparse_attention_visible"
SAVED_INDEX_LSE = "sparse_attention_index_lse"

# Queries a tile: (Q_TILE, S) fp32 scores and (J, Q_TILE, S) fp32 dots are
# the selection's transients.  The loss meets a tile's causal keys K_CHUNK
# at a time: (J, Q_TILE, K_CHUNK) and (heads a K / V head, Q_TILE, K_CHUNK).
Q_TILE = 512
K_CHUNK = 2048
_NEG_INF = -1e30


def tile_scores(qi_t, w_t, ki):
    """I for a tile of queries against every key, causality apart: ``qi_t``
    (T, J, Di), ``w_t`` (T, J) fp32, ``ki`` (S, Di) -> (T, S) fp32."""
    dots = jnp.einsum("tjd,sd->jts", qi_t, ki,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w_t.T[:, :, None], axis=0)


def _ordered(x):
    """fp32 -> uint32 of the same order (a positive's sign bit set, a
    negative's bits flipped); both zeros one key; never 0 for a number."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    return jnp.where(x == 0, jnp.uint32(1 << 31), keys)


def _kth_largest(keys, k: int):
    """The largest v with ``count(keys >= v) >= k`` a row, (T,) uint32: the
    k-th largest key, built from the top bit down."""
    def bit(i, v):
        candidate = v | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        count = jnp.sum(keys >= candidate[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= k, candidate, v)
    return lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[0], jnp.uint32))


def _latest(tied, need):
    """The first index of the ``need`` last ``tied`` places of a row, (T,)
    int32: the largest p with ``count(tied & index >= p) >= need``."""
    s = tied.shape[-1]
    at = jnp.arange(s, dtype=jnp.int32)
    bits = max(s - 1, 1).bit_length()

    def bit(i, p):
        candidate = p | (jnp.int32(1 << (bits - 1)) >> i)
        count = jnp.sum(tied & (at >= candidate[:, None]), axis=-1,
                        dtype=jnp.int32)
        return jnp.where(count >= need, candidate, p)
    return lax.fori_loop(0, bits, bit, jnp.zeros(tied.shape[0], jnp.int32))


def select_tile(scores, first: jax.Array, topk: int):
    """(T, S) bool: for the queries ``first .. first + T - 1``, whether each
    key is one of the ``topk`` highest-scoring at or before the query, ties
    to the later key."""
    t, s = scores.shape
    at = jnp.arange(s, dtype=jnp.int32)[None, :]
    causal = at <= first + jnp.arange(t, dtype=jnp.int32)[:, None]
    keys = jnp.where(causal, _ordered(scores), jnp.uint32(0))
    kth = _kth_largest(keys, topk)[:, None]
    at_or_above = keys >= kth
    tied_rows = jnp.sum(at_or_above, axis=-1, dtype=jnp.int32) > topk

    def break_ties(_):
        above = keys > kth
        tied = causal & (keys == kth)
        need = topk - jnp.sum(above, axis=-1, dtype=jnp.int32)
        return above | (tied & (at >= _latest(tied, need)[:, None]))

    # A row short of topk keys has kth 0 and every key above it.
    chosen = lax.cond(jnp.any(tied_rows & (kth[:, 0] > 0)), break_ties,
                      lambda _: at_or_above, None)
    return causal & chosen


def _tiles(x, tile: int):
    """(B, S, ...) -> (B, S // tile, tile, ...)."""
    return x.reshape(x.shape[0], x.shape[1] // tile, tile, *x.shape[2:])


def _tile_of(s: int) -> int:
    tile = min(Q_TILE, s)
    if s % tile:
        raise ValueError(f"{s} positions are not whole tiles of {tile}")
    return tile


def select(qi, ki, w, topk: int):
    """The visibility of a selected call, (B, S keys, S queries) int8: 1
    where the query chose the key.  ``qi`` (B, S, J, Di), ``ki`` (B, S,
    Di), ``w`` (B, S, J) fp32.  No gradient: the choice is discrete."""
    qi, ki, w = (lax.stop_gradient(x) for x in (qi, ki, w))
    b, s = qi.shape[:2]
    tile = _tile_of(s)

    def sequence(args):
        qi_b, w_b, ki_b = args

        def one(tile_args):
            i, qi_t, w_t = tile_args
            scores = tile_scores(qi_t, w_t, ki_b)
            with scope(INDEX_SCOPES[1]):
                return select_tile(scores, i * tile, topk).astype(jnp.int8)

        chosen = lax.map(one, (jnp.arange(s // tile, dtype=jnp.int32),
                               qi_b, w_b))                  # (tiles, T, S)
        return chosen.reshape(s, s).T
    visible_t = lax.map(sequence, (_tiles(qi, tile), _tiles(w, tile), ki))
    return checkpoint_name(visible_t, SAVED_VISIBLE)


# ---------------------------------------------------------------------------
# The indexer's loss
# ---------------------------------------------------------------------------

def _pbar(q_t, k, lse_t, scale: float):
    """The heads' mean probability of every key for a tile of queries, the
    visibility apart: ``exp(q . k * scale - lse)`` summed over the heads a
    K / V head at a time.  ``q_t`` (T, Hkv, G, D), ``k`` (S, Hkv, D),
    ``lse_t`` (Hkv, G, T) -> (T, S) fp32."""
    def group(total, args):
        q_g, k_g, lse_g = args               # (G, T, D), (S, D), (G, T)
        scores = jnp.einsum("gtd,sd->gts", q_g, k_g,
                            preferred_element_type=jnp.float32) * scale
        return total + jnp.sum(jnp.exp(scores - lse_g[..., None]), 0), None

    t, hkv, g, _ = q_t.shape
    total, _ = lax.scan(group, jnp.zeros((t, k.shape[0]), jnp.float32),
                        (q_t.transpose(1, 2, 0, 3), k.transpose(1, 0, 2),
                         lse_t))
    return total / (hkv * g)


def _loss_tiles(qi, w, q, lse, hkv: int, tile: int):
    """What one sequence's tiles of queries bring, as ``lax.scan``'s xs:
    (the tile's index, its indexer queries, weights, main queries grouped by
    K / V head, their lse)."""
    s = q.shape[0]
    n = s // tile
    q = q.reshape(n, tile, hkv, q.shape[1] // hkv, q.shape[2])
    lse = lse.reshape(hkv, -1, n, tile).transpose(2, 0, 1, 3)
    return (jnp.arange(n, dtype=jnp.int32), qi.reshape(n, tile, *qi.shape[1:]),
            w.reshape(n, tile, -1), q, lse)


def _chunk_of(s: int) -> int:
    chunk = min(K_CHUNK, s)
    if s % chunk:
        raise ValueError(f"{s} positions are not whole chunks of {chunk}")
    return chunk


def _over_live_chunks(i, tile: int, chunk: int, ki, k, visible, body, carry):
    """``body(first key, ki's chunk, k's chunk, the (T, C) bool visibility,
    carry) -> carry`` over the chunks of keys that hold a key at or before
    the last query of tile ``i``, in order: a tile of queries meets the
    causal part of the sequence and no more."""
    def step(c, carry):
        at = c * chunk
        seen = lax.dynamic_slice(visible, (at, i * tile), (chunk, tile)).T
        return body(at, lax.dynamic_slice_in_dim(ki, at, chunk),
                    lax.dynamic_slice_in_dim(k, at, chunk), seen != 0, carry)
    return lax.fori_loop(0, ((i + 1) * tile + chunk - 1) // chunk, step,
                         carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def index_loss(qi, ki, w, q, k, lse, visible_t, scale: float):
    """``mean_{b, t} KL(pbar[t, .] || softmax_{s in S_t} I[t, s])``, a
    scalar, differentiable in ``qi``, ``ki`` and ``w`` alone.  ``q`` (B, S,
    H, D) and ``k`` (B, S, Hkv, D) are the main attention's operands as its
    kernels took them (K before it is repeated to the query heads), ``lse``
    (B, H, S) what its forward saved, ``visible_t`` ``select``'s result.

    A tile of ``Q_TILE`` queries at a time against its causal keys
    ``K_CHUNK`` at a time, the softmax's normaliser carried across the
    chunks as a running maximum and sum: with a = sum pbar log pbar, b = sum
    pbar I and p = sum pbar over a query's chosen keys and logz the
    log-normaliser of I over them, its divergence is a - b + logz p."""
    return _index_loss_fwd(qi, ki, w, q, k, lse, visible_t, scale)[0]


def _index_loss_fwd(qi, ki, w, q, k, lse, visible_t, scale):
    b, s = qi.shape[:2]
    tile, chunk = _tile_of(s), _chunk_of(s)

    def sequence(args):
        qi_b, ki_b, w_b, q_b, k_b, lse_b, visible_b = args

        def one(total, xs):
            i, qi_t, w_t, q_t, lse_t = xs

            def chunk_sums(_at, ki_c, k_c, seen, carry):
                m, l, a, bp, p = carry
                scores = tile_scores(qi_t, w_t, ki_c)
                pbar = jnp.where(seen, _pbar(q_t, k_c, lse_t, scale), 0.0)
                m_new = jnp.maximum(m, jnp.max(
                    jnp.where(seen, scores, _NEG_INF), axis=-1))
                grown = jnp.where(seen, jnp.exp(scores - m_new[:, None]), 0.0)
                return (m_new, l * jnp.exp(m - m_new) + jnp.sum(grown, -1),
                        a + jnp.sum(jax.scipy.special.xlogy(pbar, pbar), -1),
                        bp + jnp.sum(pbar * jnp.where(seen, scores, 0.0), -1),
                        p + jnp.sum(pbar, -1))

            zero = jnp.zeros(tile, jnp.float32)
            m, l, a, bp, p = _over_live_chunks(
                i, tile, chunk, ki_b, k_b, visible_b, chunk_sums,
                (zero + _NEG_INF, zero, zero, zero, zero))
            logz = m + jnp.log(l)
            return total + jnp.sum(a - bp + logz * p), jnp.stack([logz, p])

        total, rows = lax.scan(one, jnp.float32(0.0), _loss_tiles(
            qi_b, w_b, q_b, lse_b, k_b.shape[1], tile))
        return total, rows.transpose(1, 0, 2).reshape(2, s)

    with scope(INDEX_SCOPES[2]):
        totals, rows = lax.map(sequence, (qi, ki, w, q, k, lse, visible_t))
        rows = checkpoint_name(rows, SAVED_INDEX_LSE)
        return (jnp.sum(totals) / (b * s),
                (qi, ki, w, q, k, lse, visible_t, rows))


def _index_loss_bwd(scale, res, g):
    """d loss / d I[t, s] = (softmax_S(I)[t, s] * sum pbar[t] - pbar[t, s])
    / (B S) on the chosen keys, from the forward's two row statistics (the
    log-normaliser and the sum of pbar), pulled back through ``tile_scores``
    a tile of queries and a chunk of keys at a time; the key head's
    cotangent is summed into its rows over a sequence's tiles."""
    qi, ki, w, q, k, lse, visible_t, rows = res
    b, s = qi.shape[:2]
    tile, chunk = _tile_of(s), _chunk_of(s)

    def sequence(args):
        qi_b, ki_b, w_b, q_b, k_b, lse_b, visible_b, rows_b = args

        def one(d_ki, xs):
            (i, qi_t, w_t, q_t, lse_t), (logz, p) = xs

            def chunk_pull(at, ki_c, k_c, seen, carry):
                d_ki, d_qi, d_w = carry
                scores, pull = jax.vjp(tile_scores, qi_t, w_t, ki_c)
                pbar = jnp.where(seen, _pbar(q_t, k_c, lse_t, scale), 0.0)
                soft = jnp.where(seen, jnp.exp(scores - logz[:, None]), 0.0)
                d_q, d_wc, d_k = pull((soft * p[:, None] - pbar)
                                      * (g / (b * s)))
                d_k = d_k + lax.dynamic_slice_in_dim(d_ki, at, chunk)
                return (lax.dynamic_update_slice_in_dim(d_ki, d_k, at, 0),
                        d_qi + d_q, d_w + d_wc)

            d_ki, d_qi, d_w = _over_live_chunks(
                i, tile, chunk, ki_b, k_b, visible_b, chunk_pull,
                (d_ki, jnp.zeros(qi_t.shape, jnp.float32),
                 jnp.zeros(w_t.shape, jnp.float32)))
            return d_ki, (d_qi.astype(qi_t.dtype), d_w)

        d_ki, (d_qi, d_w) = lax.scan(
            one, jnp.zeros(ki_b.shape, jnp.float32),
            (_loss_tiles(qi_b, w_b, q_b, lse_b, k_b.shape[1], tile),
             tuple(r.reshape(-1, tile) for r in rows_b)))
        return (d_qi.reshape(qi_b.shape), d_ki.astype(ki_b.dtype),
                d_w.reshape(w_b.shape))

    with scope(INDEX_SCOPES[2]):
        d_qi, d_ki, d_w = lax.map(
            sequence, (qi, ki, w, q, k, lse, visible_t, rows))
    return (d_qi, d_ki, d_w, jnp.zeros_like(q), jnp.zeros_like(k),
            jnp.zeros_like(lse),
            np.zeros(visible_t.shape, dtype=jax.dtypes.float0))


index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)
