"""Tensor (model) parallel building blocks — Megatron-style column/row
parallel projections over a mesh axis, for use inside ``shard_map``.

The reference framework is data-parallel only (SURVEY.md §2.3); tensor
parallelism is part of this framework's TPU-native scope.  The math:

* column-parallel: ``Y_shard = X @ W[:, shard]`` — no communication; the
  activation comes out feature-sharded.
* row-parallel: ``Y = psum_over_axis(X_shard @ W[shard, :])`` — one psum
  (or reduce_scatter when the consumer is sequence-sharded, the
  Megatron-SP fusion).

The ring.  Over P > 1 members the sequence-parallel gather in front of a
column-parallel matmul and the scatter behind a row-parallel one are P - 1
hops of ``lax.ppermute`` to the next member, each hop cut into pieces: a
gather multiplies the pieces it holds while the next chunk's arrive, a
scatter adds an arriving piece into a later piece's product and sends it on.
XLA runs a collective-permute beside independent matmuls (it runs an
all-gather or a reduce-scatter alone), so the transfers hide behind the
matmuls they feed; at P = 1 the calls are the plain collectives'.

Weights are stored pre-sharded (each member holds only its shard), so the
framework never materializes the full matrix — FSDP-style memory scaling on
top of TP.
"""

from __future__ import annotations

import math
from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..compat import axis_size
from ..metrics.registry import registry

# What one ring hop is weighed against: matmul FLOPs the chip does in the time
# its link moves one byte to the ring neighbour.  TPU v5e: ~85 % of 197
# TFLOP/s over 42 GB/s, one ``ppermute`` of bf16[10,4096,1024] between ``mp``
# peers taking 1.995 ms (PERF.md section 6, PR 37).
_FLOPS_PER_WIRE_BYTE = 4000.0
# A hop is cut into at most this many pieces, none lighter than this on the
# wire: a piece costs a launch, a DMA and a matmul too thin to fill the MXU.
_MAX_PIECES = 4
_MIN_PIECE_BYTES = 256 * 1024

# The (lo, hi) sequence rows of one piece within its chunk.
_Bounds = Tuple[Tuple[int, int], ...]


def column_parallel(x: jax.Array, w_shard: jax.Array,
                    b_shard: Optional[jax.Array] = None) -> jax.Array:
    """(..., d_in) @ (d_in, d_out/P) -> (..., d_out/P); no communication."""
    y = jnp.einsum("...i,io->...o", x, w_shard)
    if b_shard is not None:
        y = y + b_shard
    return y


class RingRows(NamedTuple):
    """The rows of a gathered sequence as a member meets them on the ring,
    not in sequence order: ``parts[hop * k + i]`` is piece i of k of the
    chunk ``hop`` hops back.  What a tokenwise consumer maps over
    (``jax.tree_util.tree_map``) and :func:`row_parallel` scatters back."""
    parts: Tuple[jax.Array, ...]


def row_parallel(x_shard: Union[jax.Array, RingRows], w_shard: jax.Array,
                 axis_name: str, b: Optional[jax.Array] = None,
                 scatter_sequence: bool = False) -> jax.Array:
    """(..., d_in/P) @ (d_in/P, d_out) -> psum -> (..., d_out); ``w_shard``
    is cast to ``x_shard``'s type.

    With ``scatter_sequence=True`` the psum becomes a reduce_scatter over the
    sequence dimension (dim -2), returning a sequence-sharded activation —
    the Megatron sequence-parallel fusion that halves the bytes on the wire.
    Over more than one member that reduce_scatter is the ring of the module
    docstring; ``x_shard`` may then be the :class:`RingRows` of
    :func:`gather_column_parallel_ring`.
    """
    ring = isinstance(x_shard, RingRows)
    if ring and not scatter_sequence:
        raise ValueError("RingRows are rows of a sequence-sharded stream: "
                         "scatter_sequence=True")
    p = axis_size(axis_name)
    d_in, d_out = w_shard.shape
    if scatter_sequence and p > 1:
        if ring:
            parts = x_shard.parts
        else:
            seq = x_shard.ndim - 2
            s_loc = x_shard.shape[seq] // p
            rows = math.prod(x_shard.shape[:seq]) * s_loc
            parts = _take(x_shard, axis_name, _pieces(
                s_loc, rows * d_out * x_shard.dtype.itemsize,
                2.0 * rows * d_in * d_out, fused_add=True), seq)
        y = jnp.concatenate(_ring_matmul_scatter(
            parts, w_shard.astype(parts[0].dtype), axis_name), axis=-2)
    else:
        if ring:
            (x_shard,) = x_shard.parts
        partial_sum = jnp.einsum("...i,io->...o", x_shard,
                                 w_shard.astype(x_shard.dtype))
        if scatter_sequence:
            y = lax.psum_scatter(partial_sum, axis_name,
                                 scatter_dimension=partial_sum.ndim - 2,
                                 tiled=True)
        else:
            y = lax.psum(partial_sum, axis_name)
    if b is not None:
        y = y + b
    return y


def qkv_slabs(wqkv: jax.Array, head_dim: int) -> Tuple[jax.Array, ...]:
    """The q, k and v weights of a fused projection, each (d_in,
    heads * head_dim) with heads in order.  ``wqkv`` is stored (d_in, heads *
    3 * head_dim), heads outermost and q, k, v inside a head, so that an
    ``mp`` shard of its columns is a slice of whole heads; here (a member's
    shard of) it is reordered to [q | k | v] columns, a transpose the size of
    the weight, and cut in three.  A product with a slab is (..., S, heads *
    head_dim) in rows, as the attention kernels read it: no activation holds
    q, k and v interleaved head by head, which XLA would write with S minor
    (a minor dimension of 64 fills half a 128-lane tile) and every consumer
    pay a transposing copy for."""
    d_in = wqkv.shape[0]
    slabs = wqkv.reshape(d_in, -1, 3, head_dim).swapaxes(1, 2)
    return tuple(slabs[:, i].reshape(d_in, -1) for i in range(3))


def gather_column_parallel(x: jax.Array,
                           w_shard: Union[jax.Array, Sequence[jax.Array]],
                           axis_name: str,
                           b_shard: Optional[jax.Array] = None):
    """``column_parallel(gather_sequence(x), w_shard)`` for a sequence-sharded
    ``x`` (sequence on dim -2): (..., S/P, d_in) -> (..., S, d_out/P), rows in
    sequence order; ``w_shard`` is cast to ``x``'s type (fp32 master weights
    under bf16 compute).  Several weights of the one input (:func:`qkv_slabs`
    in front of attention; no bias then) give a product each, a tuple.  Over
    more than one member the gather is the ring of the module docstring, each
    piece multiplied by every weight, the products then put in sequence
    order."""
    several = isinstance(w_shard, (tuple, list))
    products, bounds = _gather_matmul(
        x, w_shard if several else (w_shard,), b_shard, axis_name, False)
    placed = tuple(parts[0] if len(parts) == 1 else
                   _place(parts, axis_name, bounds, x.ndim - 2)
                   for parts in products)
    return placed if several else placed[0]


def gather_column_parallel_ring(x: jax.Array, w_shard: jax.Array,
                                axis_name: str,
                                b_shard: Optional[jax.Array] = None
                                ) -> RingRows:
    """:func:`gather_column_parallel` for a consumer that works row by row
    (an MLP's activation, then ``row_parallel(..., scatter_sequence=True)``):
    the rows stay in the order the ring brought them, so nothing is copied
    into sequence order and back."""
    (parts,), _ = _gather_matmul(x, (w_shard,), b_shard, axis_name, True)
    return RingRows(tuple(parts))


def _gather_matmul(x, weights, b_shard, axis_name: str,
                   scattered_behind: bool):
    """The products of the gathered rows with each of ``weights``, a list of
    parts a weight, and the parts' bounds: one member's plain all-gather and
    matmuls, whole, or the ring's, in ring order."""
    if axis_size(axis_name) == 1:
        gathered = gather_sequence(x, axis_name, dim=x.ndim - 2)
        return ([[column_parallel(gathered, w.astype(x.dtype), b_shard)]
                 for w in weights], ((0, x.shape[-2]),))
    return _ring_gather_matmul(x, [w.astype(x.dtype) for w in weights],
                               b_shard, axis_name, scattered_behind)


def _ring_built(form: str) -> None:
    """Trace-time count of the ring matmuls built, by form: none at P = 1."""
    registry().counter(
        "hvd_tp_ring_matmuls_built_total",
        "ring-overlapped tensor-parallel matmuls traced, by form",
        form=form).inc()


def _ring_perm(p: int) -> List[Tuple[int, int]]:
    return [(i, (i + 1) % p) for i in range(p)]


def _pieces(s_loc: int, hop_bytes: int, hop_flops: float,
            fused_add: bool) -> _Bounds:
    """The pieces one hop's chunk of ``s_loc`` sequence rows is cut into, from
    the shapes alone.  ``r`` is the hop's time on the wire over its chunk's
    matmul time.  A scatter always needs two, so that the add of an arriving
    piece rides on a later piece's matmul and never waits for the piece sent
    just before it, and more where the wire is the slower (the smallest k
    with r <= 2 (1 - 1/k)).  A gather's pieces are slices of the activation
    that must be copied out for the wire (two passes over HBM, an eighth of
    the hop's own time): it is cut only where the tail of a chunk would
    arrive further than that behind the head's matmul (r > 1.25; then the
    smallest k with r <= 2 - 1/k).  Powers of two."""
    r = hop_bytes * _FLOPS_PER_WIRE_BYTE / max(hop_flops, 1.0)
    if fused_add:
        k = 2
        while k < _MAX_PIECES and r > 2.0 * (1.0 - 1.0 / k):
            k *= 2
    else:
        k = 1
        while r > 1.25 and k < _MAX_PIECES and r > 2.0 - 1.0 / k:
            k *= 2
    k = max(1, min(k, s_loc, hop_bytes // _MIN_PIECE_BYTES))
    return tuple((s_loc * i // k, s_loc * (i + 1) // k) for i in range(k))


def _ring_gather_matmul(x: jax.Array, weights: Sequence[jax.Array],
                        b_shard: Optional[jax.Array], axis_name: str,
                        scattered_behind: bool):
    """``column_parallel`` of every chunk of the gathered sequence with each
    of ``weights``, as the ring brings them: the pieces held are multiplied
    while the next chunk's arrive.  ``scattered_behind``: the products go on
    to a ring scatter as they are, which wants a chunk in two pieces at the
    least.  (The products in ring order, a list a weight; the bounds a chunk
    was cut at, which hang on the weights' widths together.)"""
    p = axis_size(axis_name)
    seq = x.ndim - 2
    rows = math.prod(x.shape[:-1])
    bounds = _pieces(x.shape[seq], rows * x.shape[-1] * x.dtype.itemsize,
                     2.0 * rows * x.shape[-1]
                     * sum(w.shape[-1] for w in weights),
                     fused_add=scattered_behind)
    _ring_built("gather")
    held = [lax.slice_in_dim(x, lo, hi, axis=seq) for lo, hi in bounds]
    out = [[] for _ in weights]
    for hop in range(p):
        if hop < p - 1:
            arriving = [lax.ppermute(c, axis_name, _ring_perm(p))
                        for c in held]
        for c in held:
            for products, w in zip(out, weights):
                products.append(column_parallel(c, w, b_shard))
        held = arriving
    return out, bounds


def _ring_matmul_scatter(parts: Sequence[jax.Array], w_shard: jax.Array,
                         axis_name: str) -> List[jax.Array]:
    """``psum_scatter(x_shard @ w_shard)`` over the sequence as a ring, from
    the ring-ordered pieces of ``x_shard``: at step t a member multiplies
    the pieces of the chunk t + 1 hops back, adds what arrived for them
    (the two in fp32, rounded once to their type for the wire) and sends
    the sums on; the last step's rows are its own, and returned."""
    p = axis_size(axis_name)
    k = len(parts) // p
    _ring_built("scatter")
    arrived = [None] * k
    for step in range(p):
        hop = (step + 1) % p
        for i in range(k):
            part = jnp.einsum("...i,io->...o", parts[hop * k + i], w_shard)
            if arrived[i] is not None:
                part = (part.astype(jnp.float32)
                        + arrived[i].astype(jnp.float32)
                        ).astype(part.dtype)
            if step < p - 1:
                part = lax.ppermute(part, axis_name, _ring_perm(p))
            arrived[i] = part
    return arrived


def _piece_start(axis_name: str, n: int, bounds: _Bounds):
    """The first sequence row, in sequence order, of the ``n``-th ring-ordered
    piece: ``hop`` hops into a gather ring a member holds the chunk of the
    member ``hop`` places back."""
    p = axis_size(axis_name)
    hop, i = divmod(n, len(bounds))
    return (((lax.axis_index(axis_name) + (p - hop)) % p) * bounds[-1][1]
            + bounds[i][0])


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _take(full: jax.Array, axis_name: str, bounds: _Bounds,
          seq: int) -> List[jax.Array]:
    """The pieces of every chunk of an array in sequence order (dimension
    ``seq``), in ring order.  Its transpose is :func:`_place` (AD's own, a
    sum of P k zero-padded slices, copied the array whole)."""
    return [lax.dynamic_slice_in_dim(
        full, _piece_start(axis_name, n, bounds), hi - lo, axis=seq)
        for n, (lo, hi) in enumerate(bounds * axis_size(axis_name))]


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _place(parts: List[jax.Array], axis_name: str, bounds: _Bounds,
           seq: int) -> jax.Array:
    """Ring-ordered pieces in sequence order: one concatenate, in the order
    that is this member's (a branch a member: the order is static in each,
    where a piece written at a computed offset is a slow copy of its own on
    this compiler).  Its transpose is :func:`_take`."""
    p, k = axis_size(axis_name), len(bounds)

    def in_the_order_of(member):
        return lambda parts: jnp.concatenate(
            [parts[((member - chunk) % p) * k + i]
             for chunk in range(p) for i in range(k)], axis=seq)

    return lax.switch(lax.axis_index(axis_name),
                      [in_the_order_of(member) for member in range(p)],
                      parts)


_take.defvjp(
    lambda full, axis_name, bounds, seq: (
        _take(full, axis_name, bounds, seq), None),
    lambda axis_name, bounds, seq, _, g: (
        _place(list(g), axis_name, bounds, seq),))
_place.defvjp(
    lambda parts, axis_name, bounds, seq: (
        _place(parts, axis_name, bounds, seq), None),
    lambda axis_name, bounds, seq, _, g: (
        _take(g, axis_name, bounds, seq),))


def gather_sequence(x: jax.Array, axis_name: str, dim: int = 1) -> jax.Array:
    """All-gather a sequence-sharded activation back to full length along
    ``dim`` (entry into a tensor-parallel region)."""
    return lax.all_gather(x, axis_name, axis=dim, tiled=True)


def vocab_parallel_logits(x: jax.Array, embed_shard: jax.Array,
                          axis_name: str) -> jax.Array:
    """Compute logits against a vocab-sharded embedding: each member holds
    vocab/P rows; the full logits stay sharded on the vocab dim."""
    return jnp.einsum("...d,vd->...v", x, embed_shard)


def vocab_parallel_cross_entropy(logits_shard: jax.Array, labels: jax.Array,
                                 vocab_shard_size: int,
                                 axis_name: str) -> jax.Array:
    """Cross-entropy over vocab-sharded logits without gathering the full
    vocab: two psums (max and sum-exp) plus a masked label pick."""
    idx = lax.axis_index(axis_name)
    lo = idx * vocab_shard_size
    lf = logits_shard.astype(jnp.float32)
    local_max = lf.max(axis=-1)
    global_max = lax.pmax(local_max, axis_name)
    shifted = lf - global_max[..., None]
    sum_exp = lax.psum(jnp.exp(shifted).sum(axis=-1), axis_name)
    # Pick the label logit if it lives in this shard, else 0; psum completes.
    local_label = labels - lo
    in_shard = (local_label >= 0) & (local_label < vocab_shard_size)
    safe_label = jnp.clip(local_label, 0, vocab_shard_size - 1)
    picked = jnp.take_along_axis(shifted, safe_label[..., None],
                                 axis=-1)[..., 0]
    label_logit = lax.psum(jnp.where(in_shard, picked, 0.0), axis_name)
    return jnp.log(sum_exp) - label_logit
