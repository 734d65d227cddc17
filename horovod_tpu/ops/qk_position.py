"""q and k's position prologue in one pass over their rows: one Pallas TPU
kernel, forward and backward.

Between the projections and the attention a grouped-query block normalises
each head of q and of k (``head_qk_norm``) and rotates it by its position
(``models/transformer.py`` ``_position``: the one statement of the
mathematics, the tests' reference and the form that runs where this kernel
does not).  Written with slices and a concatenate that costs several passes
over q and k in HBM, fp32 temporaries among them, and copies: the last axis
of the (B, S, H·D) rows the projections write, and the flash kernels read
(``ops/flash_attention.py``), is the lane axis, and halves cut out of it are
laid out again before they are rows once more.  ``forward`` reads q and k
once, in the rows they are, and writes them once:

* **the rotation without a slice**: ``t·C + roll(t, −half)·A + roll(t,
  +half)·B`` along the 128 lanes of a vector register, with three fp32
  tables a position (``tables``): C holds cos on the lanes that rotate and 1
  on those that pass, A −sin on the first half of a head's rotating lanes, B
  +sin on the second half, both 0 elsewhere.  A roll that crosses into a
  neighbouring head meets a zero, so the one form is a whole head of 128
  lanes, the first ``rot`` of them, or the 128 / D heads of D lanes a
  register holds;
* **the per-head norm in the same pass**: the mean square over the head's
  lanes — a product on the MXU, which has nothing else to do here
  (``_head_means``) —, ``rsqrt``, the (D,) scale, then the rotation, with no
  rounding between the two;
* fp32 inside, one rounding to the rows' type at the store.

``backward`` is the same pass transposed, ``g·C + roll(g·A, +half) +
roll(g·B, −half)``, then the norm's own backward from the projection read
again (nothing is saved but what the caller holds anyway); the scales'
gradients leave as a program's partial sums, (8, 128) each, for the caller
to add.

The grid is (blocks of positions, batch): a program takes every head of
``block`` positions of one sequence, in chunks of rows, q then k.  A table
shared by the batch keeps its block across the inner axis and is fetched
once; tables a sequence (several position streams) are fetched a program.
``block`` says whether the shapes fit: heads that tile 128 lanes, rows a
whole number of registers wide, positions a multiple of a block.

Compiled by Mosaic unless the caller passes ``interpret=True``; nothing here
looks at the backend or differentiates (``models/transformer.py``
``_qk_position`` holds the custom VJP and decides).
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .token_sum import _three_bf16

KERNEL = "hvd_qk_position"
LANES = 128

# Positions a program, at most, and rows a trip of its loop: the blocks'
# bytes against the steps' fixed cost, and the unrolled heads' code against
# the trips (PERF.md section 6, PR 53).
_BLOCK = 256
_CHUNK = 32
# What the blocks of a program may take of VMEM, twice buffered.
_BLOCK_BYTES = 24 << 20


def block(positions: int, q_width: int, k_width: int, head_dim: int,
          itemsize: int):
    """Positions a program takes, or None where the kernel does not fit:
    ``head_dim`` tiles the 128 lanes, both rows are whole registers wide,
    and the widest of 256, 128, ... 16 positions that divides the sequence
    and keeps a backward program's blocks (three arrays of q and of k,
    twice buffered) inside ``_BLOCK_BYTES``."""
    if LANES % head_dim or q_width % LANES or k_width % LANES:
        return None
    n = _BLOCK
    while n >= 16:
        if (positions % n == 0
                and 6 * n * (q_width + k_width) * itemsize <= _BLOCK_BYTES):
            return n
        n //= 2
    return None


def lanes(head_dim: int, half: int) -> np.ndarray:
    """(128,) ints: the frequency, 0 .. ``half`` - 1, whose angle turns each
    lane of a register that holds 128 / ``head_dim`` heads, the first ``2
    half`` lanes of each rotating; 0 on a lane that passes."""
    j = np.arange(LANES) % head_dim
    return np.where(j < 2 * half, j % half, 0)


def tables(cos, sin, head_dim: int, half: int):
    """(3, Bt, S, 128) fp32, [C, A, B] of the module's header, from the
    cos and sin of every lane's own angle (:func:`lanes`), (S, 128) — or
    (Bt, S, 128), a table a sequence.  Products with 0 / 1 masks: nothing is
    sliced or joined."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    j = np.arange(LANES) % head_dim
    first, rotating = j < half, j < 2 * half
    zero = np.zeros(LANES)
    of_cos, of_sin, alone = (
        np.stack(m).astype(np.float32)[:, None, None] for m in (
            (rotating, zero, zero), (zero, -1.0 * first, rotating & ~first),
            (~rotating, zero, zero)))
    return (cos.astype(jnp.float32)[None] * of_cos
            + sin.astype(jnp.float32)[None] * of_sin + alone)


def _rotate(t, c, a, b, half: int):
    return (t * c + pltpu.roll(t, LANES - half, 1) * a
            + pltpu.roll(t, half, 1) * b)


def _rotate_t(g, c, a, b, half: int):
    return (g * c + pltpu.roll(g * a, half, 1)
            + pltpu.roll(g * b, LANES - half, 1))


def _heads(ref):
    """The 128-lane slices of a row block, statically."""
    return [slice(h, h + LANES) for h in range(0, ref.shape[-1], LANES)]


def _mean_of_heads(head_dim: int):
    """(128, 128) bf16: 1 / head_dim where row and column are lanes of one
    head, 0 elsewhere — ``x @ it`` is every head's mean in its own lanes."""
    head = [lax.broadcasted_iota(jnp.int32, (LANES, LANES), axis) // head_dim
            for axis in (0, 1)]
    return jnp.where(head[0] == head[1], 1.0 / head_dim, 0.0).astype(
        jnp.bfloat16)


def _head_means(xs, mean_of):
    """The mean over its head's lanes of every element of each of ``xs``,
    (rows, 128) fp32 arrays of one shape holding 128 / head_dim heads a
    row, in every lane of the head.  One product on the MXU for all of them,
    stacked along their rows against ``mean_of`` (:func:`_mean_of_heads`) —
    which stays latched while the rows stream — and not a reduction across
    lanes a register, which costs the unit that also rolls seven steps a
    register and left the kernel waiting on it.  Exact in fp32: the rows go
    as the three bf16 parts that add up to them (``ops/token_sum.py``), the
    matrix is a power of two."""
    n = xs[0].shape[0]
    means = sum(jnp.dot(part, mean_of, preferred_element_type=jnp.float32)
                for part in _three_bf16(jnp.concatenate(xs, axis=0)))
    return [means[i * n:(i + 1) * n] for i in range(len(xs))]


def _fwd_kernel(tab_ref, *refs, head_dim: int, half: int, eps: float,
                normed: bool, chunk: int):
    if normed:
        q_ref, k_ref, qs_ref, ks_ref, qo_ref, ko_ref = refs
    else:
        (q_ref, k_ref, qo_ref, ko_ref), qs_ref, ks_ref = refs, None, None
    sites = [(ref, scale_ref, out_ref, head)
             for ref, scale_ref, out_ref in ((q_ref, qs_ref, qo_ref),
                                             (k_ref, ks_ref, ko_ref))
             for head in _heads(ref)]
    mean_of = _mean_of_heads(head_dim) if normed else None

    def trip(r, carry):
        rows = pl.ds(pl.multiple_of(r * chunk, chunk), chunk)
        c, a, b = (tab_ref[j, 0, rows, :] for j in range(3))
        # Head by head: a generator, read as the loop below stores, unless
        # the norm wants every head's mean at once.
        ts = (ref[0, rows, head].astype(jnp.float32)
              for ref, _, _, head in sites)
        if normed:
            ts = list(ts)
            ts = [t * lax.rsqrt(var + eps) * scale_ref[...]
                  for t, var, (_, scale_ref, _, _) in zip(
                      ts, _head_means([t * t for t in ts], mean_of), sites)]
        for t, (_, _, out_ref, head) in zip(ts, sites):
            out_ref[0, rows, head] = _rotate(t, c, a, b, half).astype(
                out_ref.dtype)
        return carry

    lax.fori_loop(0, q_ref.shape[1] // chunk, trip, 0)


def _bwd_kernel(tab_ref, *refs, head_dim: int, half: int, eps: float,
                normed: bool, chunk: int):
    if normed:
        (q_ref, k_ref, gq_ref, gk_ref, qs_ref, ks_ref,
         dq_ref, dk_ref, dqs_ref, dks_ref) = refs
    else:
        gq_ref, gk_ref, dq_ref, dk_ref = refs
        q_ref = k_ref = qs_ref = ks_ref = None
    sites = [(x_ref, scale_ref, g_ref, d_ref, head)
             for x_ref, scale_ref, g_ref, d_ref in (
                 (q_ref, qs_ref, gq_ref, dq_ref),
                 (k_ref, ks_ref, gk_ref, dk_ref))
             for head in _heads(g_ref)]
    q_heads = len(_heads(gq_ref))
    mean_of = _mean_of_heads(head_dim) if normed else None

    def trip(r, sums):
        rows = pl.ds(pl.multiple_of(r * chunk, chunk), chunk)
        c, a, b = (tab_ref[j, 0, rows, :] for j in range(3))
        gs = (_rotate_t(g_ref[0, rows, head].astype(jnp.float32), c, a, b,
                        half) for _, _, g_ref, _, head in sites)
        if normed:
            gs = list(gs)
            xs = [x_ref[0, rows, head].astype(jnp.float32)
                  for x_ref, _, _, _, head in sites]
            invs = [lax.rsqrt(var + eps)
                    for var in _head_means([x * x for x in xs], mean_of)]
            units = [x * inv for x, inv in zip(xs, invs)]
            by_unit = [g * unit for g, unit in zip(gs, units)]
            sums = (sums[0] + sum(by_unit[:q_heads]),
                    sums[1] + sum(by_unit[q_heads:]))
            gs = [g * scale_ref[...]
                  for g, (_, scale_ref, _, _, _) in zip(gs, sites)]
            alongs = _head_means(
                [g * unit for g, unit in zip(gs, units)], mean_of)
            gs = [inv * (g - unit * along)
                  for inv, g, unit, along in zip(invs, gs, units, alongs)]
        for g, (_, _, _, d_ref, head) in zip(gs, sites):
            d_ref[0, rows, head] = g.astype(d_ref.dtype)
        return sums

    zero = jnp.zeros((chunk, LANES), jnp.float32)
    sums = lax.fori_loop(0, gq_ref.shape[1] // chunk, trip,
                         (zero, zero) if normed else 0)
    if normed:
        for ref, acc in zip((dqs_ref, dks_ref), sums):
            # Sublane groups added up: registers added to registers.
            ref[0, 0] = acc.reshape(chunk // 8, 8, LANES).sum(axis=0)


def _call(kernel, tab, rows, scales, out_shapes, *, head_dim: int, block: int,
          half: int, eps: float, interpret: bool):
    """One pass: ``rows`` the (B, S, width) arrays a program takes a block
    of, after the tables and before ``scales`` ((1, 128) each, whole);
    ``out_shapes`` the results, row arrays blocked alike and (S / block, B,
    8, 128) partial sums a program."""
    b, s, _ = rows[0].shape
    chunk = min(_CHUNK, block)
    per_batch = tab.shape[1] > 1

    def row_spec(width):
        return pl.BlockSpec((1, block, width), lambda i, j: (j, i, 0))

    def out_spec(shape):
        if len(shape.shape) == 3:
            return row_spec(shape.shape[-1])
        return pl.BlockSpec((1, 1, 8, LANES), lambda i, j: (i, j, 0, 0))

    in_specs = [pl.BlockSpec((3, 1, block, LANES),
                             lambda i, j: (0, j if per_batch else 0, i, 0))]
    in_specs += [row_spec(t.shape[-1]) for t in rows]
    in_specs += [pl.BlockSpec((1, LANES), lambda i, j: (0, 0))
                 for _ in scales]
    blocks = 2 * block * (3 * LANES * 4 + sum(
        t.shape[-1] * t.dtype.itemsize
        for t in tuple(rows) + tuple(o for o in out_shapes
                                     if len(o.shape) == 3)))
    return pl.pallas_call(
        functools.partial(kernel, head_dim=head_dim, half=half, eps=eps,
                          normed=bool(scales), chunk=chunk),
        grid=(s // block, b),
        in_specs=in_specs,
        out_specs=[out_spec(o) for o in out_shapes],
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(16 << 20, blocks + (8 << 20))),
        name=KERNEL,
        interpret=interpret,
    )(tab, *rows, *scales)


def _lane_scales(scales, head_dim: int):
    return tuple(jnp.tile(s.astype(jnp.float32), LANES // head_dim)[None]
                 for s in scales)


@functools.partial(jax.jit, static_argnames=("head_dim", "half", "eps",
                                             "block", "interpret"))
def forward(q, k, tab, scales, *, head_dim: int, half: int, eps: float,
            block: int, interpret: bool = False):
    """(q, k) normalised a head by ``scales`` — (q's, k's), (head_dim,) each,
    or () for no norm — and rotated by ``tab`` (``tables``).  q (B, S, Hq·D),
    k (B, S, Hkv·D); results of the same shapes and type.  ``block`` as
    :func:`block` gives it.  Jitted, so that a program's equal calls — a
    model's layers of one kind — are traced and lowered once."""
    shapes = [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (q, k)]
    return _call(_fwd_kernel, tab, (q, k), _lane_scales(scales, head_dim),
                 shapes, head_dim=head_dim, block=block, half=half, eps=eps,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("head_dim", "half", "eps",
                                             "block", "interpret"))
def backward(q, k, tab, scales, gq, gk, *, head_dim: int, half: int,
             eps: float, block: int, interpret: bool = False):
    """``forward``'s pullback of (gq, gk): (dq, dk, the scales' gradients
    as partial sums — (S / block, B, 8, 128) fp32 each, a lane the lane of a
    register's 128 / head_dim heads, for the caller to add —, or ()).
    Without a norm q and k are not read (pass None)."""
    shapes = [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (gq, gk)]
    kw = dict(head_dim=head_dim, block=block, half=half, eps=eps,
              interpret=interpret)
    if not scales:
        return (*_call(_bwd_kernel, tab, (gq, gk), (), shapes, **kw), ())
    b, s, _ = q.shape
    partial_sums = jax.ShapeDtypeStruct((s // block, b, 8, LANES),
                                        jnp.float32)
    dq, dk, *sums = _call(
        _bwd_kernel, tab, (q, k, gq, gk), _lane_scales(scales, head_dim),
        shapes + [partial_sums] * 2, **kw)
    return dq, dk, tuple(sums)
