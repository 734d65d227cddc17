"""Rows summed into their tokens in fast memory: one Pallas TPU kernel.

``token_sum(rows, token, scale, tokens)`` is the (tokens, d) fp32 array whose
row t is the sum of ``rows[j] * scale[j]`` over the j with ``token[j] == t``,
for rows that are **listed by token**: ``token`` ascends, and whatever is past
the last token's rows carries a key of ``tokens`` or more and goes nowhere.
What a scatter-add does by reading, adding to and writing a row of the result
in HBM for every row that arrives, this does by additions in VMEM: rows that
belong to one token are neighbours in the list, so a tile of the result is
held in VMEM while the row chunks that hold its tokens' rows pass, and is
written once, densely.  A token with no row is written as zeros.

``_token_sum_kernel`` (``hvd_moe_token_sum``) walks a list of (row chunk,
token tile) steps, laid out on the device from ``token`` in integers
(``walk``: a few hundred small operations, so a caller with several sums
over one list makes it once) and scalar-prefetched, as the flash kernels'
grids walk their live tile pairs (``ops/flash_attention.py`` ``_walk``):
tile after tile, and inside a tile the chunks that hold its rows, one after
another.  The output
tile is resident across the steps that share it; a chunk that straddles two
tiles is fetched once (consecutive steps, one block index).  A step adds its
chunk to its tile as one product on the MXU, ``W @ rows`` with ``W[t, j] =
scale[j]`` where row j is token t's and 0 elsewhere, accumulated in fp32.
That product is exact in fp32: bf16 rows against ``W`` split into the three
bf16 parts that add up to it (a 0 / 1 ``W`` is one part), fp32 rows at the
highest precision — Mosaic runs an fp32 operand as one bf16 pass by default,
and a weight rounded to bf16 is another result.  A zero of ``W`` times a row
is 0 only for a finite row: the caller zeroes what is not live.

The grid is static, chunks + tiles - 1 steps, the most a list can have; the
steps past the list's end stay on its last blocks and do nothing, and a walk
laid out for a longer list with the same live rows serves a prefix of it.
Tile and chunk come from the shapes (``tiling``), ``vmem_limit_bytes`` from
the blocks.

Compiled by Mosaic unless the caller passes ``interpret=True``; nothing here
looks at the backend (``parallel/moe.py`` ``_token_sums`` decides).
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "hvd_moe_token_sum"

# Tokens a tile and rows a chunk, at most: the product a step is 2 tile chunk
# d FLOPs a pass and the list has tokens / tile + rows / chunk steps, so the
# MXU's work grows with either and the steps' fixed cost falls with both
# (PERF.md section 6, PR 47, for the timings that chose them).
_TILE = 128
_CHUNK = 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def tiling(tokens: int, rows: int):
    """(tokens a tile, rows a chunk): the widest allowed, a multiple of 8,
    no wider than the array."""
    return (min(_TILE, _round_up(tokens, 8)), min(_CHUNK, _round_up(rows, 8)))


def walk(token, tokens: int, tile: int, chunk: int):
    """The kernel's walk over a list, in integers: ((2, steps) int32 — each
    step's token tile and row chunk —, (1,) int32 — the steps the list has;
    the rest are dead and stay on the last live step's blocks).  ``token``
    (n,) ascends, ``tokens`` or more past the live rows; steps = tiles + chunks
    - 1 of the list padded to whole tiles and chunks.  Tile i's rows are
    ``bounds[i] .. bounds[i + 1] - 1`` of the list, in the chunks ``bounds[i]
    // chunk .. (bounds[i + 1] - 1) // chunk``; a tile with no row takes one
    step (it is written, as zeros), on the chunk its rows would start in, no
    later than the last chunk with a live row: no step names a chunk past the
    live rows, so the walk serves any prefix of the list that holds them."""
    n_tiles, n_chunks = -(-tokens // tile), -(-token.shape[0] // chunk)
    # ``compare_all``: one fused comparison, where the default is a ``while``
    # of log2(n) dependent trips.  A key past the live rows is past every
    # tile's bound but the last, which counts the live rows.
    bounds = jnp.searchsorted(
        token, jnp.minimum(jnp.arange(n_tiles + 1, dtype=jnp.int32) * tile,
                           tokens), method="compare_all").astype(jnp.int32)
    lo, hi = bounds[:-1], bounds[1:]
    first_chunk = jnp.minimum(lo, jnp.maximum(bounds[-1] - 1, 0)) // chunk
    last_chunk = jnp.where(hi > lo, (hi - 1) // chunk, first_chunk)
    taken = last_chunk - first_chunk + 1
    ends = jnp.cumsum(taken)
    first_step = ends - taken
    step = jnp.arange(n_tiles + n_chunks - 1, dtype=jnp.int32)
    tile_of = jnp.minimum(
        jnp.searchsorted(ends, step, side="right",
                         method="compare_all").astype(jnp.int32),
        n_tiles - 1)
    chunk_of = jnp.minimum(first_chunk[tile_of] + step - first_step[tile_of],
                           last_chunk[tile_of])
    return jnp.stack([tile_of, chunk_of]), ends[-1:]


def _three_bf16(w):
    """fp32 ``w`` as three bf16 arrays that add up to it exactly (8 + 8 + 8
    bits of mantissa)."""
    hi = w.astype(jnp.bfloat16)
    rest = w - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _token_sum_kernel(walk_ref, live_ref, token_ref, *refs, tile: int,
                      scaled: bool):
    if scaled:
        scale_ref, rows_ref, out_ref = refs
    else:
        rows_ref, out_ref = refs
    s = pl.program_id(0)
    i = walk_ref[0, s]
    first = jnp.logical_or(s == 0, walk_ref[0, jnp.maximum(s - 1, 0)] != i)

    @pl.when(first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(s < live_ref[0])
    def _add():
        rows = rows_ref[...]                               # (chunk, d)
        own = token_ref[0] - i * tile                      # (1, chunk)
        hit = lax.broadcasted_iota(
            jnp.int32, (tile, own.shape[-1]), 0) == own    # (tile, chunk)
        dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
        w = jnp.where(hit, scale_ref[0] if scaled else 1.0, 0.0)
        if rows.dtype != jnp.bfloat16:
            out_ref[...] += dot(w, rows.astype(jnp.float32),
                                precision=lax.Precision.HIGHEST)
        else:
            parts = _three_bf16(w) if scaled else (w.astype(jnp.bfloat16),)
            out_ref[...] += sum(dot(part, rows) for part in parts)


def _vmem_limit(tile: int, chunk: int, d: int, itemsize: int) -> int:
    """Mosaic's default scoped VMEM, or what the blocks ask for where that is
    more: the resident tile and the row chunk twice buffered, the chunk once
    more in fp32, and the (tile, chunk) weights' few copies."""
    blocks = 2 * tile * d * 4 + chunk * d * (2 * itemsize + 4)
    return max(16 << 20, blocks + 8 * tile * chunk * 4 + (4 << 20))


@functools.partial(jax.jit,
                   static_argnames=("tokens", "tile", "chunk", "interpret"))
def token_sum(rows, token, scale, steps, tokens: int, *, tile: int,
              chunk: int, interpret: bool = False):
    """(tokens, d) fp32: row t is the sum of ``rows[j] * scale[j]`` (``scale``
    None: ``rows[j]``) over the j with ``token[j] == t``, products and
    additions in fp32.  ``rows`` (n, d) listed by token: ``token`` (n,) int32
    ascends; an entry of ``tokens`` or more goes nowhere, and its row must
    still be finite (zeros).  ``steps``: ``walk(token, tokens, tile, chunk)``
    of this list or of a longer one with the same live rows; ``tile`` and
    ``chunk`` as ``tiling`` gives them (the tests force others).  Jitted, so
    that a program's calls of one shape — a model's expert blocks — are
    traced once and lowered as one function: every Mosaic kernel lowered is
    set-up time."""
    n, d = rows.shape
    t_pad, n_pad = _round_up(tokens, tile), _round_up(n, chunk)
    n_tiles, n_chunks = t_pad // tile, n_pad // chunk
    # A key past the live rows falls in no tile, the last one's padding
    # included.
    token = jnp.where(token < tokens, token, t_pad).astype(jnp.int32)
    rows = jnp.pad(rows, ((0, n_pad - n), (0, 0)))
    token = jnp.pad(token, (0, n_pad - n), constant_values=t_pad)
    table, live = steps

    def by_chunk(block):
        return pl.BlockSpec(block, lambda s, table, live: (
            table[1, s],) + (0,) * (len(block) - 1))

    lanes = by_chunk((1, 1, chunk))        # a chunk's scalars, a lane each
    operands, specs = [token.reshape(n_chunks, 1, chunk)], [lanes]
    if scale is not None:
        scale = jnp.pad(scale.astype(jnp.float32), (0, n_pad - n))
        operands.append(scale.reshape(n_chunks, 1, chunk))
        specs.append(lanes)
    out = pl.pallas_call(
        functools.partial(_token_sum_kernel, tile=tile,
                          scaled=scale is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles + n_chunks - 1,),
            in_specs=specs + [by_chunk((chunk, d))],
            out_specs=pl.BlockSpec(
                (tile, d), lambda s, table, live: (table[0, s], 0))),
        out_shape=jax.ShapeDtypeStruct((t_pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(tile, chunk, d,
                                         rows.dtype.itemsize)),
        name=KERNEL,
        interpret=interpret,
    )(table, live, *operands, rows)
    return out if t_pad == tokens else out[:tokens]
