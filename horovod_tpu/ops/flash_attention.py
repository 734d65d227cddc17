"""Fused flash attention as Pallas TPU kernels.

The hot op of the flagship transformer (models/transformer.py). The
reference framework is model-agnostic middleware and carries no attention
code (SURVEY.md §5.7); on TPU the attention inner loop is ours to own, and
a fused kernel is how it belongs on the hardware: Q/K/V tiles stream
HBM→VMEM, the (bk, bq) score block lives only in VMEM, softmax is the
online (running max / running sum) recurrence so the O(S²) score matrix is
never materialized in HBM.

What reaches the MXU is the type the caller passed.  q, k, v and dO go from
their refs into the nine ``dot_general``s as they are (2 in the forward, 3
in dQ, 4 in dK/dV), every one accumulating in fp32
(``preferred_element_type``).  Five of the nine (K·Qᵀ three times, V·dOᵀ
twice) have two caller operands; the other four take one operand the kernel
computed in fp32 — Pᵀ in Vᵀ·Pᵀ and Pᵀ·dO, dSᵀ in Kᵀ·dSᵀ and dSᵀ·Q — and
that one is cast to the other operand's type at the dot, and only there.
Everything that is softmax is fp32 and computed before the cast: scores,
scale, mask, running max, ``exp``, ``alpha``, the running sum ``l`` (summed
from the fp32 probabilities), ``lse``, ``delta``, ``dp - delta`` and all
scratch accumulators.  So an fp32 caller gets fp32 operands and no cast at
all, and a bf16 caller — the trainers — the one rounding every bf16
attention makes.  (Mosaic at the default precision takes one bf16 pass of
the MXU for fp32 operands too, rounding them on the way in: the type decides
what is rounded where, not how fast the dots run.  PERF.md section 6, PR 27.)

All three bodies work on the *transposed* score tile, (bk, bq): keys on
sublanes, queries on lanes.  The row statistics (m, l, lse, delta) are then
lane-dense (1, bq) rows — the layout lse is stored in — that broadcast down
the sublanes; the softmax's max and sum reduce over sublanes, elementwise
between vector registers, not across lanes; and a computed tile enters its
dot as it lies, (M, K) on the left or (K, N) on the right, so only the
caller's narrow (block, D) tiles are ever transposed for the MXU.  The
forward and dQ accumulate (D, bq) and transpose once, at the last key tile.
On a v5e that orientation, not the dots, is what the time was: the forward
takes 7.6 ps a live score element against 10.9 with queries on sublanes
(bare timing at (5, 8192, 16, 64) bf16 causal, PERF.md section 6, PR 27).

The tile is up to 1024 x 1024 (``_supported``: the widest of 1024, 512, 256,
128 that divides each length, so 8192 and 4096 take 1024 on both sides, 1536
and BERT's 512 take 512).  The kernels pay per grid step and per re-read of
the operand they stream past the accumulator they keep resident (queries for
the forward and dQ, keys for dK/dV), not per FLOP: a wider resident side
halves the steps and the re-reads, a wider streamed side halves the steps
again, and on a causal grid fewer steps are dead (at 512 x 512 a (batch,
head) of 8192 walks 256 steps for 136 live tiles, at 1024 x 1024 64 for 36;
a dead step is not free, 0.5-0.6 us with 512 keys a block: its K / V blocks
are fetched before ``pl.when`` skips it).  Bare on a v5e, (5, 8192, 16, 64) bf16
causal, ms a call (PERF.md section 6, PR 29):

    tile (bq x bk)   512x512  1024x512  512x1024  1024x1024  2048x1024
    forward            20.33     16.45     18.13      15.33      14.71
    dQ                 22.55     18.33     18.70      18.11      19.01
    dK/dV              27.00     25.60     24.18      22.97      24.13 *

(* needs ``vmem_limit_bytes`` of 32 MiB.)  Narrower tiles lose badly (256 x
256: twice the time), 2048 wins for the forward at head 64 alone (at head 128
3.55 against 3.50), and 1024 x 1024 is the widest all three compile on
inside Mosaic's default 16 MiB of scoped VMEM — for heads up to 256 bytes a
row (bf16 128, fp32 64); wider heads keep 512 (``_WIDE_BLOCK_ROW_BYTES``).

A call with a ``window`` (each query sees the ``window`` keys up to and
including its own) runs the same three bodies over a grid that walks the
band only: the streamed axis has ``_band_extent`` steps a resident tile
whatever the sequence length (two for a window and tiles of 512), its
index map offset from the resident tile's index and clamped at the
sequence's ends.  Its kernels are named ``hvd_flash_fwd_win``,
``hvd_flash_bwd_dq_win`` and ``hvd_flash_bwd_dkv_win``; a call without a
window traces to what it always did.

Three kernels:

* ``_fwd_kernel``      — out + logsumexp, online softmax over K/V tiles.
* ``_bwd_dq_kernel``   — dQ, streaming over K/V tiles.
* ``_bwd_dkv_kernel``  — dK/dV, streaming over Q tiles.

Public API:

* ``flash_attention(q, k, v, causal=…, window=…)`` — differentiable (custom
  VJP).
* ``flash_attention_with_lse`` — also returns logsumexp rows, which is the
  composition hook ring attention (parallel/ring_attention.py) uses to
  merge per-ring-step partials into an exact global softmax.

Under a layer checkpoint the forward kernel runs once.  A bare
``jax.checkpoint`` saves nothing a custom VJP made, so the backward's
recompute of a layer ran ``hvd_flash_fwd`` a second time to remake ``out``
and ``lse`` for ``_flash_bwd``.  ``_flash_fwd`` names the two
(``SAVED_OUT``, ``SAVED_LSE``) and the models' layer checkpoint saves those
names (``parallel/ring_attention.checkpoint_keeping_attention``): the
recompute stops at the named values and the backward reads what the forward
wrote.  The named output is the (B, S, H·D) reshape, and both the primal
output and the residual are that value reshaped back: a name on a copy that
nothing downstream reads saves the copy and reruns the kernel, and a
(B, S, H, D) value stacked by the layers' scan pads a head of 64 to 128
lanes (PERF.md section 6, PR 32).  ``flash_attention_with_lse`` — ring
attention's hook, under a custom VJP of its own — sets no names.

Layout is (batch, seq, heads, head_dim) throughout, matching the rest of
the framework. ``q_offset``/``kv_offset`` globalize the causal mask when
q/k are shards of a longer sequence (they are traced values under
shard_map — ring attention passes ``kv_offset = ring_rank * block``).

The kernels are compiled by Mosaic unless a caller passes
``interpret=True`` (the CPU tests do, to validate numerics); nothing here
looks at the backend.  Off-TPU dispatch to the pure-XLA path is the
callers' decision (parallel/ring_attention.py ``_flash_enabled``).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# ``jax.ad_checkpoint.checkpoint_name``s of what the differentiated forward
# made: the output as (B, S, H·D) and the (B, H, S) fp32 ``lse``.
SAVED_OUT = "flash_attention_out"
SAVED_LSE = "flash_attention_lse"

_BLOCK_CANDIDATES = (1024, 512, 256, 128)
# A dimension no candidate divides is taken whole up to this length, and a
# head too wide for a 1024-row tile (below) tiles at most this wide.
_NARROW_BLOCK = 512
# Bytes of one row of an operand block (head_dim x itemsize) up to which
# all three kernels compile on 1024 x 1024 tiles inside Mosaic's default
# scoped VMEM, 16 MiB on a v5e: bf16 heads up to 128, fp32 heads up to 64,
# fp32 also at ``highest`` precision.  At twice that dK/dV no longer fits
# (bf16 head 256: 21.1 MiB; fp32 head 128: 18.5 MiB, 22.0 at ``highest``;
# compiled for a described v5e, PERF.md section 6, PR 29).
_WIDE_BLOCK_ROW_BYTES = 256


def _pick_block(size: int, widest: int, env: str = "") -> Optional[int]:
    """Widest 128-aligned candidate up to ``widest`` that divides ``size``,
    else the whole dim (Mosaic's equal-to-array-dim exemption) when small
    enough to fit VMEM tiles, else None: the kernels cannot tile ``size``.

    ``env`` names an override variable (HVD_TPU_FLASH_BLOCK_Q/K) for
    silicon block-size tuning: the override must divide the dimension,
    else it is ignored and auto-selection applies."""
    if env:
        try:
            forced = int(os.environ.get(env, "0"))
        except ValueError:
            forced = 0  # non-numeric override: ignore, auto-select
        # A 128-aligned divisor no wider than the widest candidate (a sweep
        # may try it at any head; auto-selection is stricter), or the whole
        # (small) dim — anything else would fail Mosaic's lane alignment /
        # VMEM fit on silicon.
        if forced > 0 and size % forced == 0 and (
                (forced % 128 == 0 and forced <= _BLOCK_CANDIDATES[0])
                or (forced == size and size <= _NARROW_BLOCK)):
            return forced
    for c in _BLOCK_CANDIDATES:
        if c <= widest and size % c == 0:
            return c
    return size if size <= _NARROW_BLOCK else None


def _win(window: Optional[int]) -> str:
    """A windowed call's kernels carry their own names: the same prefixes,
    so a reader that matches kernels by prefix counts them, and a suffix
    that tells them apart."""
    return "" if window is None else "_win"


def _compiler_params(n_parallel: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",))


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _scores_t(q, k, *, causal: bool, scale: float, q_start, k_start,
              window: Optional[int] = None):
    """The transposed score tile Sᵀ = K·Qᵀ · scale, (bk, bq) fp32, from the
    caller's (bq, D) and (bk, D) tiles as they are; causally masked at the
    tile's global positions, and with a ``window`` to the band ``q_pos -
    window < k_pos <= q_pos``."""
    st = jax.lax.dot_general(
        k, q, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
        keep = q_pos >= k_pos
        if window is not None:
            keep = jnp.logical_and(keep, q_pos - k_pos < window)
        st = jnp.where(keep, st, _NEG_INF)
    return st


# ---------------------------------------------------------------------------
# The band of a windowed call
# ---------------------------------------------------------------------------
#
# With a window the streamed axis of each grid walks only the tiles the band
# ``q_pos - window < k_pos <= q_pos`` touches: ``_band_extent`` steps a
# resident tile, a number that depends on the two tile widths and the window
# and not on the sequence.  Step j of resident tile i is streamed tile
# ``_band_first(i) + j``; where that falls off either end of the sequence the
# index map clamps it to the end's tile (the block it names is then the one
# the neighbouring step reads, so nothing is fetched twice) and the body
# skips the step.

def _floordiv(a, b: int):
    """a // b for a >= 0: a Python int, or a traced int32 of a grid."""
    return a // b if isinstance(a, int) else lax.div(a, jnp.int32(b))


def _band_first(i, block_res: int, block_str: int, window: int,
                keys_streamed: bool):
    """First streamed tile the band touches for resident tile ``i`` (may be
    negative).  Keys streamed past resident queries (forward, dQ): the tile
    of key ``i * bq - (window - 1)``.  Queries streamed past resident keys
    (dK/dV): the tile of query ``i * bk``."""
    if not keys_streamed:
        return _floordiv(i * block_res, block_str)
    back = -(-(window - 1) // block_str)        # tiles a window reaches back
    return _floordiv(i * block_res + back * block_str - (window - 1),
                     block_str) - back


def _band_extent(n_res: int, block_res: int, block_str: int, window: int,
                 keys_streamed: bool) -> int:
    """Streamed tiles the band touches for one resident tile, at most."""
    reach = block_res - 1 + (0 if keys_streamed else window - 1)
    return max((i * block_res + reach) // block_str
               - _band_first(i, block_res, block_str, window, keys_streamed)
               + 1 for i in range(n_res))


def _streamed_tile(i, j, n_str: int, block_res: int, block_str: int,
                   window: Optional[int], keys_streamed: bool):
    """(streamed tile of grid step (i, j), whether it lies in the sequence).
    Without a window the grid walks every tile: j itself."""
    if window is None:
        return j, True
    t = _band_first(i, block_res, block_str, window, keys_streamed) + j
    return t, jnp.logical_and(t >= 0, t < n_str)


def _tile_live(q_start, k_start, block_q: int, block_k: int, causal: bool,
               window: Optional[int], inside):
    """Whether a score tile has an unmasked pair.  Causal: unless every
    (q, k) has q_pos < k_pos; with a window also unless every k lies at or
    before q_pos - window; a step clamped at the sequence's end is none."""
    if not causal:
        return True
    live = q_start + block_q - 1 >= k_start
    if window is not None:
        live = jnp.logical_and(
            jnp.logical_and(live, k_start + block_k - 1 > q_start - window),
            inside)
    return live


def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, causal: bool, scale: float,
                block_q: int, block_k: int, window: Optional[int] = None,
                n_str: int = 0):
    i = pl.program_id(2)          # q tile
    j = pl.program_id(3)          # k step (innermost: scratch carries over j)
    nk = pl.num_programs(3)
    kt, inside = _streamed_tile(i, j, n_str, block_q, block_k, window, True)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_off = off_ref[0, 0]
    kv_off = off_ref[0, 1]
    q_start = q_off + i * block_q
    k_start = kv_off + kt * block_k
    live = _tile_live(q_start, k_start, block_q, block_k, causal, window,
                      inside)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                # (bq, D), the caller's type
        k = k_ref[0, 0]                # (bk, D)
        v = v_ref[0, 0]
        st = _scores_t(q, k, causal=causal, scale=scale, q_start=q_start,
                       k_start=k_start, window=window)         # (bk, bq)
        m_prev = m_scr[:1, :]                                  # (1, bq)
        l_prev = l_scr[:1, :]
        m_cur = jnp.max(st, axis=0, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.where(m_prev <= _NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_new))
        pt = jnp.where(st <= _NEG_INF / 2, 0.0, jnp.exp(st - m_new))
        l_new = l_prev * alpha + jnp.sum(pt, axis=0, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            v, pt.astype(v.dtype),     # the one rounding; l_new had fp32 pt
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (D, bq)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        m = m_scr[:1, :]
        l = l_scr[:1, :]
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = jnp.transpose(acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l <= 0.0, _NEG_INF, m + jnp.log(l_safe))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _streamed_index(n_res: int, n_str: int, block_res: int, block_str: int,
                    window: Optional[int], keys_streamed: bool):
    """(steps of the streamed grid axis, grid indices (i, j) -> the streamed
    tile's block index): every tile without a window, with one the band's
    tiles clamped into the sequence."""
    if window is None:
        return n_str, lambda i, j: j
    steps = _band_extent(n_res, block_res, block_str, window, keys_streamed)

    def tile(i, j):
        return jnp.clip(_band_first(i, block_res, block_str, window,
                                    keys_streamed) + j, 0, n_str - 1)
    return steps, tile


def _fwd_call(q_bhsd, k_bhsd, v_bhsd, offsets, *, causal, scale,
              block_q, block_k, interpret, window=None):
    b, h, sq, d = q_bhsd.shape
    sk = k_bhsd.shape[2]
    nq, nk = sq // block_q, sk // block_k
    steps, kt = _streamed_index(nq, nk, block_q, block_k, window, True)
    grid = (b, h, nq, steps)
    kern = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                             block_q=block_q, block_k=block_k)
    if window is not None:
        kern = functools.partial(kern, window=window, n_str=nk)
    out, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2), lambda b, h, i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, i, j: (b, h, kt(i, j), 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, i, j: (b, h, kt(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, i, j: (b, h, i, 0)),
            # lse rows replicated over 8 sublanes so the (…, 8, block_q)
            # tile meets Mosaic's (8, 128)-alignment; squeezed by callers.
            pl.BlockSpec((1, 1, 8, block_q),
                         lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q_bhsd.dtype),
            jax.ShapeDtypeStruct((b, h, 8, sq), jnp.float32),
        ],
        # m, l as lane-dense rows (8 sublanes for the (8, 128) tiling, like
        # lse); the output accumulator transposed, as the body works.
        scratch_shapes=[
            pltpu.VMEM((8, block_q), jnp.float32),
            pltpu.VMEM((8, block_q), jnp.float32),
            pltpu.VMEM((d, block_q), jnp.float32),
        ],
        compiler_params=_compiler_params(3),
        interpret=interpret,
        name="hvd_flash_fwd" + _win(window),
    )(offsets, q_bhsd, k_bhsd, v_bhsd)
    return out, lse[:, :, 0, :]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, causal: bool, scale: float,
                   block_q: int, block_k: int, window: Optional[int] = None,
                   n_str: int = 0):
    i = pl.program_id(2)          # q tile
    j = pl.program_id(3)          # k step (innermost)
    nk = pl.num_programs(3)
    kt, inside = _streamed_tile(i, j, n_str, block_q, block_k, window, True)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_off = off_ref[0, 0]
    kv_off = off_ref[0, 1]
    q_start = q_off + i * block_q
    k_start = kv_off + kt * block_k
    live = _tile_live(q_start, k_start, block_q, block_k, causal, window,
                      inside)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                                        # (bq, D)
        k = k_ref[0, 0]                                        # (bk, D)
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:1, :]                             # (1, bq)
        delta = delta_ref[0, 0][:1, :]
        st = _scores_t(q, k, causal=causal, scale=scale, q_start=q_start,
                       k_start=k_start, window=window)         # (bk, bq)
        pt = jnp.where(jnp.logical_or(st <= _NEG_INF / 2,
                                      lse <= _NEG_INF / 2),
                       0.0, jnp.exp(st - lse))
        dpt = jax.lax.dot_general(
            v, do, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bk, bq)
        dst = pt * (dpt - delta) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            k, dst.astype(k.dtype),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (D, bq)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = jnp.transpose(dq_scr[:]).astype(dq_ref.dtype)


def _bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                    scale: float, block_q: int, block_k: int,
                    window: Optional[int] = None, n_str: int = 0):
    i = pl.program_id(2)          # k tile
    j = pl.program_id(3)          # q step (innermost)
    nq = pl.num_programs(3)
    qt, inside = _streamed_tile(i, j, n_str, block_k, block_q, window, False)

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_off = off_ref[0, 0]
    kv_off = off_ref[0, 1]
    q_start = q_off + qt * block_q
    k_start = kv_off + i * block_k
    live = _tile_live(q_start, k_start, block_q, block_k, causal, window,
                      inside)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                                        # (bq, D)
        k = k_ref[0, 0]                                        # (bk, D)
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:1, :]                             # (1, bq)
        delta = delta_ref[0, 0][:1, :]
        st = _scores_t(q, k, causal=causal, scale=scale, q_start=q_start,
                       k_start=k_start, window=window)         # (bk, bq)
        pt = jnp.where(jnp.logical_or(st <= _NEG_INF / 2,
                                      lse <= _NEG_INF / 2),
                       0.0, jnp.exp(st - lse))                 # (bk, bq)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pt.astype(do.dtype), do,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bk, D)
        dpt = jax.lax.dot_general(
            v, do, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bk, bq)
        dst = pt * (dpt - delta) * scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            dst.astype(q.dtype), q,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bk, D)

    @pl.when(j == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_call(q_bhsd, k_bhsd, v_bhsd, do_bhsd, lse, delta, offsets, *,
              causal, scale, block_q, block_k, interpret, window=None):
    b, h, sq, d = q_bhsd.shape
    sk = k_bhsd.shape[2]
    nq, nk = sq // block_q, sk // block_k

    # Row statistics in the sublane-replicated (B, H, 8, S) kernel layout.
    lse = jnp.broadcast_to(lse[:, :, None, :], (b, h, 8, sq))
    delta = jnp.broadcast_to(delta[:, :, None, :], (b, h, 8, sq))

    off_spec = pl.BlockSpec((1, 2), lambda b, h, i, j: (0, 0),
                            memory_space=pltpu.SMEM)

    def q_spec(ix):
        return pl.BlockSpec((1, 1, block_q, d), ix)

    def k_spec(ix):
        return pl.BlockSpec((1, 1, block_k, d), ix)

    def row_spec(ix):
        return pl.BlockSpec((1, 1, 8, block_q), ix)

    def kernel(body, n_str):
        kern = functools.partial(body, causal=causal, scale=scale,
                                 block_q=block_q, block_k=block_k)
        if window is not None:
            kern = functools.partial(kern, window=window, n_str=n_str)
        return kern

    # dQ: grid over (q tiles, k steps), k innermost.
    k_steps, kt = _streamed_index(nq, nk, block_q, block_k, window, True)
    dq = pl.pallas_call(
        kernel(_bwd_dq_kernel, nk),
        grid=(b, h, nq, k_steps),
        in_specs=[
            off_spec,
            q_spec(lambda b, h, i, j: (b, h, i, 0)),
            k_spec(lambda b, h, i, j: (b, h, kt(i, j), 0)),
            k_spec(lambda b, h, i, j: (b, h, kt(i, j), 0)),
            q_spec(lambda b, h, i, j: (b, h, i, 0)),
            row_spec(lambda b, h, i, j: (b, h, 0, i)),
            row_spec(lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_specs=q_spec(lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q_bhsd.dtype),
        scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32)],
        compiler_params=_compiler_params(3),
        interpret=interpret,
        name="hvd_flash_bwd_dq" + _win(window),
    )(offsets, q_bhsd, k_bhsd, v_bhsd, do_bhsd, lse, delta)

    # dK/dV: grid over (k tiles, q steps), q innermost.
    q_steps, qt = _streamed_index(nk, nq, block_k, block_q, window, False)
    dk, dv = pl.pallas_call(
        kernel(_bwd_dkv_kernel, nq),
        grid=(b, h, nk, q_steps),
        in_specs=[
            off_spec,
            q_spec(lambda b, h, i, j: (b, h, qt(i, j), 0)),
            k_spec(lambda b, h, i, j: (b, h, i, 0)),
            k_spec(lambda b, h, i, j: (b, h, i, 0)),
            q_spec(lambda b, h, i, j: (b, h, qt(i, j), 0)),
            row_spec(lambda b, h, i, j: (b, h, 0, qt(i, j))),
            row_spec(lambda b, h, i, j: (b, h, 0, qt(i, j))),
        ],
        out_specs=[
            k_spec(lambda b, h, i, j: (b, h, i, 0)),
            k_spec(lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k_bhsd.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v_bhsd.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(3),
        interpret=interpret,
        name="hvd_flash_bwd_dkv" + _win(window),
    )(offsets, q_bhsd, k_bhsd, v_bhsd, do_bhsd, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Differentiable entry points (custom VJP on (B, S, H, D) layout)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, offsets, causal, scale, block_q, block_k, interpret,
           window=None):
    out, _ = _flash_impl(q, k, v, offsets, causal, scale, block_q, block_k,
                         interpret, window)
    return out


def _flash_impl(q, k, v, offsets, causal, scale, block_q, block_k,
                interpret, window=None):
    qt = q.transpose(0, 2, 1, 3)      # (B, H, S, D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out, lse = _fwd_call(qt, kt, vt, offsets, causal=causal, scale=scale,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret, window=window)
    return out.transpose(0, 2, 1, 3), lse


def _flash_fwd(q, k, v, offsets, causal, scale, block_q, block_k, interpret,
               window=None):
    out, lse = _flash_impl(q, k, v, offsets, causal, scale, block_q,
                           block_k, interpret, window)
    # Named for ``checkpoint_keeping_attention``.  The primal output and the
    # residual are both the named value reshaped back: were either the
    # kernel's own output beside a named copy, the recompute would need the
    # kernel again to make it.
    b, s, h, d = out.shape
    out = checkpoint_name(out.reshape(b, s, h * d), SAVED_OUT)
    out = out.reshape(b, s, h, d)
    lse = checkpoint_name(lse, SAVED_LSE)
    return out, (q, k, v, offsets, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res, g):
    q, k, v, offsets, out, lse = res
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = g.transpose(0, 2, 1, 3)
    # In the layout ``g`` and the saved ``out`` arrive in: from a transposed
    # ``out`` XLA widened the saved (B, S, H·D) stack's slice to fp32 and
    # transposed that, 3 ms a layer in the BERT cell (PERF.md section 6,
    # PR 32).
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)                # (B, H, Sq)
    dq, dk, dv = _bwd_call(qt, kt, vt, dot, lse, delta, offsets,
                           causal=causal, scale=scale, block_q=block_q,
                           block_k=block_k, interpret=interpret,
                           window=window)
    d_off = np.zeros(offsets.shape, dtype=jax.dtypes.float0)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3), d_off)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _supported(q, k, window: Optional[int] = None
               ) -> Optional[Tuple[int, int]]:
    """The (block_q, block_k) all three kernels tile these (B, S, H, D)
    shapes with, or None where they cannot.  Read from the two sequence
    lengths, from the head's width and type for what fits VMEM, and from
    the window where the call has one."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if d % 8 != 0 or d > 512:
        return None
    row_bytes = d * jnp.dtype(q.dtype).itemsize
    widest = (_BLOCK_CANDIDATES[0] if row_bytes <= _WIDE_BLOCK_ROW_BYTES
              else _NARROW_BLOCK)
    if window is not None:
        widest = min(widest, _window_block(window))
    bq = _pick_block(sq, widest, env="HVD_TPU_FLASH_BLOCK_Q")
    bk = _pick_block(sk, widest, env="HVD_TPU_FLASH_BLOCK_K")
    if bq is None or bk is None:
        return None
    return bq, bk


def _window_block(window: int) -> int:
    """The widest tile of a windowed call: the widest candidate no wider
    than the window.  A band ``window`` wide under square tiles of b
    computes ``b + window`` (rounded up to tiles) score elements a query
    row for ``window`` live, in ``window / b + 1`` steps a resident tile:
    wider than the window is mostly dead elements, narrower is more steps.
    Bare on a v5e, (2, 8192, 36, 128) bf16, window 512, ms a call (PERF.md
    section 6, PR 33):

        tile (bq x bk)  1024x1024  1024x512  512x1024  512x512  512x256  256x256  128x128
        forward             6.18      5.37      6.03     4.71     5.28     6.69    13.51
        dQ                  6.28      5.24      5.80     4.58     5.46     5.80    13.63
        dK/dV               8.64      8.46      7.21     5.60     6.31     7.11    12.81
    """
    return next((c for c in _BLOCK_CANDIDATES if c <= window),
                _BLOCK_CANDIDATES[-1])


def _checked_window(window, causal, q, k, q_offset, kv_offset):
    """``window`` as the kernels take it: None where it masks nothing (a
    window that reaches the first key is the causal call, bit for bit)."""
    if window is None:
        return None
    if window < 1:
        raise ValueError(f"window {window} must be at least 1")
    if not causal:
        raise ValueError("a window is a causal call's: q_pos - window < "
                         "k_pos <= q_pos")
    if q.shape[1] != k.shape[1] or not (
            isinstance(q_offset, int) and isinstance(kv_offset, int)
            and q_offset == kv_offset == 0):
        raise NotImplementedError(
            "a windowed call takes one whole sequence: equal lengths and "
            "no offsets (the band's grid is laid out at trace time)")
    return None if window >= k.shape[1] else int(window)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: Optional[float] = None,
                    q_offset=0, kv_offset=0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    window: Optional[int] = None) -> jax.Array:
    """Differentiable fused attention; (B, S, H, D) in and out.

    ``window`` (causal only) keeps for each query the ``window`` keys up to
    and including its own.  A shape the kernels cannot tile (``_supported``
    is None) takes the XLA path with the same semantics."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    window = _checked_window(window, causal, q, k, q_offset, kv_offset)
    blocks = _supported(q, k, window)
    if blocks is None:
        out, _ = _xla_attention_with_lse(q, k, v, causal, scale,
                                         q_offset, kv_offset, window)
        return out
    bq, bk = blocks
    if block_q:
        if q.shape[1] % block_q != 0:
            raise ValueError(
                f"block_q={block_q} must divide seq_q={q.shape[1]}")
        bq = block_q
    if block_k:
        if k.shape[1] % block_k != 0:
            raise ValueError(
                f"block_k={block_k} must divide seq_k={k.shape[1]}")
        bk = block_k
    offsets = jnp.stack(
        [jnp.asarray(q_offset, jnp.int32),
         jnp.asarray(kv_offset, jnp.int32)]).reshape(1, 2)
    return _flash(q, k, v, offsets, causal, float(scale), bq, bk,
                  bool(interpret), window)


def flash_attention_with_lse(q, k, v, causal: bool = True,
                             scale: Optional[float] = None,
                             q_offset=0, kv_offset=0,
                             interpret: bool = False):
    """Non-differentiable primitive returning (out, lse).

    ``lse`` is (B, H, Sq) fp32 — the softmax log-normalizer per query row,
    ``_NEG_INF`` where the row saw no unmasked key. Ring attention merges
    per-step (out, lse) pairs with :func:`combine_blocks`.
    """
    blocks = _supported(q, k)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if blocks is None:
        return _xla_attention_with_lse(q, k, v, causal, scale,
                                       q_offset, kv_offset)
    offsets = jnp.stack(
        [jnp.asarray(q_offset, jnp.int32),
         jnp.asarray(kv_offset, jnp.int32)]).reshape(1, 2)
    return _flash_impl(q, k, v, offsets, causal, float(scale), blocks[0],
                       blocks[1], bool(interpret))


def _xla_attention_with_lse(q, k, v, causal, scale, q_offset, kv_offset,
                            window=None):
    """XLA fallback with identical (out, lse) semantics."""
    sq, sk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        q_pos = q_offset + jnp.arange(sq)
        k_pos = kv_offset + jnp.arange(sk)
        mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.maximum(m, _NEG_INF / 2)
    p = jnp.where(s <= _NEG_INF / 2, 0.0, jnp.exp(s - m_safe[..., None]))
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.maximum(l, 1e-30)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / l_safe[..., None],
                     v.astype(jnp.float32))
    lse = jnp.where(l <= 0.0, _NEG_INF, m_safe + jnp.log(l_safe))
    return out.astype(q.dtype), lse


def combine_blocks(o1, lse1, o2, lse2):
    """Merge two normalized blockwise-attention partials exactly.

    o*: (B, S, H, D); lse*: (B, H, S). Returns (o, lse) of the union of the
    two key sets, as if softmax had been computed over both at once.
    """
    lse_new = jnp.where(
        jnp.logical_and(lse1 <= _NEG_INF / 2, lse2 <= _NEG_INF / 2),
        _NEG_INF, jnp.logaddexp(lse1, lse2))
    w1 = jnp.where(lse1 <= _NEG_INF / 2, 0.0, jnp.exp(lse1 - lse_new))
    w2 = jnp.where(lse2 <= _NEG_INF / 2, 0.0, jnp.exp(lse2 - lse_new))
    w1 = w1.transpose(0, 2, 1)[..., None]        # (B, S, H, 1)
    w2 = w2.transpose(0, 2, 1)[..., None]
    o = o1.astype(jnp.float32) * w1 + o2.astype(jnp.float32) * w2
    return o.astype(o1.dtype), lse_new
