"""The state-space mixer's three pieces (Mamba-2, Dao & Gu 2024,
arXiv:2405.21060): a causal depthwise convolution (and the same over a
product of two factors, which a gated short-convolution block takes), the
selective state-space recurrence by the chunked (state-space dual)
algorithm, and the gated group RMSNorm.  Plain ``jax.numpy`` that XLA compiles; the
backward pass is JAX's differentiation of this program.  A Pallas kernel
of the scan is ROADMAP M7's other half.

The recurrence, per head h of group g with state ``S`` (P x N):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t^g
    y_t = S_t C_t^g + D x_t

Chunked at ``chunk`` positions: inside a chunk ``y = (L o C B^T) (dt x)``
with ``L[i, j] = exp(a_i - a_j)`` for i >= j (``a`` the cumulative sum of
``dt A`` inside the chunk) and 0 above the diagonal; a chunk's final state
is ``sum_j exp(a_last - a_j) dt_j x_j (x) B_j`` plus the carried state
decayed by ``exp(a_last)``; position i also reads the state carried into
its chunk through ``exp(a_i) C_i``.

Types: ``dt``, the cumulative log-decays, their exponentials and the
carried state are fp32; the operands of the four matrix products are
``x.dtype`` (bf16 in training), their accumulators fp32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal convolution along the sequence: ``out[t, c] = b[c]
    + sum_j w[c, j] x[t - (K - 1) + j, c]`` with positions before 0 read as
    0, so that position t sees itself and the K - 1 before it.  ``x``:
    (batch, S, channels); ``w``: (channels, K); ``b``: (channels,).  K
    shifted products in fp32, the result in ``x.dtype``."""
    k = w.shape[1]
    s = x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    out = b.astype(jnp.float32)
    for j in range(k):
        out = out + xp[:, j:j + s] * w[:, j].astype(jnp.float32)
    return out.astype(x.dtype)


def gated_causal_conv1d(x: jax.Array, gate: jax.Array,
                        w: jax.Array) -> jax.Array:
    """:func:`causal_conv1d` of ``x * gate`` (both (batch, S, channels)),
    without a bias, in fp32 for the caller to round:
    ``out[t, c] = sum_j w[c, j] (x * gate)[t - (K - 1) + j, c]``.
    Each tap's product is taken from the two factors shifted in their own
    type, so that neither the product nor a widened copy of a factor is an
    array of its own: one pass reads ``x`` and ``gate`` once (compiled for a
    v5e, PERF.md section 6, PR 41: a product made first, in fp32, was a
    write and K reads of 4 bytes a value beside factors of 2)."""
    k = w.shape[1]
    s = x.shape[1]

    def shifted(t, by):          # position t reads t - by, 0 before the first
        if by == 0:
            return t.astype(jnp.float32)
        return jnp.pad(t, ((0, 0), (by, 0), (0, 0)))[:, :s].astype(jnp.float32)

    out = 0.0
    for j in range(k):
        by = k - 1 - j
        tap = shifted(x, by) * shifted(gate, by) * w[:, j].astype(jnp.float32)
        out = tap if j == 0 else out + tap
    return out


def gated_group_rmsnorm(y: jax.Array, z: jax.Array, scale: jax.Array,
                        n_groups: int, eps: float) -> jax.Array:
    """``RMSNorm(y * silu(z))`` over each of ``n_groups`` equal groups of
    the last axis, with a scale a feature: the gate first, then the norm
    (Mamba-2's ``norm_before_gate=False``).  fp32 inside, ``y.dtype``
    out."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = g.reshape(g.shape[:-1] + (n_groups, -1))
    var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    normed = (grouped * lax.rsqrt(var + eps)).reshape(g.shape)
    return (normed * scale.astype(jnp.float32)).astype(y.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, chunk: int) -> jax.Array:
    """The recurrence above for every position, zero state before the
    first.  ``x``: (batch, S, H, P); ``dt``: (batch, S, H) fp32, already
    positive (softplus applied); ``a``: (H,) negative; ``b``, ``c``:
    (batch, S, G, N) with H a multiple of G (head h reads group h // (H /
    G)); ``d``: (H,).  Returns (batch, S, H, P) in ``x.dtype``.  A sequence
    that is not a multiple of ``chunk`` is padded with ``dt = 0`` positions
    (decay 1, no input), which change nothing before them."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    r = h // g
    dtype = x.dtype
    f32 = jnp.float32
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    dt = dt.astype(f32)
    # (batch, chunks, chunk, ...), heads as (group, heads of the group).
    xc = x.reshape(bsz, nc, chunk, g, r, p)
    dtc = dt.reshape(bsz, nc, chunk, g, r)
    bc = b.reshape(bsz, nc, chunk, g, n)
    cc = c.reshape(bsz, nc, chunk, g, n)
    log_decay = dtc * a.astype(f32).reshape(g, r)            # <= 0
    cum = jnp.cumsum(log_decay, axis=2)                      # a_i, fp32
    total = cum[:, :, -1]                                    # (b, nc, g, r)
    xdt = (xc.astype(f32) * dtc[..., None])                  # dt_j x_j, fp32

    # Inside the chunk: (L o C B^T) (dt x).  The (chunk, chunk) pair is
    # kept minor, heads before it.
    scores = jnp.einsum("bcign,bcjgn->bcgij", cc, bc,
                        preferred_element_type=f32)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    by_head = cum.transpose(0, 1, 3, 4, 2)                   # (b,nc,g,r,i)
    # exp of a difference of cumulative sums, masked before the exp so the
    # upper triangle (positive exponents) cannot overflow.
    diff = by_head[..., :, None] - by_head[..., None, :]     # (b,nc,g,r,i,j)
    decay = jnp.exp(jnp.where(tri, diff, -jnp.inf))
    weights = (decay * scores[:, :, :, None]).astype(dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", weights, xdt.astype(dtype),
                   preferred_element_type=f32)

    # Each chunk's own contribution to its final state.
    to_end = jnp.exp(total[:, :, None] - cum)                # (b,nc,j,g,r)
    own = jnp.einsum("bcjgn,bcjgrp->bcgrpn", bc,
                     (xdt * to_end[..., None]).astype(dtype),
                     preferred_element_type=f32)

    # Between chunks: the carried state, fp32, one step a chunk.
    def carry(state, inp):
        own_c, total_c = inp
        return state * jnp.exp(total_c)[..., None, None] + own_c, state

    _, entering = lax.scan(
        carry, jnp.zeros((bsz, g, r, p, n), f32),
        (own.transpose(1, 0, 2, 3, 4, 5), total.transpose(1, 0, 2, 3)))
    entering = entering.transpose(1, 0, 2, 3, 4, 5)          # (b,nc,g,r,p,n)

    # What position i reads of the state carried into its chunk.
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", cc, entering.astype(dtype),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y + xc.astype(f32) * d.astype(f32).reshape(g, r)[:, :, None]
    return y.reshape(bsz, s + pad, h, p)[:, :s].astype(dtype)


def ssd_recurrence(x, dt, a, b, c, d):
    """The same function position by position (a ``lax.scan`` over S), in
    the arguments' types: what :func:`ssd_scan` is tested against."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp                  # (b,h,p) (b,h) (b,g,n) x2
        b_h = jnp.repeat(b_t, r, axis=1)
        c_h = jnp.repeat(c_t, r, axis=1)
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_h) + d[:, None] * x_t

    _, y = lax.scan(step, jnp.zeros((bsz, h, p, n), x.dtype),
                    tuple(t.swapaxes(0, 1) for t in (x, dt, b, c)))
    return y.swapaxes(0, 1)
