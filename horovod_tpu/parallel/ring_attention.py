"""Ring attention — sequence/context parallelism over a mesh axis.

Long-context training shards the sequence dimension across devices.  The
reference framework has no attention code (it is model-agnostic middleware —
SURVEY.md §5.7); the only primitive it offers for sequence layouts is
alltoall.  TPU-native, we make sequence parallelism first-class with ring
attention: Q stays resident, K/V shards rotate around the ring via
``lax.ppermute`` (riding ICI neighbor links), and each step accumulates a
blockwise-softmax partial (flash-attention online normalization, fp32
accumulators).  Communication per step is the K/V block — overlap with the
block matmul is XLA's latency-hiding scheduler's job.

Two compute paths per ring step:

* **Pallas flash kernel** (default on TPU): each step runs the fused
  ``ops/flash_attention.py`` kernel over the resident Q and the visiting
  K/V shard, returning (out, logsumexp); partials merge exactly via
  ``combine_blocks``.  The custom VJP re-walks the ring, accumulating dK/dV
  *onto the rotating shards* so each gradient lands back on its owner after
  a full revolution.
* **XLA path** (off-TPU, unsupported shapes): the original blockwise
  einsum recurrence, differentiated by JAX AD.

Layout: q, k, v are (batch, seq_local, heads, head_dim) shards of the global
(batch, seq_local * ring_size, heads, head_dim) arrays, sequence-major across
the axis: rank i holds positions [i*seq_local, (i+1)*seq_local).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from ..compat import axis_size

_NEG_INF = -1e30
_FLASH_MIN_SEQ = 512


def _flash_enabled(seq_k: Optional[int] = None) -> bool:
    """Dispatch policy for the fused kernel. ``HVD_TPU_FLASH=1/0`` forces;
    in auto mode, use it on TPU once the key sequence is long enough that
    the kernel's O(S) memory + tiling beat XLA's fused attention (measured
    on v5e: +18% BERT-Base train throughput already at S=512)."""
    v = os.environ.get("HVD_TPU_FLASH", "auto")
    if v == "0":
        return False
    if v == "1":
        return True
    if jax.default_backend() != "tpu":
        return False
    return seq_k is None or seq_k >= _FLASH_MIN_SEQ


def _block_attn(q, k, v, q_offset, kv_offset, causal, scale, m, l, o):
    """One blockwise attention step with online softmax accumulation.

    q: (B, Sq, H, D); k, v: (B, Sk, H, D); m, l: (B, H, Sq); o: (B, Sq, H, D).
    All accumulators fp32.
    """
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale  # (B,H,Sq,Sk)
    if causal:
        sq = q.shape[1]
        sk = k.shape[1]
        q_pos = q_offset + jnp.arange(sq)
        k_pos = kv_offset + jnp.arange(sk)
        mask = q_pos[:, None] >= k_pos[None, :]          # (Sq, Sk)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))               # (B,H,Sq)
    # exp(_NEG_INF - _NEG_INF) would be 1; clamp so fully-masked blocks stay 0.
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
    alpha = jnp.where(m <= _NEG_INF / 2, 0.0, alpha)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _ring_perm(sp):
    return [(i, (i - 1) % sp) for i in range(sp)]


def _ring_attention_xla(q, k, v, axis_name, causal, scale):
    sp = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape

    m = jnp.full((b, h, sq), _NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((b, h, sq), dtype=jnp.float32)
    o = jnp.zeros((b, sq, h, d), dtype=jnp.float32)
    q_offset = idx * sq

    # Send K/V to the left neighbor each step; after t steps we hold the
    # shard originating from rank (idx + t) % sp.
    perm = _ring_perm(sp)

    def body(t, carry):
        k_t, v_t, m_t, l_t, o_t = carry
        kv_rank = (idx + t) % sp
        kv_offset = kv_rank * sq
        m_t, l_t, o_t = _block_attn(q, k_t, v_t, q_offset, kv_offset,
                                    causal, scale, m_t, l_t, o_t)
        k_nxt = lax.ppermute(k_t, axis_name, perm)
        v_nxt = lax.ppermute(v_t, axis_name, perm)
        return k_nxt, v_nxt, m_t, l_t, o_t

    if sp == 1:
        _, _, m, l, o = body(0, (k, v, m, l, o))
    else:
        # Static python loop: sp is small and static; lets XLA pipeline the
        # ppermutes against the matmuls without a loop-carried dependence on
        # trip count.
        carry = (k, v, m, l, o)
        for t in range(sp):
            carry = body(t, carry)
        _, _, m, l, o = carry

    l = jnp.maximum(l, 1e-20)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash-kernel ring path (custom VJP; dK/dV ride the ring home)
# ---------------------------------------------------------------------------

def _ring_flash_forward(q, k, v, axis_name, causal, scale, interpret):
    from ..ops import flash_attention as fa
    sp = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    sq = q.shape[1]
    perm = _ring_perm(sp)
    q_offset = idx * sq

    o = lse = None
    k_t, v_t = k, v
    for t in range(sp):
        kv_rank = lax.rem(idx + t, sp)
        o_t, lse_t = fa.flash_attention_with_lse(
            q, k_t, v_t, causal=causal, scale=scale,
            q_offset=q_offset, kv_offset=kv_rank * sq, interpret=interpret)
        o_t = o_t.astype(jnp.float32)
        if o is None:
            o, lse = o_t, lse_t
        else:
            o, lse = fa.combine_blocks(o, lse, o_t, lse_t)
        if t < sp - 1:
            k_t = lax.ppermute(k_t, axis_name, perm)
            v_t = lax.ppermute(v_t, axis_name, perm)
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, causal, scale, interpret):
    out, _ = _ring_flash_forward(q, k, v, axis_name, causal, scale,
                                 interpret)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, interpret):
    out, lse = _ring_flash_forward(q, k, v, axis_name, causal, scale,
                                   interpret)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, interpret, res, g):
    from ..ops import flash_attention as fa
    q, k, v, out, lse = res
    sp = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    sq = q.shape[1]
    perm = _ring_perm(sp)

    bq, bk, group = fa._supported(q, k)

    rows = fa._rows               # the kernels' own layout: no copy
    dot = g.astype(q.dtype)
    delta = jnp.sum(dot.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)                # (B, H, Sq)

    dq = jnp.zeros(rows(q).shape, jnp.float32)
    k_t, v_t = rows(k), rows(v)
    dk_t = jnp.zeros(k_t.shape, jnp.float32)
    dv_t = jnp.zeros(v_t.shape, jnp.float32)
    for t in range(sp):
        kv_rank = lax.rem(idx + t, sp)
        offsets = jnp.stack([
            (idx * sq).astype(jnp.int32),
            (kv_rank * sq).astype(jnp.int32)]).reshape(1, 2)
        dq_b, dk_b, dv_b = fa._bwd_call(
            rows(q), k_t, v_t, rows(dot), lse, delta, offsets,
            heads=q.shape[2], group=group, causal=causal, scale=scale,
            block_q=bq, block_k=bk, interpret=interpret)
        dq = dq + dq_b.astype(jnp.float32)
        dk_t = dk_t + dk_b.astype(jnp.float32)
        dv_t = dv_t + dv_b.astype(jnp.float32)
        # Rotate after every step (sp total): each K/V shard — and the
        # gradient accumulating on it — completes a full revolution and
        # lands back on its owner.
        if sp > 1:
            k_t = lax.ppermute(k_t, axis_name, perm)
            v_t = lax.ppermute(v_t, axis_name, perm)
            dk_t = lax.ppermute(dk_t, axis_name, perm)
            dv_t = lax.ppermute(dv_t, axis_name, perm)
    return (dq.reshape(q.shape).astype(q.dtype),
            dk_t.reshape(k.shape).astype(k.dtype),
            dv_t.reshape(v.shape).astype(v.dtype))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool = True,
                   scale: Optional[float] = None,
                   use_flash: Optional[bool] = None,
                   interpret: bool = False,
                   window: Optional[int] = None,
                   diffusion_block: Optional[int] = None) -> jax.Array:
    """Exact attention over a sequence-sharded axis via K/V ring rotation.

    Call inside ``shard_map``; returns the local (B, Sq, H, D) output shard.
    ``interpret`` runs the flash kernels in the Pallas interpreter (CPU
    tests); it is never chosen here.  A ``window`` is refused: a band that
    crosses shards would visit only the neighbouring ring steps, which the
    walk does not know how to skip.  So is a ``diffusion_block``: the mask
    is laid out over one whole doubled sequence, and a shard of it is
    neither half.
    """
    if window is not None:
        raise NotImplementedError(
            "ring attention takes no sliding window: windowed layers run "
            "through full_attention (attn_mode 'megatron')")
    if diffusion_block is not None:
        raise NotImplementedError(
            "ring attention takes no block-diffusion mask: the doubled "
            "sequence runs whole through full_attention (attn_mode "
            "'megatron', mp 1)")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    from ..ops import flash_attention as fa
    if use_flash is None:
        use_flash = _flash_enabled(k.shape[1])
    # Even when requested, the kernel path needs tileable shapes — the
    # backward walk has no per-step XLA fallback.
    use_flash = use_flash and fa._supported(q, k) is not None
    if use_flash:
        return _ring_flash(q, k, v, axis_name, causal, float(scale),
                           bool(interpret))
    return _ring_attention_xla(q, k, v, axis_name, causal, scale)


def reference_attention(q, k, v, causal: bool = True,
                        scale: Optional[float] = None,
                        window: Optional[int] = None,
                        diffusion_block: Optional[int] = None,
                        visible: Optional[jax.Array] = None) -> jax.Array:
    """Pure-XLA unsharded attention — the numerics oracle for tests.
    ``window`` (causal only): each query sees the ``window`` keys up to and
    including its own, ``q_pos - window < k_pos <= q_pos``.
    ``diffusion_block`` (causal only, no window): the block-diffusion mask
    over a noised and a clean half, built densely
    (``ops/flash_attention.diffusion_mask``).  ``visible`` (B, Sq, Sk)
    bool, an explicit mask laid over the others: a query sees the keys its
    row marks and no other."""
    b, sq, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if window is not None and not causal:
        raise ValueError("a window is a causal call's")
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if diffusion_block is not None:
        from ..ops import flash_attention as fa
        fa._checked_diffusion(diffusion_block, causal, window, q, k, 0, 0)
        s = jnp.where(fa.diffusion_mask(sq, diffusion_block)[None, None], s,
                      _NEG_INF)
    elif causal:
        q_pos = jnp.arange(sq)
        k_pos = jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    if visible is not None:
        s = jnp.where(visible[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def checkpoint_keeping_attention(layer_fn):
    """``jax.checkpoint(layer_fn)`` for a layer that calls
    :func:`full_attention`: the backward recomputes the layer but for the
    flash forward kernel, whose output and ``lse`` are saved by name
    (``ops/flash_attention.py`` ``_flash_fwd``) — one (B, S, H·D) activation
    and one fp32 row statistic a layer.  The XLA path sets no names and is
    recomputed whole.  A layer of learned sparse attention also keeps what
    its selection made (``ops/sparse_index.py``: the int8 (B, S, S)
    visibility and the (B, S) log-normaliser of the indexer's loss), so the
    recompute ranks nothing again."""
    from ..ops import flash_attention as fa
    from ..ops import sparse_index as si
    return jax.checkpoint(
        layer_fn, policy=jax.checkpoint_policies.save_only_these_names(
            fa.SAVED_OUT, fa.SAVED_LSE, si.SAVED_VISIBLE,
            si.SAVED_INDEX_LSE))


def full_attention(q, k, v, causal: bool = True,
                   scale: Optional[float] = None,
                   use_flash: Optional[bool] = None,
                   window: Optional[int] = None,
                   diffusion_block: Optional[int] = None) -> jax.Array:
    """Unsharded attention (same layout as ring_attention). Dispatches to
    the fused Pallas kernel on TPU, XLA einsums elsewhere.  ``window``: a
    sliding window of that many keys a query, its own included.
    ``diffusion_block``: the sequence is a noised and a clean copy of the
    same tokens under the block-diffusion mask
    (``ops/flash_attention.flash_attention``)."""
    if use_flash is None:
        from ..ops import flash_attention as fa
        use_flash = (_flash_enabled(k.shape[1]) and
                     fa._supported(q, k, window, diffusion_block) is not None)
    if use_flash:
        from ..ops import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                  window=window,
                                  diffusion_block=diffusion_block)
    return reference_attention(q, k, v, causal=causal, scale=scale,
                               window=window,
                               diffusion_block=diffusion_block)


def selected_attention(q, k, v, visible_t, scale: Optional[float] = None,
                       use_flash: Optional[bool] = None):
    """Causal attention in which a query sees, of the keys at or before it,
    those a selection chose: ``(out, lse)``, (B, S, H, D) and (B, H, S)
    fp32.  ``visible_t`` (B, S keys, S queries) int8 is
    ``ops/sparse_index.select``'s result, data the step made and not a rule
    on positions, so it is an operand of the kernels
    (``ops/flash_attention.flash_attention(visible_t=)``) where the dispatch
    takes them and of the XLA path elsewhere.  Unsharded: a query's keys
    live anywhere in the sequence."""
    from ..ops import flash_attention as fa
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if use_flash is None:
        use_flash = (_flash_enabled(k.shape[1])
                     and fa._supported(q, k) is not None)
    if use_flash:
        return fa.flash_attention(q, k, v, causal=True, scale=scale,
                                  visible_t=visible_t)
    return fa._xla_attention_with_lse(q, k, v, True, scale, 0, 0,
                                      visible_t=visible_t)
