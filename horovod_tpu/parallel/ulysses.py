"""Ulysses-style sequence parallelism — all-to-all head/sequence resharding.

The second of the two standard long-context layouts (beside ring attention,
ring_attention.py).  The reference's only sequence-layout primitive is
alltoall (SURVEY.md §5.7: "the building block a Ulysses-style SP would
use"); this module is that layout made first-class on TPU:

1. activations arrive sequence-sharded: (B, S/P, H, D);
2. one ``all_to_all`` trades the sequence shards for head shards:
   (B, S, H/P, D) — every device now sees the **full** sequence for a
   subset of heads;
3. plain (flash) attention runs locally — no per-step ring hops, one
   collective each way, which on ICI is a single fused all-to-all;
4. a second ``all_to_all`` restores sequence sharding.

Compared with ring attention: 2 collectives total instead of P ppermute
rounds (better for moderate P / long S), but requires heads % P == 0 and
peak activation memory holds the full sequence for H/P heads.

Call inside ``shard_map`` with the sequence axis sharded over
``axis_name``; differentiable by JAX AD (all_to_all transposes to itself).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax import lax
from ..compat import axis_size

from . import ring_attention as ra


def _seq_to_head_sharded(x, axis_name):
    # (…, B, S/P, H, D) → (…, B, S, H/P, D); leading stack dims allowed.
    nd = x.ndim
    return lax.all_to_all(x, axis_name, split_axis=nd - 2,
                          concat_axis=nd - 3, tiled=True)


def _head_to_seq_sharded(x, axis_name):
    # (…, B, S, H/P, D) → (…, B, S/P, H, D)
    nd = x.ndim
    return lax.all_to_all(x, axis_name, split_axis=nd - 3,
                          concat_axis=nd - 2, tiled=True)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str, causal: bool = True,
                      scale: Optional[float] = None,
                      window: Optional[int] = None,
                      diffusion_block: Optional[int] = None) -> jax.Array:
    """Exact attention over a sequence-sharded axis via head resharding.

    q, k, v: (B, S_local, H, D) shards; returns the (B, S_local, H, D)
    output shard.  Requires H divisible by the axis size.  A ``window`` is
    refused, as ring attention refuses it: no model runs windowed layers
    sequence-parallel yet.  So is a ``diffusion_block``.
    """
    if window is not None:
        raise NotImplementedError(
            "ulysses attention takes no sliding window: windowed layers run "
            "through full_attention (attn_mode 'megatron')")
    if diffusion_block is not None:
        raise NotImplementedError(
            "ulysses attention takes no block-diffusion mask: the doubled "
            "sequence runs whole through full_attention (attn_mode "
            "'megatron', mp 1)")
    sp = axis_size(axis_name)
    h = q.shape[2]
    if h % sp != 0:
        raise ValueError(
            f"ulysses_attention needs heads ({h}) divisible by the "
            f"sequence-parallel degree ({sp}); use ring_attention for "
            "head counts that don't divide")
    if sp == 1:
        return ra.full_attention(q, k, v, causal=causal, scale=scale)
    # One fused all-to-all for q/k/v (stacked on a leading dim) + one for
    # the output: 2 collective launches per attention, not 4.
    import jax.numpy as jnp
    qkv = _seq_to_head_sharded(jnp.stack([q, k, v]), axis_name)
    oh = ra.full_attention(qkv[0], qkv[1], qkv[2], causal=causal,
                           scale=scale)
    return _head_to_seq_sharded(oh, axis_name)
