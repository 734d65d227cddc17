"""Plain reference for the flagship decoder-only transformer: forward pass
and loss in fp32 ``jax.numpy``, written from the layer equations.  No
kernel, no ``shard_map``, no cache, nothing imported from ``horovod_tpu``.
Gradients are ``jax.grad`` of this loss.  The caller puts
``jax.default_matmul_precision("highest")`` around the whole jitted call.

The block (what the repository's flagship computes; not a public model):

    x_0   = E[tokens] + P[0:S]                              learned positions
    a_l   = x_l + Attn(RMSNorm(x_l; g1_l)) Wo_l             pre-norm, causal
    x_l+1 = a_l + GELU_tanh(RMSNorm(a_l; g2_l) W1_l) W2_l
    logits = RMSNorm(x_L; g_f) E^T                          tied head, fp32
    loss  = mean over every position of -log softmax(logits)[label]

    RMSNorm(x; g) = x / sqrt(mean(x^2) + 1e-6) * g
    Attn: per head softmax(Q K^T / sqrt(hd) + causal mask) V, no bias

Weight layout (a fact about the parameters, not about the code under test):
``wqkv`` is (d, H*3*hd) with the fused dimension ordered head-major, then
q/k/v, then the head dimension.  ``layers`` leaves are stacked (L, ...).

Memory: attention runs in blocks of queries under ``jax.checkpoint`` and the
layers under a checkpointed ``lax.scan``, so at S = 8192 one block's fp32
scores (H x block x S) is alive at a time, not the (H, S, S) matrix.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# |system - reference| allowed, and why.  The system computes matmuls and
# attention in bf16 (8 bits of mantissa, relative rounding 2^-9 = 2e-3 per
# operand) with fp32 normalisation, softmax statistics and head; the
# reference is fp32 throughout at the highest matmul precision.
#  - loss: a mean of log-probabilities near ln(V); bf16 noise in the hidden
#    state averages out over positions.  Measured on the chip at the
#    published widths, over every run file PR 22's calls returned (call1,
#    call2, m1, m4, proof): 2.9e-6..2.3e-4 in 21 runs of the flagship on one
#    chip (8192 positions), 0..8.3e-5 in 9 runs on four (16384), and
#    5.9e-5..1.04e-3 in 21 runs of BERT on ONE sequence of 80 predicted
#    positions, which left the bound a factor of two: BERT's family now
#    checks 8 sequences (640 positions; families/bert.py).  3e-5..4e-5 on
#    the CPU at the tests' size.
#  - gradients: relative L2 error per leaf.  bf16 rounding of activations and
#    of the backward matmuls' operands gives 1.15-1.40 % on the worst leaf on
#    the chip at the published widths (the same run files), 1.0-1.5 % at the
#    tests' size: the bound is a little over twice that.  Dropping the causal
#    mask or the 1/sqrt(hd) scale moves gradients by more than twice the
#    bound and is refused
#    (tests/benchmark_tests/test_benchmark_reference.py).
#  - THE GAP: a softmax computed in bf16 is NOT refused at the tests' size.
#    With the reference's probabilities rounded to bf16 the worst leaf moves
#    from 1.49 % to 1.72 % (flagship) and from 1.04 % to 1.31 % (BERT) at
#    sequence 128 on the CPU, inside the bound, because the system's own
#    bf16 matmuls already cost as much.  Whether 8192 terms accumulated in
#    bf16 would pass at the published widths has not been tried on the chip;
#    until it has, these bounds say nothing about the softmax's type.
TOLERANCES = {"loss_abs": 2e-3, "grad_rel_l2": 3e-2}
Q_BLOCK = 1024


def rmsnorm(x, g):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * g


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x * x * x)))


def attention(q, k, v, causal: bool, q_block: int = Q_BLOCK):
    """softmax(Q K^T / sqrt(hd)) V; q, k, v are (B, S, H, hd)."""
    b, s, h, hd = q.shape
    blk = min(q_block, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not a multiple of {blk}")
    n_blk = s // blk
    k_pos = jnp.arange(s)

    @jax.checkpoint
    def one_block(args):
        i, q_i = args                                   # (B, blk, H, hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_i, k) / math.sqrt(hd)
        if causal:
            q_pos = i * blk + jnp.arange(blk)
            scores = jnp.where(q_pos[:, None] >= k_pos[None, :], scores,
                               -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    q_blocks = q.reshape(b, n_blk, blk, h, hd).transpose(1, 0, 2, 3, 4)
    out = lax.map(one_block, (jnp.arange(n_blk), q_blocks))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


def split_heads(qkv, n_heads: int):
    b, s, e = qkv.shape
    qkv = qkv.reshape(b, s, n_heads, 3, e // (3 * n_heads))
    return qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]


def loss(params, tokens, labels, *, n_heads: int):
    f32 = lambda t: jax.tree_util.tree_map(           # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    params = f32(params)
    s = tokens.shape[1]
    x = params["embed"][tokens] + params["pos"][None, :s]

    @jax.checkpoint
    def layer(x, lp):
        q, k, v = split_heads(rmsnorm(x, lp["ln1"]) @ lp["wqkv"], n_heads)
        o = attention(q, k, v, causal=True)
        x = x + o.reshape(x.shape[0], s, -1) @ lp["wo"]
        x = x + gelu_tanh(rmsnorm(x, lp["ln2"]) @ lp["w1"]) @ lp["w2"]
        return x, None

    x, _ = lax.scan(layer, x, params["layers"])
    logits = rmsnorm(x, params["final_norm"]) @ params["embed"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
