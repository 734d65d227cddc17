"""Plain reference for OLMoE-1B-7B (Muennighoff et al. 2024,
arXiv:2409.02060; HF ``modeling_olmoe.py``): forward pass and training loss
in fp32 ``jax.numpy``, written from the layer equations.  No kernel, no
sort, no grouped matmul, no ``shard_map``, nothing imported from
``horovod_tpu``.  Gradients are ``jax.grad`` of this loss.  The caller puts
``jax.default_matmul_precision("highest")`` around the whole jitted call.

The layer:

    h     = RMSNorm(x_l; g1_l)
    q,k,v = h Wq_l, h Wk_l, h Wv_l
    q, k  = RMSNorm(q; gq_l), RMSNorm(k; gk_l)        over all H*hd features,
                                                      before the heads split
    q, k  = RoPE(q), RoPE(k)                          rotate-half, theta
    a_l   = x_l + Attn(q, k, v) Wo_l                  causal, scale 1/sqrt(hd)
    h     = RMSNorm(a_l; g2_l)
    p     = softmax(h Wg_l)                           over all E experts
    x_l+1 = a_l + sum over the top_k experts e of p, in p's own value (not
            renormalised): p_e (silu(h Wgate_l[e]) * (h Wup_l[e])) Wdown_l[e]
    logits = RMSNorm(x_L; g_f) Wlm^T                  untied head
    loss  = mean over positions of -log softmax(logits)[label]
            + aux * mean_l(E sum_e f_le P_le) + z * mean_l(mean_t lse_lt^2)

    RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g
    RoPE(t)_i = [t1 cos - t2 sin, t2 cos + t1 sin], t = [t1, t2] split at
                hd/2, angle = position * theta^(-2i/hd)
    f_le = tokens that chose expert e at layer l / tokens (the f add up to
           top_k: HF ``load_balancing_loss_func``), P_le = mean_t p_lte,
           lse_lt = logsumexp(h_t Wg_l); all three over every token of the
           batch, the losses layer by layer, then the mean over the layers.

Every expert is evaluated for every token and weighted by ``p`` where the
token chose it and by 0 where it did not: a mask, not a dispatch.

Weight layout (a fact about the parameters, not about the code under test):
``wqkv`` is (d, H*3*hd) with the fused dimension ordered head-major, then
q/k/v, then the head dimension; ``q_norm`` / ``k_norm`` are (H*hd,)
head-major.  ``layers`` leaves are stacked (L, ...).

Memory: one sequence at a time under ``jax.checkpoint`` (attention is per
sequence, everything else per token, and the router's statistics are sums),
attention in blocks of queries, the layers under a checkpointed
``lax.scan``, each expert under a checkpoint of its own.  At the published
widths one sequence's fp32 logits (4096 x 50304) are 0.77 GiB.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# |system - reference| allowed, and why.  The system computes matmuls and
# attention in bf16 with fp32 normalisation, router, softmax statistics and
# head; the reference is fp32 throughout at the highest matmul precision.
#  - A router decides by comparison.  Where a token's 8th and 9th
#    probabilities lie closer than the bf16 noise of the router's input
#    (0.2 % of a logit's spread against a mean gap of 8 %: some 3 % of the
#    tokens, 0.4 % of the (token, expert) pairs), system and reference send
#    the token to different experts.  For that token the block's output, the
#    hidden state the head sees and every gradient that flows back through
#    it differ by tens of percent, so EVERY leaf's gradient moves by about
#    sqrt(share of such tokens) x that, not only the experts'.  It is the
#    price of a discrete choice under bf16, not an error of either side: with
#    the compute type set to fp32 the system equals this file to 1e-6 on
#    every leaf and layout (tests/benchmark_tests/test_benchmark_olmoe.py).
#  - loss: a mean over positions, so the flips average out, less well than
#    the flagship's rounding does.  Measured on the chip at the published
#    widths, 2 sequences of 4096 a check (my chip runs, PR 26, calls o1-o5):
#    system minus reference -9.2e-4..+6.8e-4 over 26 checks of 22 seeds,
#    centred on 0 with a spread near 4.5e-4; 1e-5..6e-5 on the CPU at the
#    tests' size.  The bound is 3 x the largest, and still under what the
#    8-bit readings below give.
#  - gradients: relative L2 error per leaf.  Measured on the chip at the
#    published widths over 8 sequences (4 seeds x 2) and in 4 traced runs:
#    worst leaf 6.2-7.0 % (the router's ``gate``), experts 5.8-6.3 %,
#    attention and embedding 5.0-5.5 %, head 4.0-4.4 %, final norm
#    1.9-2.1 %; at the tests' size the
#    experts are worst, 6.7-9.7 %, and the leaves no decision reaches are
#    the flagship's 0.4-2.4 %.  The bound is a little over twice the
#    largest on the chip.  What it still refuses, each by a test: top-7
#    routing (35 %), renormalised top-k weights (84 %), a capacity clamp at
#    1.25 (180 %), no QK-norm, RoPE with the halves swapped, a tied head (all
#    over 100 %); and the nearest precision below the stated one: with every
#    matmul operand of this file rounded to an 8-bit float the worst leaf is
#    29 % (e5m2) and 84 % (e4m3) on the chip at the published widths, |loss
#    difference| 3.7e-3 and 1.3e-2, all four not correct.
#  - THE GAP: a fault that moves gradients by less than the flips do (a few
#    percent) passes, where the flagship's 3 % bound would refuse it; and as
#    there, these bounds say nothing about the attention softmax's type.
#  (The runner prints a bound to one digit: 1.5e-1 reads "1e-01" in its line.)
TOLERANCES = {"loss_abs": 3e-3, "grad_rel_l2": 1.5e-1}
Q_BLOCK = 1024


def matmul(a, b):
    """Every matrix product of this file, so that a test can ask what a
    lower precision would give by rounding the operands here."""
    return a @ b


def rmsnorm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def qk_norm(t, g, eps):
    """RMSNorm over all H*hd features of a projected q or k; t: (S, H, hd)."""
    return rmsnorm(t.reshape(t.shape[0], -1), g, eps).reshape(t.shape)


def rope(t, theta):
    """t: (S, H, hd), positions 0 .. S-1."""
    s, _, hd = t.shape
    half = hd // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def attention(q, k, v, q_block: int = Q_BLOCK):
    """Causal softmax(Q K^T / sqrt(hd)) V; q, k, v are (S, H, hd)."""
    s, h, hd = q.shape
    blk = min(q_block, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not a multiple of {blk}")
    k_pos = jnp.arange(s)

    @jax.checkpoint
    def one_block(args):
        i, q_i = args                                   # (blk, H, hd)
        scores = matmul(q_i.transpose(1, 0, 2),         # (H, blk, S)
                        k.transpose(1, 2, 0)) / math.sqrt(hd)
        q_pos = i * blk + jnp.arange(blk)
        scores = jnp.where(q_pos[:, None] >= k_pos[None, :], scores,
                           -jnp.inf)
        return matmul(jax.nn.softmax(scores, -1),
                      v.transpose(1, 0, 2)).transpose(1, 0, 2)

    out = lax.map(one_block, (jnp.arange(s // blk),
                              q.reshape(s // blk, blk, h, hd)))
    return out.reshape(s, h, hd)


def route(h, wg, top_k: int):
    """(weights (T, E): p where the token chose the expert, else 0;
    probabilities (T, E); logsumexp of the logits (T,))."""
    logits = matmul(h, wg)
    probs = jax.nn.softmax(logits, axis=-1)
    kth = lax.top_k(probs, top_k)[0][:, -1:]
    return (jnp.where(probs >= kth, probs, 0.0), probs,
            jax.nn.logsumexp(logits, axis=-1))


def experts(h, weights, w_gate, w_up, w_down):
    """sum_e weights[:, e] * (silu(h Wgate[e]) * (h Wup[e])) Wdown[e]."""
    @jax.checkpoint
    def one(w, wg, wu, wd):
        return w[:, None] * matmul(
            jax.nn.silu(matmul(h, wg)) * matmul(h, wu), wd)

    def add(y, ew):
        return y + one(*ew), None

    y, _ = lax.scan(add, jnp.zeros_like(h),
                    (weights.T, w_gate, w_up, w_down))
    return y


def sequence(params, tokens, labels, *, n_heads, top_k, rope_theta,
             norm_eps):
    """One sequence: (sum of the positions' negative log-likelihoods, and
    per layer the tokens that chose each expert (L, E), the sum of the
    router's probabilities (L, E) and of logsumexp^2 (L,))."""
    s = tokens.shape[0]
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, lp):
        h = rmsnorm(x, lp["ln1"], norm_eps)
        qkv = matmul(h, lp["wqkv"]).reshape(s, n_heads, 3, -1)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        q = rope(qk_norm(q, lp["q_norm"], norm_eps), rope_theta)
        k = rope(qk_norm(k, lp["k_norm"], norm_eps), rope_theta)
        x = x + matmul(attention(q, k, v).reshape(s, -1), lp["wo"])
        h = rmsnorm(x, lp["ln2"], norm_eps)
        weights, probs, lse = route(h, lp["gate"], top_k)
        x = x + experts(h, weights, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x, (jnp.sum(weights > 0, axis=0).astype(jnp.float32),
                   jnp.sum(probs, axis=0), jnp.sum(lse * lse))

    x, stats = lax.scan(layer, x, params["layers"])
    logits = matmul(rmsnorm(x, params["final_norm"], norm_eps),
                    params["lm_head"].T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.sum(jnp.take_along_axis(logp, labels[:, None], -1))
    return nll, stats


def loss(params, tokens, labels, *, n_heads: int, top_k: int,
         rope_theta: float, norm_eps: float, aux_loss_coef: float,
         z_loss_coef: float):
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    one = jax.checkpoint(lambda tl: sequence(
        params, *tl, n_heads=n_heads, top_k=top_k, rope_theta=rope_theta,
        norm_eps=norm_eps))
    nll, (chose, prob_sum, lse_sq) = jax.tree_util.tree_map(
        lambda a: jnp.sum(a, axis=0), lax.map(one, (tokens, labels)))
    n = tokens.size
    n_experts = chose.shape[-1]
    balance = n_experts * jnp.sum((chose / n) * (prob_sum / n), axis=-1)
    return (nll / n + aux_loss_coef * jnp.mean(balance)
            + z_loss_coef * jnp.mean(lse_sq / n))
