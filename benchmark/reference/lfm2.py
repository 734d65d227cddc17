"""Plain reference for LFM2-24B-A2B (``lfm2_moe``; LiquidAI/LFM2-24B-A2B
``config.json``; HF transformers ``modeling_lfm2_moe.py``): forward pass and
training loss in fp32 ``jax.numpy``, written from the layer equations.  No
kernel, no sort, no grouped matmul, no scan over layers, no ``shard_map``,
nothing imported from ``horovod_tpu``.  Gradients are ``jax.grad`` of this
loss.  The caller puts ``jax.default_matmul_precision("highest")`` around the
whole jitted call.

A layer is ``h = x + op(RMSNorm(x; g1))`` then ``y = h + ffn(RMSNorm(h;
g2))``, RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g:

  op    conv:  [B, C, u] = split3(n W_in)          three d-wide thirds
               g = B * u
               c[t] = sum_{j=0..K-1} w[:, j] * g[t - (K - 1) + j]
                      depthwise, causal (positions before 0 read 0), no
                      bias, no activation
               out = (C * c) W_out
        attention:  q = n Wq (H heads of hd), k = n Wk, v = n Wv (Hkv heads)
               q, k = RMSNorm over each head's hd features (one g of hd for
               q, one for k, shared by the heads), then RoPE: t = [t1, t2]
               at the head's half, [t1 cos - t2 sin, t2 cos + t1 sin] at
               angle position x theta^(-2i/hd)
               query head i reads key / value head i // (H / Hkv); query t
               sees the keys j <= t; softmax at 1/sqrt(hd)
               out = concat_i(o_i) Wo
  ffn   dense:  (silu(n W1) * n W3) W2
        sparse: s = sigmoid(n Wr) over all E router outputs; the top_k
               largest of s + b (b the correction bias, outside the
               gradient); w_e = s_e / (sum_chosen s + renorm_eps) x scale
               for the chosen, else 0;
               out = sum over the experts held of w_e (silu(n W1_e) * n
               W3_e) W2_e
  logits = RMSNorm(x_L; g_f) E^T on the tied embedding table E;  loss = mean
  over positions of -log softmax(logits)[label]

The experts held are the first ``w1.shape[0]`` of the router's outputs: what
the absent ones would have added is left out, as in the program.  Each held
expert is evaluated for every token and weighted by ``w_e`` where the token
chose it and by 0 where it did not: a mask, not a dispatch.  Nothing is
dropped.

Weight layout (a fact about the parameters): ``layers`` is a list, one dict
a published layer, ``{"op": ..., "ffn": ...}``; an ``op`` with a ``conv``
leaf is a convolution ``{ln, w_in, conv (d, K), w_out}``, else attention
``{ln, wq, wk, wv, q_norm, k_norm, wo}``; an ``ffn`` with a ``router`` leaf
is sparse ``{ln, router, w1, w3, w2 (leading axis: the experts held)}`` —
``router`` is (d + 1, E), the correction bias its last row — else dense
``{ln, w1, w3, w2}``; projections are (in, out) with the heads outermost in
a fused (H * hd) dimension.  The head is ``embed``.

Memory (not part of the equations): one sequence at a time, each layer's two
halves and each block of ``Q_BLOCK`` queries under ``jax.checkpoint``; a
block of queries is scored against every key and the causal mask is
explicit; the held experts one at a time in a ``lax.scan``; the dense MLP
and the head (both act on a token alone) ``T_BLOCK`` tokens at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# |system - reference| allowed, and why.  The system computes matmuls and
# attention in bf16 with fp32 normalisation, rotations, gates, convolution,
# router, softmax statistics and head; the reference is fp32 throughout at
# the highest matmul precision.  Measured on the chip at the published
# widths, one sequence of 32,768 a check, on the family's seeded weights
# (``families/lfm2.py`` ``init_params``: the correction bias balanced, the
# query and key heads each at a size of its own), through the runner's own
# comparison (my chip runs, PR 41: ``tools/lfm2_controls.py``,
# ``chiprun_out/pr41/controls.jsonl`` and ``final/controls_final.jsonl``,
# every leaf in ``leaves.jsonl``; PERF.md section 6, PR 41).
#  - loss: a mean over 32,768 positions, 9.42 on seeded weights.  System
#    minus reference 7.6e-6 .. 3.6e-4 in size over 23 readings; the limit
#    is the accepted expert cells' (Nemotron's, Laguna's, SDAR's), 4.1 times
#    the largest.  With every matmul operand of this file rounded to e5m2
#    the difference is 1.8e-3, over the limit; e4m3 3.4e-4, inside it: that
#    control is the gradients'.
#  - gradients: relative L2 error per leaf.  A router decides by comparison:
#    where a token's 4th and 5th of 64 scores + bias lie closer than the
#    bf16 noise of the router's input, system and reference choose different
#    experts — 4.5, 5.5, 6.7, 7.7 % of the tokens in the four sparse layers,
#    counted on the chip at 32,768 positions on two seeds
#    (``tools/lfm2_controls.py --flips``; PERF.md section 6, PR 41), and a
#    flipped choice is one of 4 a token, not one of 10 or 22 as in the
#    accepted expert cells, so it moves more.  With those tokens' sparse
#    blocks taken out of both gradients the same ``router`` leaves read
#    3.0-4.0 % for 19.6-26.6 %, the experts' 2.7-3.4 % for 14.2-19.1 %, and
#    no leaf reads over 4.04 %: the flips are the cause, not a guess at
#    it.  The last layer's ``router`` is the worst leaf of every
#    sound reading: 24.8 .. 27.8 % over ten seeds (the four routers 19.7,
#    23.7, 23.9, 26.9 % on one seed, later layers seeing more noise); the
#    experts' w1 / w3 / w2 and their norm 14.5-19.1 % (the rows a flip moves
#    from one expert to another); every leaf no decision reaches 3.4-4.0 %
#    (the flips' different backward signal, alike in every earlier leaf),
#    the final norm 0.7 %.  The controls, worst leaf: the bias left out of
#    the choice 43.2 and 45.9 % on two seeds (a ``router``), QK-norm over
#    all features instead of each head 89.4 % (32.0 % on heads all of one
#    size, where it could not be told from the flips: why the family draws
#    the heads' sizes), e5m2 80.6 %, the renormalisation left out 88.1 %,
#    e4m3 121 %, the taps reversed 143 % (and the loss by 6.3e-3), B and C
#    swapped 155 %, the chosen experts weighed alike, whatever their scores:
#    infinite (the reference's router then has no gradient).  The limit
#    stands between the largest sound reading and the weakest control, 1.26
#    times the one and 0.81 of the other; the sound readings lie within
#    three points of each other over the seeds (32,768 tokens average a
#    flip's weight out), so a fresh seed is not likely to read much higher.
#  - THE GAP: one limit for every leaf is set by the leaf the flips reach
#    most, so a fault that moves any leaf by less than 35 % passes here; the
#    CPU tests at compute type fp32 (system = this file to 1e-5 on every
#    leaf and layout) are what refuses such a fault.  PERF.md section 7.
#  (The runner prints a bound to one digit: 3.5e-1 reads "3e-01".)
TOLERANCES = {"loss_abs": 1.5e-3, "grad_rel_l2": 3.5e-1}
# 32 heads x 256 queries x 32,768 keys in fp32 is 1 GiB a score tile; 4,096
# tokens x 11,776 in fp32 is 184 MiB a dense hidden array.
Q_BLOCK = 256
T_BLOCK = 4096


def matmul(a, b):
    """Every matrix product of this file, so that a test can ask what a
    lower precision would give by rounding the operands here."""
    return a @ b


def rmsnorm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


# -- the convolution operator --------------------------------------------------

def causal_conv(g, w):
    """c[t] = sum_j w[:, j] g[t - (K - 1) + j]; g: (S, d), w: (d, K)."""
    taps = w.shape[1]
    s = g.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, g.shape[1]), g.dtype), g])
    return sum(padded[j:j + s] * w[:, j] for j in range(taps))


def conv_block(n, lp):
    b, c, u = jnp.split(matmul(n, lp["w_in"]), 3, axis=-1)
    return matmul(c * causal_conv(b * u, lp["conv"]), lp["w_out"])


# -- attention -------------------------------------------------------------------

def rope(t, theta: float):
    """t: (S, H, hd), positions 0 .. S-1; rotate-half over the whole head."""
    half = t.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = jnp.arange(t.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def attention(q, k, v, q_block: int = Q_BLOCK):
    """softmax(Q K^T / sqrt(hd)) V over the keys j <= t; q: (S, H, hd); k,
    v: (S, Hkv, hd)."""
    s, hq, hd = q.shape
    k, v = (jnp.repeat(t, hq // t.shape[1], axis=1) for t in (k, v))
    blk = min(q_block, s)
    if s % blk:
        raise ValueError(f"{s} positions are not a multiple of {blk}")
    k_at = jnp.arange(s)

    @jax.checkpoint
    def one_block(args):
        i, q_i = args                                   # (blk, H, hd)
        scores = matmul(q_i.transpose(1, 0, 2),         # (H, blk, S)
                        k.transpose(1, 2, 0)) / math.sqrt(hd)
        seen = k_at[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, -1),
                      v.transpose(1, 0, 2)).transpose(1, 0, 2)

    out = lax.map(one_block, (jnp.arange(s // blk),
                              q.reshape(s // blk, blk, hq, hd)))
    return out.reshape(s, hq, hd)


def head_norm(t, g, eps):
    """RMSNorm over each head's features; t: (S, heads, hd), g: (hd,)."""
    return rmsnorm(t, g, eps)


def attention_block(n, lp, *, n_kv_heads, head_dim, rope_theta, norm_eps):
    s = n.shape[0]
    q = matmul(n, lp["wq"]).reshape(s, -1, head_dim)
    k = matmul(n, lp["wk"]).reshape(s, n_kv_heads, head_dim)
    v = matmul(n, lp["wv"]).reshape(s, n_kv_heads, head_dim)
    q = rope(head_norm(q, lp["q_norm"], norm_eps), rope_theta)
    k = rope(head_norm(k, lp["k_norm"], norm_eps), rope_theta)
    return matmul(attention(q, k, v).reshape(s, -1), lp["wo"])


# -- the MLPs --------------------------------------------------------------------

def swiglu(n, w1, w3, w2):
    return matmul(jax.nn.silu(matmul(n, w1)) * matmul(n, w3), w2)


def route(n, router, top_k: int, renorm_eps: float, scale: float):
    """(T, E) weights: for the top_k experts by ``s + bias`` the score ``s``
    (without the bias) over the chosen's sum + ``renorm_eps``, times
    ``scale``; 0 for the others."""
    s = jax.nn.sigmoid(matmul(n, router[:-1]))
    choice = s + lax.stop_gradient(router[-1])
    kth = lax.top_k(choice, top_k)[0][:, -1:]
    w = jnp.where(choice >= kth, s, 0.0)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + renorm_eps) * scale


def experts(n, weights, w1, w3, w2):
    """sum over the experts held of weights[:, e] swiglu_e(n), one expert
    at a time (a loop, so that the program holds one expert's code and not
    eight's a layer)."""
    def add(y, expert):
        w_e, *matrices = expert
        return y + w_e[:, None] * jax.checkpoint(swiglu)(n, *matrices), None

    return lax.scan(add, jnp.zeros_like(n),
                    (weights.T[:w1.shape[0]], w1, w3, w2))[0]


def in_blocks(f, x, t_block: int = T_BLOCK):
    """``f`` on blocks of ``t_block`` rows of ``x`` (an array, or a tuple of
    arrays with the same rows) one after the other, the results stacked:
    for an ``f`` that acts on each token alone."""
    rows = jax.tree_util.tree_leaves(x)[0].shape[0]
    blk = min(t_block, rows)
    if rows % blk:
        raise ValueError(f"{rows} tokens are not a multiple of {blk}")
    out = lax.map(jax.checkpoint(f), jax.tree_util.tree_map(
        lambda a: a.reshape(-1, blk, *a.shape[1:]), x))
    return out.reshape(rows, *out.shape[2:])


def ffn_block(n, lp, *, top_k, renorm_eps, router_scale):
    if "router" not in lp:
        return in_blocks(
            lambda rows: swiglu(rows, lp["w1"], lp["w3"], lp["w2"]), n)
    return experts(n, route(n, lp["router"], top_k, renorm_eps, router_scale),
                   lp["w1"], lp["w3"], lp["w2"])


# -- the model -------------------------------------------------------------------

def sequence(params, tokens, labels, *, norm_eps, n_kv_heads, head_dim,
             rope_theta, top_k, renorm_eps, router_scale):
    """One sequence's sum of negative log-likelihoods; ``tokens`` and
    ``labels`` (S,)."""
    x = params["embed"][tokens]
    for lp in params["layers"]:
        @jax.checkpoint
        def op_half(x, op):
            n = rmsnorm(x, op["ln"], norm_eps)
            if "conv" in op:
                return x + conv_block(n, op)
            return x + attention_block(
                n, op, n_kv_heads=n_kv_heads, head_dim=head_dim,
                rope_theta=rope_theta, norm_eps=norm_eps)

        @jax.checkpoint
        def ffn_half(x, mp):
            return x + ffn_block(rmsnorm(x, mp["ln"], norm_eps), mp,
                                 top_k=top_k, renorm_eps=renorm_eps,
                                 router_scale=router_scale)

        x = ffn_half(op_half(x, lp["op"]), lp["ffn"])
    return -jnp.sum(in_blocks(
        lambda rows: log_likelihood(rows[0], rows[1], params, norm_eps),
        (x, labels)))


def log_likelihood(x, labels, params, norm_eps):
    """log softmax(logits)[label] of each row, fp32, over the vocabulary
    slice held: the tied embedding table is the head."""
    logits = matmul(rmsnorm(x, params["final_norm"], norm_eps),
                    params["embed"].T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]


def loss(params, tokens, labels, **architecture):
    """The mean next-token cross-entropy over the batch's B x S positions.
    ``architecture``: ``sequence``'s keyword arguments
    (``Family.reference_args``)."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)

    def one(tl):
        return sequence(params, *tl, **architecture)

    return jnp.sum(lax.map(one, (tokens, labels))) / labels.size
