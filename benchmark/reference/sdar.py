"""Plain reference for SDAR-30B-A3B-Chat (``sdar_moe``; JetLM/SDAR-30B-A3B-Chat
``config.json``; SDAR, arXiv:2510.06303; the vectorised block-diffusion
training forward of BD3-LMs, Arriola et al. 2025, arXiv:2503.09573): forward
pass and training loss in fp32 ``jax.numpy``, written from the layer
equations.  No kernel, no sort, no grouped matmul, no scan over layers, no
``shard_map``, nothing imported from ``horovod_tpu``.  Gradients are
``jax.grad`` of this loss.  The caller puts
``jax.default_matmul_precision("highest")`` around the whole jitted call.

The step.  A sequence ``x0`` of L ids in blocks of ``block`` positions; the
batch brings ``tokens = [xt ; x0]`` (2L: the noised copy, where some ids are
the mask token, then the clean copy), ``labels = x0`` (L) and ``weights``
(L; ``masked / t`` of the position's block).  All 2L positions go through
the layers, at positions ``p mod L``; with ``beta(p) = (p mod L) // block``
and a position noised iff ``p < L``, query q sees key k iff

    q, k noised and beta(q) == beta(k),  or
    q noised, k clean and beta(k) < beta(q),  or
    q, k clean and beta(k) <= beta(q)

so the noised positions of block b are denoised together given the clean
blocks before b.  ``loss = (1 / (B L)) sum_i weights_i * -log
softmax(logits_i)[labels_i]`` over the L noised positions.

A layer is ``x <- x + Attn(RMSNorm(x; g1))`` then ``x <- x + MoE(RMSNorm(x;
g2))``, RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g:

  Attn  q = h Wq (H heads of hd), k = h Wk, v = h Wv (Hkv heads)
        q, k = RMSNorm over each head's hd features (one g of hd for q, one
        for k, shared by the heads), then RoPE: t = [t1, t2] at the head's
        half, [t1 cos - t2 sin, t2 cos + t1 sin] at angle (p mod L) x
        theta^(-2i/hd)
        query head i reads key / value head i // (H / Hkv); softmax at
        1/sqrt(hd) over the keys the rule above lets the query see
        out = concat_i(o_i) Wo
  MoE   p = softmax(h Wr) over all E router outputs; the top_k largest;
        w_e = p_e / sum_chosen p for the chosen, else 0;
        out = sum over the experts held of w_e (silu(h W1_e) * h W3_e) W2_e
  logits = RMSNorm(x_L[:L]; g_f) Wlm^T, log-softmax in fp32

The experts held are the first ``w1.shape[0]`` of the router's outputs: what
the absent ones would have added is left out, as in the program.  Each held
expert is evaluated for every position and weighted by ``w_e`` where the
position chose it and by 0 where it did not: a mask, not a dispatch.
Nothing is dropped.

Weight layout (a fact about the parameters): ``layers`` is a list, one dict
a published layer, ``{"attn": {ln, wq, wk, wv, q_norm, k_norm, wo}, "mlp":
{ln, router, w1, w3, w2 (leading axis: the experts held)}}``; projections
are (in, out) with the heads outermost in a fused (H * hd) dimension.

Memory (not part of the equations): one sequence at a time, each layer's two
halves and each block of ``Q_BLOCK`` queries under ``jax.checkpoint``; a
block of queries is scored against every key and the mask is explicit.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# |system - reference| allowed, and why.  The system computes matmuls and
# attention in bf16 with fp32 normalisation, rotations, router, softmax
# statistics and head; the reference is fp32 throughout at the highest
# matmul precision.  Measured on the chip at the published widths, one
# sequence of 4096 tokens (8192 positions) a check, on the family's seeded
# weights (``families/sdar.py`` ``init_params``: the embedding at unit RMS,
# the mask token's eight experts decided by a margin, one of them held),
# through the runner's own comparison (my chip run, PR 39,
# ``tools/sdar_controls.py``, ``chiprun_out/pr39b/controls.log``; PERF.md
# section 6, PR 39 holds every reading).
#  - loss: a weighted sum over the ~2048 masked positions of 4096; with
#    seeded weights every term is about ln 18992 = 9.85 x the position's 1 / t
#    and the loss reads 9.8-10.8.  System minus reference 1.3e-5 .. 3.1e-4 in
#    size over eight seeds (4.6e-4 the largest of seven more on the first
#    draft's weights); the limit is the accepted expert cells' (Nemotron's,
#    Laguna's), 4.8 times the largest.  With every matmul operand of this
#    file rounded to e5m2 the difference is 3.4e-3, 2.3 times the limit
#    (e4m3: 1.1e-3, inside it: that control is the gradients'); weights
#    ignored 0.52.
#  - gradients: relative L2 error per leaf.  Every leaf but a layer's
#    ``router`` reads under 2 %.  A ``router`` reads 2.1, 2.8, 3.9, 5.6, 6.5,
#    8.6, 9.3, 11.2 % over the eight seeds, another layer's each time: its
#    gradient comes from the masked positions alone (they carry the whole
#    loss), which all bring the same hidden state, so it is that one vector
#    times a sum over ~2048 positions of terms whose signs cancel (to a
#    twentieth of a random walk's size in some layers: CPU, PR 39), and
#    bf16's error in each term does not cancel with them.  The controls on
#    one seed (sound reading 5.6 %): a noised block's other noised keys
#    dropped **17.9 %** (an attention ``wv``), the clean copy of a block
#    visible to its own noised block 24.2 % (``wv``), QK-norm over all
#    features 25.6 % (``wk``), e4m3 145 %, positions not wrapped 150 %.  The
#    limit stands between the largest sound reading and the weakest control,
#    1.43 times the one and 0.89 of the other, the more room above the sound
#    readings because fresh seeds read higher (eight more on the CPU at
#    L = 1024: 2.6 .. 10.9 % and one 15.9 %): there is no more room to give,
#    and PERF.md section 7 says what would make some.
#  - NOT SEEN: log-probabilities carried in bf16.  Each is off by up to half
#    a spacing (0.031 near ln 18992) with no bias and the loss is a weighted
#    mean of them, so the loss moves by a draw whose size follows the
#    batch's largest weights (2.0e-3 on the seed above, refused; 7e-4 in
#    the mean, inside the limit): a limit that refused it on every seed
#    would refuse sound ones.
#  (The runner prints a bound to one digit.)
TOLERANCES = {"loss_abs": 1.5e-3, "grad_rel_l2": 0.16}
Q_BLOCK = 1024


def matmul(a, b):
    """Every matrix product of this file, so that a test can ask what a
    lower precision would give by rounding the operands here."""
    return a @ b


def rmsnorm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


# -- the mask and the positions ------------------------------------------------

def positions(doubled: int):
    """The position of each of the ``doubled`` = 2L places of the stack:
    0 .. L-1 for the noised copy, 0 .. L-1 again for the clean one."""
    return jnp.arange(doubled) % (doubled // 2)


def visible(q_at, k_at, length: int, block: int):
    """(len(q_at), len(k_at)) bool: whether the query at place q of the 2L
    sees the key at place k, by the rule of the docstring."""
    q_noised, k_noised = (q_at < length)[:, None], (k_at < length)[None, :]
    q_beta = ((q_at % length) // block)[:, None]
    k_beta = ((k_at % length) // block)[None, :]
    return ((q_noised & k_noised & (q_beta == k_beta))
            | (q_noised & ~k_noised & (k_beta < q_beta))
            | (~q_noised & ~k_noised & (k_beta <= q_beta)))


def rope(t, theta: float):
    """t: (2L, H, hd); rotate-half over the whole head at ``positions``."""
    half = t.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = positions(t.shape[0]).astype(jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


# -- attention -------------------------------------------------------------------

def attention(q, k, v, block: int, q_block: int = Q_BLOCK):
    """softmax(Q K^T / sqrt(hd)) V over the keys a query sees; q: (2L, H,
    hd); k, v: (2L, Hkv, hd)."""
    s, hq, hd = q.shape
    k, v = (jnp.repeat(t, hq // t.shape[1], axis=1) for t in (k, v))
    blk = min(q_block, s)
    if s % blk:
        raise ValueError(f"{s} places are not a multiple of {blk}")
    k_at = jnp.arange(s)

    @jax.checkpoint
    def one_block(args):
        i, q_i = args                                   # (blk, H, hd)
        scores = matmul(q_i.transpose(1, 0, 2),         # (H, blk, 2L)
                        k.transpose(1, 2, 0)) / math.sqrt(hd)
        seen = visible(i * blk + jnp.arange(blk), k_at, s // 2, block)
        scores = jnp.where(seen, scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, -1),
                      v.transpose(1, 0, 2)).transpose(1, 0, 2)

    out = lax.map(one_block, (jnp.arange(s // blk),
                              q.reshape(s // blk, blk, hq, hd)))
    return out.reshape(s, hq, hd)


def head_norm(t, g, eps):
    """RMSNorm over each head's features; t: (2L, heads, hd), g: (hd,)."""
    return rmsnorm(t, g, eps)


def attention_block(h, lp, *, n_kv_heads, head_dim, rope_theta, block,
                    norm_eps):
    s = h.shape[0]
    q = matmul(h, lp["wq"]).reshape(s, -1, head_dim)
    k = matmul(h, lp["wk"]).reshape(s, n_kv_heads, head_dim)
    v = matmul(h, lp["wv"]).reshape(s, n_kv_heads, head_dim)
    q = rope(head_norm(q, lp["q_norm"], norm_eps), rope_theta)
    k = rope(head_norm(k, lp["k_norm"], norm_eps), rope_theta)
    return matmul(attention(q, k, v, block).reshape(s, -1), lp["wo"])


# -- the experts -------------------------------------------------------------------

def swiglu(h, w1, w3, w2):
    return matmul(jax.nn.silu(matmul(h, w1)) * matmul(h, w3), w2)


def route(h, router, top_k: int):
    """(T, E) weights: for the top_k experts by probability p over the
    chosen's sum; 0 for the others."""
    p = jax.nn.softmax(matmul(h, router), axis=-1)
    kth = lax.top_k(p, top_k)[0][:, -1:]
    w = jnp.where(p >= kth, p, 0.0)
    return w / jnp.sum(w, axis=-1, keepdims=True)


def experts(h, weights, w1, w3, w2):
    """sum over the experts held of weights[:, e] swiglu_e(h), one expert
    at a time (a loop, so that the program holds one expert's code and not
    sixteen's a layer)."""
    def add(y, expert):
        w_e, *matrices = expert
        return y + w_e[:, None] * jax.checkpoint(swiglu)(h, *matrices), None

    return lax.scan(add, jnp.zeros_like(h),
                    (weights.T[:w1.shape[0]], w1, w3, w2))[0]


def moe_block(h, lp, *, top_k):
    return experts(h, route(h, lp["router"], top_k), lp["w1"], lp["w3"],
                   lp["w2"])


# -- the model -------------------------------------------------------------------

def head(x, params, norm_eps):
    """fp32 log-probabilities of the noised copy over the vocabulary slice
    held."""
    logits = matmul(rmsnorm(x, params["final_norm"], norm_eps),
                    params["lm_head"].T)
    return jax.nn.log_softmax(logits, axis=-1)


def sequence(params, tokens, labels, weights, *, norm_eps, n_kv_heads,
             head_dim, rope_theta, top_k, block):
    """One sequence's weighted sum of the noised positions' negative
    log-likelihoods; ``tokens`` (2L,), ``labels`` and ``weights`` (L,)."""
    length = labels.shape[0]
    x = params["embed"][tokens]
    for lp in params["layers"]:
        @jax.checkpoint
        def attn_half(x, ap):
            return x + attention_block(
                rmsnorm(x, ap["ln"], norm_eps), ap, n_kv_heads=n_kv_heads,
                head_dim=head_dim, rope_theta=rope_theta, block=block,
                norm_eps=norm_eps)

        @jax.checkpoint
        def mlp_half(x, mp):
            return x + moe_block(rmsnorm(x, mp["ln"], norm_eps), mp,
                                 top_k=top_k)

        x = mlp_half(attn_half(x, lp["attn"]), lp["mlp"])
    logp = head(x[:length], params, norm_eps)
    return -jnp.sum(weights * jnp.take_along_axis(
        logp, labels[:, None], -1)[:, 0])


def loss(params, tokens, labels, weights, **architecture):
    """The weighted cross-entropy over the batch's B x L data tokens.
    ``architecture``: ``sequence``'s keyword arguments
    (``Family.reference_args``)."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)

    def one(tlw):
        return sequence(params, *tlw, **architecture)

    return jnp.sum(lax.map(one, (tokens, labels, weights))) / labels.size
