"""Plain reference for SmallThinker-21BA3B-Instruct (``smallthinker``;
PowerInfer/SmallThinker-21BA3B-Instruct ``config.json``; the family's report,
arXiv:2507.20984): forward pass and training loss in fp32 ``jax.numpy``,
written from the layer equations.  No kernel, no sort, no grouped matmul, no
scan over layers, no ``shard_map``, nothing imported from ``horovod_tpu``.
Gradients are ``jax.grad`` of this loss.  The caller puts
``jax.default_matmul_precision("highest")`` around the whole jitted call.

A layer, x its input, RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g, no bias
anywhere:

  r   = x Wr                       (E,): the router's logits, from the
                                   layer's INPUT, ahead of attention, unnormed
  h   = RMSNorm(x; g1)
  q = h Wq (H heads of hd), k = h Wk, v = h Wv (Hkv heads); query head i
        reads key / value head i // (H / Hkv); softmax at 1/sqrt(hd)
  a ``sliding`` layer: q, k <- RoPE(q), RoPE(k): t = [t1, t2] split at the
        head's half, [t1 cos - t2 sin, t2 cos + t1 sin] at angle position x
        theta^(-2i/hd); query t sees the keys t - window < j <= t (window
        keys, its own included)
  a ``full`` layer: no rotation and no position information at all; query t
        sees the keys j <= t
  y   = x + concat(a) Wo
  m   = RMSNorm(y; g2)
  the top_k largest of r are chosen; w_e = softmax over the chosen's logits
        (= softmax over all E, the chosen's share renormalised), 0 elsewhere
  out = y + sum over the experts held of w_e (relu(m W1_e) * m W3_e) W2_e

  logits = RMSNorm(x_L; g_f) Wlm^T;  loss = mean over positions of
  -log softmax(logits)[label]

The experts held are the first ``w1.shape[0]`` of the router's outputs: what
the absent ones would have added is left out, as in the program.  Each held
expert is evaluated for every token and weighted by ``w_e`` where the token
chose it and by 0 where it did not: a mask, not a dispatch.  Nothing is
dropped.

Weight layout (a fact about the parameters): ``layers`` is a list, one dict
a published layer, ``{"attn": {ln, wq, wk, wv, wo}, "mlp": {ln, router, w1,
w3, w2 (leading axis: the experts held)}}``; projections are (in, out) with
the heads outermost in a fused (H * hd) dimension.  Which layers are
``sliding`` is the argument ``layer_types``.

Memory (not part of the equations): one sequence at a time, each layer's two
halves and each block of ``Q_BLOCK`` queries under ``jax.checkpoint``; a
block of queries is scored against every key and the mask is explicit, for
the window as for the causal order; the held experts one at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# |system - reference| allowed, and why.  The system computes matmuls and
# attention in bf16 with fp32 normalisation, rotations, gates, router,
# softmax statistics and head; the reference is fp32 throughout at the
# highest matmul precision.  Measured on the chip at the published widths,
# one sequence of 16,384 a check, on the family's seeded weights
# (``families/smallthinker.py`` ``init_params``: the embedding at unit RMS,
# the norm gains off 1), through the runner's own comparison (my chip runs,
# PR 46: 19 runs of the cell and ``tools/smallthinker_controls.py``, a seed
# each; ``chiprun_out/pr46/``; PERF.md section 6, PR 46 holds every reading).
#  - loss: a mean over 16,384 positions.  System minus reference 8.6e-6 ..
#    1.20e-4 in size over 25 seeds, either sign, 5.1e-5 in the root mean
#    square.  With every matmul operand of this file rounded to e4m3, the
#    nearest precision below the configuration's bf16, the reference's loss
#    falls by 3.4e-4 .. 6.5e-4 and the difference reads 2.84e-4, 4.9e-4 and
#    7.1e-4 on three seeds (e5m2: 2.9e-3).  The limit lies between the
#    largest sound reading and the smallest of those, 1.7 times the one
#    (3.9 times the root mean square) and 0.70 of the other: an untraced
#    run, which compares the loss alone, reads a step computed in either
#    8-bit type as not correct.  The two readings lie 2.4 times apart, so
#    neither side has the room of 2 a limit would like: a fresh seed's sound
#    reading is what must not be refused, and e4m3 also fails the gradients'
#    limit three times over.
#  - gradients: relative L2 error per leaf.  The worst leaf is the last
#    layer's ``w1`` on every seed, 7.68, 7.74, 7.79, 8.02, 8.14, 8.17, 8.23 %
#    over seven: a ReLU gate decides by a sign, and where a gate's
#    pre-activation lies closer to 0 than bf16's error in it, system and
#    reference open
#    different units; ``w1`` takes its gradient through that step function
#    (the routers, which decide by comparison too, read under it: the
#    unit-RMS operand gives logits of std ~1 and few near-ties).  The
#    controls on one seed (sound reading 7.68 %): SiLU for ReLU **41.7 %**
#    (the same ``w1``), e4m3 56.5 % (an attention ``wo``), the router on the
#    normed stream after the attention 67.6 % (an expert block's norm), a
#    rotated full layer 112 % (its ``wk``).  The limit stands between the
#    largest sound reading and the weakest of those, 2.2 times the one and
#    0.43 of the other, at their geometric middle (18.5 %): set from the
#    chip's two readings and from nothing else.  e5m2 reads 12.5 % (a
#    ``router``), under this limit, and is refused by the loss; e4m3 by
#    both.  (At the CPU tests' small presets a flip weighs more: 256 tokens
#    at hidden 64 read 5-25 % by the seed, so the rehearsal's seed is one
#    that reads 5.8 %, and this limit is not theirs to move.)
#  - NOT SEEN on the chip: **a window off by one key** (4,095 or 4,097 keys
#    of 4,096: the worst leaf reads 7.71 / 7.76 % where it reads 7.68, the
#    loss moves by 1e-6), as in Laguna's cell and for its reason: one key
#    in 4,096 is far under the gates' own noise.  What refuses it: the CPU
#    tests at compute type fp32 (system = this file to 1e-5 on every leaf;
#    a window of one key less or more fails them), and the kernels against
#    ``reference_attention`` with the same window.  Nor can operands rounded
#    to bf16 be asked for there: XLA removes an fp32 -> bf16 -> fp32 round
#    trip (``xla_allow_excess_precision``) and the control reads the sound
#    reading to the last digit; the 8-bit types are the precision below the
#    configuration's, and the CPU tests round to bf16 under fp32 compute.
#  (The runner prints a bound to one digit: 1.8e-1 reads "2e-01".)
TOLERANCES = {"loss_abs": 2e-4, "grad_rel_l2": 0.18}
Q_BLOCK = 512


def matmul(a, b):
    """Every matrix product of this file, so that a test can ask what a
    lower precision would give by rounding the operands here."""
    return a @ b


def rmsnorm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


# -- positions -------------------------------------------------------------------

def rope(t, theta: float):
    """t: (S, H, hd), positions 0 .. S-1; rotate-half over the whole head."""
    half = t.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = jnp.arange(t.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def positioned(q, k, sliding: bool, theta: float):
    """q and k as the layer's kind leaves them: rotated in a sliding layer,
    as they are in a full one."""
    return (rope(q, theta), rope(k, theta)) if sliding else (q, k)


# -- attention -------------------------------------------------------------------

def attention(q, k, v, window, q_block: int = Q_BLOCK):
    """softmax(Q K^T / sqrt(hd)) V over the keys a query sees; q: (S, H,
    hd); k, v: (S, Hkv, hd); ``window`` None for a full layer."""
    s, hq, hd = q.shape
    k, v = (jnp.repeat(t, hq // t.shape[1], axis=1) for t in (k, v))
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
        seen = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            seen = seen & (q_pos[:, None] - k_pos[None, :] < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, -1),
                      v.transpose(1, 0, 2)).transpose(1, 0, 2)

    out = lax.map(one_block, (jnp.arange(s // blk),
                              q.reshape(s // blk, blk, hq, hd)))
    return out.reshape(s, hq, hd)


def attention_block(h, lp, *, sliding: bool, n_kv_heads, head_dim, window,
                    rope_theta):
    s = h.shape[0]
    q = matmul(h, lp["wq"]).reshape(s, -1, head_dim)
    k = matmul(h, lp["wk"]).reshape(s, n_kv_heads, head_dim)
    v = matmul(h, lp["wv"]).reshape(s, n_kv_heads, head_dim)
    q, k = positioned(q, k, sliding, rope_theta)
    o = attention(q, k, v, window if sliding else None)
    return matmul(o.reshape(s, -1), lp["wo"])


# -- the experts -------------------------------------------------------------------

def gate_activation(u):
    return jnp.where(u > 0, u, 0.0)                     # ReLU


def reglu(m, w1, w3, w2):
    return matmul(gate_activation(matmul(m, w1)) * matmul(m, w3), w2)


def router_operand(x, y, mp, norm_eps):
    """What the router reads, of the layer's input ``x`` and the stream
    after attention ``y``: the layer's input as it is."""
    return x


def route(r, top_k: int):
    """(T, E) weights from the logits ``r``: the softmax over the top_k
    chosen's logits; 0 for the others."""
    p = jax.nn.softmax(r, axis=-1)
    kth = lax.top_k(p, top_k)[0][:, -1:]
    w = jnp.where(p >= kth, p, 0.0)
    return w / jnp.sum(w, axis=-1, keepdims=True)


def experts(m, weights, w1, w3, w2):
    """sum over the experts held of weights[:, e] reglu_e(m), one expert at
    a time (a loop, so that the program holds one expert's code and not
    sixteen's a layer)."""
    def add(y, expert):
        w_e, *matrices = expert
        return y + w_e[:, None] * jax.checkpoint(reglu)(m, *matrices), None

    return lax.scan(add, jnp.zeros_like(m),
                    (weights.T[:w1.shape[0]], w1, w3, w2))[0]


# -- the model -------------------------------------------------------------------

def sequence(params, tokens, labels, *, layer_types, norm_eps, n_kv_heads,
             head_dim, window, rope_theta, top_k):
    """One sequence's sum of the positions' negative log-likelihoods.
    ``layer_types``: "full" | "sliding" a layer."""
    x = params["embed"][tokens]
    if len(layer_types) != len(params["layers"]):
        raise ValueError(f"{len(params['layers'])} layers, "
                         f"{len(layer_types)} layer types")
    for kind, lp in zip(layer_types, params["layers"]):
        @jax.checkpoint
        def attn_half(x, ap, sliding=kind == "sliding"):
            return x + attention_block(
                rmsnorm(x, ap["ln"], norm_eps), ap, sliding=sliding,
                n_kv_heads=n_kv_heads, head_dim=head_dim, window=window,
                rope_theta=rope_theta)

        @jax.checkpoint
        def mlp_half(x, y, mp):
            r = matmul(router_operand(x, y, mp, norm_eps), mp["router"])
            return y + experts(rmsnorm(y, mp["ln"], norm_eps),
                               route(r, top_k), mp["w1"], mp["w3"], mp["w2"])

        x = mlp_half(x, attn_half(x, lp["attn"]), lp["mlp"])
    logits = matmul(rmsnorm(x, params["final_norm"], norm_eps),
                    params["lm_head"].T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], -1))


def loss(params, tokens, labels, **architecture):
    """Mean token cross-entropy over the batch.  ``architecture``:
    ``sequence``'s keyword arguments (``Family.reference_args``)."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    one = jax.checkpoint(lambda tl: sequence(params, *tl, **architecture))
    return jnp.sum(lax.map(one, (tokens, labels))) / tokens.size
