"""Plain reference for Keye-VL-2.0-30B-A3B's language model (``KeyeVL2``;
Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``; the indexer and its training
are DeepSeek-V3.2-Exp's sparse attention, the three-stream rotation is
Qwen2-VL's M-RoPE): forward pass and training loss in fp32 ``jax.numpy``,
written from the layer equations.  No kernel, no grouped matmul, no scan over
layers, no ``shard_map``, nothing imported from ``horovod_tpu``; the choice
of keys is ``lax.top_k`` over a dense row of scores.  Gradients are
``jax.grad`` of this loss.  The caller puts
``jax.default_matmul_precision("highest")`` around the whole jitted call.

48 identical pre-norm layers (4 here), hidden d = 2048, RMSNorm(x; g) = x /
sqrt(mean(x^2) + 1e-6) * g, no biases:  h = x + Attn(RMSNorm(x)),
y = h + MoE(RMSNorm(h)).

1. Positions.  A position t has three integers (p0, p1, p2: temporal,
   height, width), the batch's third array (3, S).  Rotary frequency i of a
   head's 64 (inv_freq_i = theta^(-2i/128), theta = 1e7, rotate-half) turns
   by p^{c(i)} * inv_freq_i with c(i) = 0 for i < 16, 1 for 16 <= i < 40, 2
   for 40 <= i < 64 (``mrope_section`` [16, 24, 24], Qwen2-VL's sectioned
   layout).  Text positions have p0 = p1 = p2; an image span of an h x w
   grid after position value m has p0 = m + 1 for all its h w positions,
   p1 = m + 1 + row, p2 = m + 1 + column, and the text after it starts at
   m + 1 + max(h, w) (Qwen2-VL's ``get_rope_index``).  Causality and the
   selection go by index in the sequence, not by these values.
2. Main attention.  n = RMSNorm(x).  q = n Wq (32 heads of 128), k = n Wk,
   v = n Wv (4 heads of 128); RMSNorm over each head of q and of k (one g of
   128 each, shared by the heads); the rotation of 1. on q and k; scale
   128^(-1/2); query head i reads key / value head i // 8.
3. Indexer (``sa_config``: 16 heads of 64, one key head, ``topk`` 2048).
   With n^ = stop_gradient(n):  qI[t, j] = n^_t WIq_j (d x 16*64),
   kI[s] = LayerNorm(n^_s WIk) (d x 64; scale and bias, eps 1e-6), then the
   rotation of 1. on qI and kI with the same three streams: the whole head
   of 64, 32 frequencies theta^(-2i/64), sections [8, 12, 12];
   w[t, j] = (n^_t WIw)_j * 16^(-1/2) * 64^(-1/2) (d x 16);
   I[t, s] = sum_j w[t, j] * ReLU(qI[t, j] . kI[s]) for s <= t.
   S_t = the 2,048 indices s <= t of largest I[t, s] (all of them while
   t < 2,048; ties to the larger s).
4. Attention over the chosen keys.  P[h, t, s] = softmax_{s in S_t}(q_{h,t}
   . k_{g(h),s} * 128^(-1/2)), o_{h,t} = sum_{s in S_t} P[h, t, s]
   v_{g(h),s}, Attn = concat_h(o) Wo.  No gradient reaches the indexer
   through S_t (the choice is discrete).
5. The indexer's loss.  pbar[t, s] = (1/32) sum_h stop_gradient(P[h, t,
   s]), s in S_t (sums to 1);  L_I = sum_layers mean_{b,t} KL(pbar[t, .] ||
   softmax_{s in S_t} I[t, s]).  The step minimises L = L_LM + 1.0 * L_I.
   So the indexer's five arrays (WIq, WIk, WIw, the LayerNorm's scale and
   bias) get L_I's gradient and nothing else; every other leaf gets L_LM's
   and nothing else.
6. MoE.  p = softmax(h Wr) over all 128 router outputs; the top 8; w_e =
   p_e / sum_chosen p; out = sum over the experts held of w_e (silu(h W1_e)
   * h W3_e) W2_e at 768; no shared expert, no auxiliary loss.  Untied head:
   logits = RMSNorm(x_L; g_f) Wlm^T over the vocabulary slice held,
   log-softmax in fp32, L_LM = mean over every position of -log
   softmax(logits)[label].

The experts held are the first ``w1.shape[0]`` of the router's outputs: what
the absent ones would have added is left out, as in the program.  Each held
expert is evaluated for every position and weighted by ``w_e`` where the
position chose it and by 0 where it did not: a mask, not a dispatch.
Nothing is dropped.

Weight layout (a fact about the parameters): ``layers`` is a list, one dict
a published layer, ``{"attn": {ln, wq, wk, wv, q_norm, k_norm, wo, index_wq,
index_wk, index_ww, index_k_norm, index_k_bias}, "mlp": {ln, router, w1, w3,
w2 (leading axis: the experts held)}}``; projections are (in, out) with the
heads outermost in a fused (H * hd) dimension.

Memory (not part of the equations): one sequence at a time, each layer's two
halves and each block of ``Q_BLOCK`` queries under ``jax.checkpoint``; a
block of queries is scored against every key, by the indexer and by every
head, and both masks are explicit.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# |system - reference| allowed, and why.  The system computes matmuls, the
# indexer's dots and attention in bf16 with fp32 normalisation, rotations,
# router, indexer weights and sums, softmax statistics and head; the
# reference is fp32 throughout at the highest matmul precision.  Measured on
# the chip at the published widths, one sequence of 16,384 a check, on the
# family's seeded weights (``families/keye_vl.py`` ``init_params``: the
# embedding at unit RMS, the norm gains off 1), through the runner's own
# comparison (my chip runs, PR 49: ``benchmark/run.py`` and
# ``tools/keye_vl_controls.py``, a seed each, ``chiprun_out/pr49/``; PERF.md
# section 6, PR 49 holds every reading).
#  - loss: L_LM, a mean over 16,384 positions, + L_I, four layers' mean KL
#    (~0.1 a layer: the loss reads 10.63-10.66 where ln 18992 is 9.85).
#    System minus reference 5.7e-6 .. 7.25e-5 in size over seven seeds.  With
#    every matmul operand of this file rounded to e4m3, the nearest precision
#    below the configuration's bf16, the difference reads 4.73e-4 and 1.21e-3
#    on two seeds; with the three position streams read as the index alone
#    2.40e-4 and 7.13e-4; with the choice by block of 512, no selection at
#    all, or L_I dropped 0.37-0.40.  The limit is the accepted SmallThinker
#    cell's: 2.8 times the largest sound reading and 0.42 of the weakest e4m3
#    one, so an untraced run, which compares the loss alone, reads a step
#    computed in 8 bits as not correct.
#  - gradients: relative L2 error per leaf.  The worst leaf reads 7.50, 7.78,
#    8.06, 8.16, 8.45, 8.58, 9.09 % over seven seeds, a ``router``, a
#    per-head ``q_norm`` or a ``wq`` of the last two layers: decisions by
#    comparison (8 experts of 128, 2,048 keys of up to 16,384) fall
#    differently where two scores lie within bf16's rounding of each other
#    (0.34 % of a layer's chosen pairs differ from the set the same scores
#    give in fp32), and those leaves take their gradient through the
#    flipped terms.  The controls on one seed (sound reading 8.58 %): the
#    main attention's two products in e4m3 **42.0 %** (a ``wv``), every
#    product in e4m3 43.3 %, positions as the index 97.1 % (a ``k_norm``),
#    the indexer's input not detached 99.95 % (an attention block's norm),
#    pbar not detached 100.3 %.  The limit stands between the largest sound
#    reading and the weakest of those, 1.98 times the one and 0.43 of the
#    other, near their geometric middle (19.5 %), the accepted SmallThinker
#    cell's number.
#  - NOT SEEN on the chip, and named in PERF.md section 7: **the choice made
#    on scores quantised to 8 bits a row** (loss 8.9e-5, worst leaf 11.2 %)
#    and **the three position sections rotated to [24, 24, 16]** (2.2e-5,
#    11.7 %), both between the sound readings and the limit: a limit that
#    refused them (0.10) would stand 1.1 times over a sound seed's 9.09 %.
#    And the two fine ones, as expected: ``topk`` 2,047 (6.1e-5, 8.54 %) and
#    ties to the earlier key (to the last digit: a layer has 1-8 rows with a
#    tie at the threshold).  What refuses all four: the CPU tests at compute
#    type fp32 (``tests/benchmark_tests/test_benchmark_keye_vl.py``: system =
#    this file to 2e-5 on every leaf, every control far outside), and
#    ``tests/test_keye_vl_layers.py`` (the choice against a sort, ties and
#    all; the rotation against a loop over elements).
#  (The runner prints a bound to one digit: 1.8e-1 reads "2e-01".)
TOLERANCES = {"loss_abs": 2e-4, "grad_rel_l2": 0.18}
Q_BLOCK = 256


def matmul(a, b):
    """Every matrix product of this file but the main attention's, so that
    a test can ask what a lower precision would give by rounding the
    operands here."""
    return a @ b


def attention_matmul(a, b):
    """The main attention's two products, q k^T and P v."""
    return a @ b


def rmsnorm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def layernorm(x, g, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g + b


# -- positions -------------------------------------------------------------------

def position_streams(positions):
    """The (3, S) streams the rotation reads: the batch's."""
    return positions


def stream_sections(sections):
    """The frequencies each stream takes, in order of the streams."""
    return tuple(sections)


def rope(t, positions, theta: float, sections):
    """t: (S, heads, hd); rotate-half over the whole head, frequency i at
    angle positions[c(i)] * theta^(-2i/hd), c(i) the section of
    ``sections`` (scaled to this head's half) that i falls in."""
    half = t.shape[-1] // 2
    sections = stream_sections(sections)
    scaled = [n * half // sum(sections) for n in sections]
    stream = np.repeat(np.arange(len(scaled)), scaled)             # (half,)
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    at = position_streams(positions).astype(jnp.float32)[stream]   # (half, S)
    angle = at.T * jnp.asarray(freqs, jnp.float32)[None, :]        # (S, half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


# -- the indexer -----------------------------------------------------------------

def indexer_input(n):
    """What the indexer reads of the block's normed input: its value."""
    return lax.stop_gradient(n)


def index_scores(qi, w, ki):
    """I[t, s] for a block of queries against every key, causality apart:
    qi (T, J, Di), w (T, J), ki (S, Di) -> (T, S)."""
    dots = matmul(qi.transpose(1, 0, 2), ki.T)                     # (J, T, S)
    return jnp.sum(w.T[:, :, None] * jnp.where(dots > 0, dots, 0.0), axis=0)


def choose(scores, seen, topk: int):
    """(T, S) bool: the ``topk`` keys of largest score among those a query
    has ``seen`` (all of them where they are no more), ties to the larger
    index: ``lax.top_k`` keeps the first of equals, so it reads the row
    from its end."""
    s = scores.shape[-1]
    ranked = jnp.where(seen, scores, -jnp.inf)[:, ::-1]
    _, at = lax.top_k(ranked, min(topk, s))
    rows = jnp.arange(scores.shape[0])[:, None]
    chosen = jnp.zeros(scores.shape, bool).at[rows, s - 1 - at].set(True)
    return chosen & seen


def heads_mean(p):
    """pbar from the heads' probabilities (H, T, S): their mean, a
    constant."""
    return lax.stop_gradient(jnp.mean(p, axis=0))


def index_loss_of(kl):
    """A layer's term of L_I from its queries' divergences."""
    return kl


# -- attention -------------------------------------------------------------------

def selected_attention(q, k, v, qi, w, ki, topk: int, q_block: int = Q_BLOCK):
    """(the heads' outputs (S, H, hd), sum over the queries of KL(pbar ||
    softmax over the chosen keys of I)).  q: (S, H, hd); k, v: (S, Hkv,
    hd); qi (S, J, Di), w (S, J), ki (S, Di)."""
    s, hq, hd = q.shape
    k, v = (jnp.repeat(t, hq // t.shape[1], axis=1) for t in (k, v))
    blk = min(q_block, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not a multiple of {blk}")
    k_at = jnp.arange(s)

    @jax.checkpoint
    def one_block(args):
        i, q_i, qi_i, w_i = args
        seen = (i * blk + jnp.arange(blk))[:, None] >= k_at[None, :]
        index = index_scores(qi_i, w_i, ki)                        # (blk, S)
        chosen = choose(lax.stop_gradient(index), seen, topk)
        scores = attention_matmul(q_i.transpose(1, 0, 2),          # (H, blk, S)
                                  k.transpose(1, 2, 0)) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(chosen, scores, -jnp.inf), -1)
        out = attention_matmul(p, v.transpose(1, 0, 2)).transpose(1, 0, 2)
        pbar = heads_mean(p)
        logq = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), -1)
        # 0 log 0 = 0, and no logarithm of a probability that is not there.
        logp = jnp.log(jnp.where(pbar > 0, pbar, 1.0))
        kl = jnp.sum(pbar * (logp - jnp.where(chosen, logq, 0.0)))
        return out, kl

    n = s // blk
    out, kl = lax.map(one_block, (
        jnp.arange(n), q.reshape(n, blk, hq, hd),
        qi.reshape(n, blk, *qi.shape[1:]), w.reshape(n, blk, -1)))
    return out.reshape(s, hq, hd), jnp.sum(kl)


def attention_block(n, lp, positions, *, n_kv_heads, head_dim, rope_theta,
                    sections, index_heads, topk, norm_eps):
    """(Attn(n), the block's sum over queries of the indexer's KL)."""
    s = n.shape[0]
    q = matmul(n, lp["wq"]).reshape(s, -1, head_dim)
    k = matmul(n, lp["wk"]).reshape(s, n_kv_heads, head_dim)
    v = matmul(n, lp["wv"]).reshape(s, n_kv_heads, head_dim)
    q = rope(rmsnorm(q, lp["q_norm"], norm_eps), positions, rope_theta,
             sections)
    k = rope(rmsnorm(k, lp["k_norm"], norm_eps), positions, rope_theta,
             sections)
    held = indexer_input(n)
    qi = matmul(held, lp["index_wq"]).reshape(s, index_heads, -1)
    ki = layernorm(matmul(held, lp["index_wk"]), lp["index_k_norm"],
                   lp["index_k_bias"], norm_eps)
    qi = rope(qi, positions, rope_theta, sections)
    ki = rope(ki[:, None], positions, rope_theta, sections)[:, 0]
    w = matmul(held, lp["index_ww"]) / math.sqrt(index_heads * qi.shape[-1])
    o, kl = selected_attention(q, k, v, qi, w, ki, topk)
    return matmul(o.reshape(s, -1), lp["wo"]), kl


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


# -- the model -------------------------------------------------------------------

def sequence(params, tokens, labels, positions, *, norm_eps, n_kv_heads,
             head_dim, rope_theta, sections, index_heads, topk, top_k):
    """(One sequence's sum of the positions' negative log-likelihoods, its
    sum over layers and queries of the indexer's KL)."""
    x = params["embed"][tokens]
    index_kl = 0.0
    for lp in params["layers"]:
        @jax.checkpoint
        def attn_half(x, ap):
            y, kl = attention_block(
                rmsnorm(x, ap["ln"], norm_eps), ap, positions,
                n_kv_heads=n_kv_heads, head_dim=head_dim,
                rope_theta=rope_theta, sections=sections,
                index_heads=index_heads, topk=topk, norm_eps=norm_eps)
            return x + y, kl

        @jax.checkpoint
        def mlp_half(x, mp):
            h = rmsnorm(x, mp["ln"], norm_eps)
            return x + experts(h, route(h, mp["router"], top_k), mp["w1"],
                               mp["w3"], mp["w2"])

        x, kl = attn_half(x, lp["attn"])
        x = mlp_half(x, lp["mlp"])
        index_kl = index_kl + index_loss_of(kl)
    logits = matmul(rmsnorm(x, params["final_norm"], norm_eps),
                    params["lm_head"].T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return (-jnp.sum(jnp.take_along_axis(logp, labels[:, None], -1)),
            index_kl)


def loss(params, tokens, labels, positions, *, index_loss_coef,
         **architecture):
    """L_LM + index_loss_coef * L_I, both means over the batch's B x S
    positions.  ``architecture``: ``sequence``'s keyword arguments
    (``Family.reference_args``)."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    one = jax.checkpoint(lambda tlp: sequence(params, *tlp, **architecture))
    nll, index_kl = lax.map(one, (tokens, labels, positions))
    return (jnp.sum(nll) + index_loss_coef * jnp.sum(index_kl)) / tokens.size
