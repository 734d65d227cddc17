"""Plain reference for the BERT encoder in masked-LM pretraining with the
gathered head: forward pass and loss in fp32 ``jax.numpy`` from the layer
equations.  No kernel, no ``shard_map``, nothing imported from
``horovod_tpu``; attention and the two small helpers are the flagship
reference's.  The caller puts ``jax.default_matmul_precision("highest")``
around the whole jitted call.

The widths are BERT-Base's as published (Devlin et al. 2018).  The block is
the repository's encoder, and departs from the published one; each
departure is the system's, the reference follows it so that the two compute
one function, and PERF.md lists them as open:

    x_0   = LN(E[tokens] + P[0:S]; g_e)      no segment embeddings
    a_l   = x_l + Attn(LN(x_l; g1_l)) Wo_l   pre-norm (published: post-norm),
                                             bidirectional, no biases
    x_l+1 = a_l + GELU_tanh(LN(a_l; g2_l) W1_l) W2_l
    h     = LN(GELU_tanh(gather(x_L, positions) Wt); g_t)   MLM transform
    logits = h E^T + b                                      tied projection
    loss  = sum of -log softmax(logits)[label] over predicted positions
            with label != -100, over their number

    LN(x; g) = (x - mean) / sqrt(var + 1e-6) * g     scale only, no bias
    GELU_tanh is the tanh form, as in the original BERT code.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import loader

_flagship = loader.load_code("reference", "flagship")
attention, gelu_tanh, split_heads = (
    _flagship.attention, _flagship.gelu_tanh, _flagship.split_heads)

# See reference/flagship.py for the reasoning and the measurements; the same
# arithmetic types meet here, so the bounds are the same.
TOLERANCES = _flagship.TOLERANCES
IGNORE_INDEX = -100


def layernorm(x, g):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + 1e-6) * g


def loss(params, tokens, positions, labels, *, n_heads: int):
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    s = tokens.shape[1]
    x = layernorm(params["embed"][tokens] + params["pos"][None, :s],
                  params["emb_norm"])

    @jax.checkpoint
    def layer(x, lp):
        q, k, v = split_heads(layernorm(x, lp["ln1"]) @ lp["wqkv"], n_heads)
        o = attention(q, k, v, causal=False)
        x = x + o.reshape(x.shape[0], s, -1) @ lp["wo"]
        x = x + gelu_tanh(layernorm(x, lp["ln2"]) @ lp["w1"]) @ lp["w2"]
        return x, None

    x, _ = lax.scan(layer, x, params["layers"])
    picked = jnp.take_along_axis(x, positions[..., None], axis=1)
    h = layernorm(gelu_tanh(picked @ params["mlm_dense"]),
                  params["mlm_norm"])
    logits = h @ params["embed"].T + params["mlm_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                             -1)[..., 0]
    mask = (labels != IGNORE_INDEX).astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
