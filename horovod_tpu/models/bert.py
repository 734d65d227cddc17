"""BERT encoder family — masked-LM pretraining, dp + tensor parallel.

The reference's second headline benchmark workload is BERT (BASELINE.md
north star: images|sequences/sec/chip for ResNet-50 and BERT; the reference
itself is model-agnostic middleware and ships BERT only as an external
benchmark recipe).  This is a TPU-first encoder: bfloat16 compute, fp32
normalization/softmax/loss, `lax.scan` over the layer stack (single XLA
compilation per stage), Megatron column/row tensor parallelism over the
``mp`` mesh axis, batch sharding over ``dp`` with gradient reductions
inserted by AD, and the fused flash-attention kernel (non-causal) for long
sequences.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel import ring_attention as ra
from ..parallel import tensor_parallel as tp
from ..utils.profiler import LAYERS_SCOPE, scope

IGNORE_INDEX = -100


class BertConfig(NamedTuple):
    vocab_size: int = 30522
    d_model: int = 768
    n_heads: int = 12
    d_ff: int = 3072
    n_layers: int = 12
    seq_len: int = 512
    dtype: Any = jnp.bfloat16
    # True/"full" = per-layer rematerialization (the flash forward's output
    # and lse kept); "dots" = save matmul outputs only (jax
    # dots_with_no_batch_dims_saveable); False = none.
    remat: Any = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def init_params(key, cfg: BertConfig) -> Dict[str, Any]:
    d, ff, v, s = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.seq_len
    h, hd = cfg.n_heads, cfg.head_dim
    n = cfg.n_layers
    ks = iter(jax.random.split(key, 12))
    std = 0.02

    def rand(kk, *shape, scale=std):
        return (jax.random.normal(kk, shape) * scale).astype(jnp.float32)

    return {
        "embed": rand(next(ks), v, d),
        "pos": rand(next(ks), s, d),
        "emb_norm": jnp.ones((d,), jnp.float32),
        "layers": {
            "ln1": jnp.ones((n, d), jnp.float32),
            "ln2": jnp.ones((n, d), jnp.float32),
            "wqkv": rand(next(ks), n, d, 3 * h * hd),
            "wo": rand(next(ks), n, h * hd, d,
                       scale=std / math.sqrt(2 * n)),
            "w1": rand(next(ks), n, d, ff),
            "w2": rand(next(ks), n, ff, d, scale=std / math.sqrt(2 * n)),
        },
        # MLM head: transform + norm; logits tie the embedding matrix.
        "mlm_dense": rand(next(ks), d, d),
        "mlm_norm": jnp.ones((d,), jnp.float32),
        "mlm_bias": jnp.zeros((cfg.vocab_size,), jnp.float32),
    }


def param_specs(cfg: BertConfig) -> Dict[str, Any]:
    """PartitionSpecs over mesh axes (dp, mp): attention + MLP Megatron
    column/row parallel over mp; embeddings/norms replicated."""
    return {
        "embed": P(),
        "pos": P(),
        "emb_norm": P(),
        "layers": {
            "ln1": P(),
            "ln2": P(),
            "wqkv": P(None, None, "mp"),
            "wo": P(None, "mp", None),
            "w1": P(None, None, "mp"),
            "w2": P(None, "mp", None),
        },
        "mlm_dense": P(),
        "mlm_norm": P(),
        "mlm_bias": P(),
    }


def _layernorm(x, scale):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + 1e-6) * scale).astype(x.dtype)


def _encoder_layer(cfg: BertConfig, lp, x, *, sharded: bool):
    """Pre-LN block at BERT's widths (each half normalises its input and
    adds its output to the residual stream; the published BERT is post-LN).
    x: (B, S, d). With ``sharded``, wqkv/wo/w1/w2 are mp-shards and
    activations cross tp.column/row_parallel."""
    hd = cfg.head_dim
    with scope("attn"):
        h = _layernorm(x, lp["ln1"])
        # The stored wqkv's columns are head-major, q, k, v inside a head
        # (an mp shard is whole heads): three products with its slabs.
        b, s = h.shape[:2]
        q, k, v = (
            tp.column_parallel(h, w).reshape(b, s, -1, hd)
            for w in tp.qkv_slabs(lp["wqkv"].astype(x.dtype), hd))
        o = ra.full_attention(q, k, v, causal=False)
        o = o.reshape(b, s, -1)
        if sharded:
            attn = tp.row_parallel(o, lp["wo"].astype(x.dtype), "mp",
                                   scatter_sequence=False)
        else:
            attn = jnp.einsum("bse,ed->bsd", o, lp["wo"].astype(x.dtype))
        x = x + attn

    with scope("mlp"):
        h = _layernorm(x, lp["ln2"])
        if sharded:
            u = jax.nn.gelu(tp.column_parallel(h, lp["w1"].astype(x.dtype)))
            mlp = tp.row_parallel(u, lp["w2"].astype(x.dtype), "mp",
                                  scatter_sequence=False)
        else:
            u = jax.nn.gelu(jnp.einsum("bsd,df->bsf", h,
                                       lp["w1"].astype(x.dtype)))
            mlp = jnp.einsum("bsf,fd->bsd", u, lp["w2"].astype(x.dtype))
        return x + mlp


def _encode(cfg: BertConfig, params, tokens, *, sharded: bool):
    with scope("embed"):
        emb = params["embed"][tokens] + params["pos"][None]
        x = _layernorm(emb.astype(cfg.dtype), params["emb_norm"])

    def body(act, lp):
        return _encoder_layer(cfg, lp, act, sharded=sharded), None

    # remat True/"full": recompute everything in bwd but the flash forward
    # kernel, whose output and lse are kept (lowest memory but for that one
    # activation a layer).  "dots": save matmul outputs, recompute
    # only the cheap elementwise chain — near remat-off compute at a
    # fraction of remat-off memory (the standard transformer policy).
    if cfg.remat == "dots":
        fn = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif cfg.remat:
        fn = ra.checkpoint_keeping_attention(body)
    else:
        fn = body
    with scope(LAYERS_SCOPE):
        x, _ = lax.scan(fn, x, params["layers"])
    return x


def _mlm_transform(cfg: BertConfig, params, hidden):
    """MLM head transform (dense + gelu + layernorm).  The dense matmul
    stays in the activation dtype (bf16 on the MXU); gelu/norm accumulate
    in fp32 like every other norm in the model."""
    h = jnp.einsum("...d,de->...e", hidden,
                   params["mlm_dense"].astype(hidden.dtype))
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(hidden.dtype)
    return _layernorm(h, params["mlm_norm"])


def _mlm_loss(cfg: BertConfig, params, hidden, labels):
    """Cross entropy at positions where labels != IGNORE_INDEX; returns
    (sum_loss, n_predictions) so callers can average globally.

    Dense path: computes logits for EVERY position.  The vocab projection
    runs in the activation dtype (bf16 — fp32 here kept the single
    largest matmul in the model off the MXU fast path and materialized a
    (B,S,V) fp32 tensor, 4 GB at batch 64/seq 512); the softmax
    normalizer is accumulated in fp32 via logsumexp, with the upcast
    fused into the reduction so no fp32 copy of the logits lands in HBM,
    and the picked logit is recomputed with fp32 accumulation so the
    per-position CE never sees a bf16-rounded value.
    For pretraining-shaped workloads prefer `_mlm_loss_gathered`, which
    only projects the ~15% masked positions (real-BERT
    max_predictions_per_seq semantics)."""
    h = _mlm_transform(cfg, params, hidden)
    logits = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(h.dtype))
    logits = logits + params["mlm_bias"].astype(h.dtype)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    safe_labels = jnp.maximum(labels, 0)
    # The picked logit is recomputed as a per-position dot with fp32
    # accumulation instead of gathered from the bf16 logits tensor: the
    # big einsum rounds every logit to bf16 (8 mantissa bits), and for
    # the ONE logit that enters the CE directly that rounding lands 1:1
    # in the per-position loss — upcasting after the gather cannot
    # recover it.  Cost: a (B,S,d) elementwise dot, ~1/V of the vocab
    # projection.
    w = jnp.take(params["embed"], safe_labels, axis=0).astype(h.dtype)
    picked = jnp.einsum("bsd,bsd->bs", h, w,
                        preferred_element_type=jnp.float32)
    picked = picked + params["mlm_bias"][safe_labels].astype(jnp.float32)
    ll = picked - lse
    mask = (labels != IGNORE_INDEX).astype(jnp.float32)
    return -(ll * mask).sum(), mask.sum()


def _mlm_loss_gathered(cfg: BertConfig, params, hidden, positions, labels):
    """Cross entropy at `positions` only — the real-BERT pretraining
    formulation (masked_lm_positions / max_predictions_per_seq): the
    vocab projection runs on (B, P, d) with P ≈ 0.15·S instead of
    (B, S, d), cutting the head's FLOPs ~6.7x and its activation
    footprint ~6.7x.  positions: (B, P) int32; labels: (B, P) with
    IGNORE_INDEX marking padded prediction slots."""
    g = jnp.take_along_axis(hidden, positions[..., None], axis=1)
    h = _mlm_transform(cfg, params, g)
    logits = jnp.einsum("bpd,vd->bpv", h, params["embed"].astype(h.dtype),
                        preferred_element_type=jnp.float32)
    logits = logits + params["mlm_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    safe_labels = jnp.maximum(labels, 0)
    ll = jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    mask = (labels != IGNORE_INDEX).astype(jnp.float32)
    return -(ll * mask).sum(), mask.sum()


def forward_loss(cfg: BertConfig, params, tokens, labels,
                 positions=None) -> jax.Array:
    """Per-device MLM loss body; call inside shard_map over (dp, mp).

    tokens: (B_local, S) int32 (batch over dp).  Without `positions`,
    labels is (B_local, S) with IGNORE_INDEX at unmasked positions
    (dense path).  With `positions` (B_local, P), labels is (B_local, P)
    and the head projects only those positions (gathered path).
    Returns the replicated global mean loss."""
    hidden = _encode(cfg, params, tokens, sharded=True)
    with scope("head"):
        if positions is None:
            loss_sum, n = _mlm_loss(cfg, params, hidden, labels)
        else:
            loss_sum, n = _mlm_loss_gathered(cfg, params, hidden, positions,
                                             labels)
    loss_sum = lax.psum(loss_sum, "dp")
    n = lax.psum(n, "dp")
    return loss_sum / jnp.maximum(n, 1.0)


def serial_forward_loss(cfg: BertConfig, params, tokens, labels,
                        positions=None):
    """Unsharded oracle computing the same math — test reference."""
    hidden = _encode(cfg, params, tokens, sharded=False)
    if positions is None:
        loss_sum, n = _mlm_loss(cfg, params, hidden, labels)
    else:
        loss_sum, n = _mlm_loss_gathered(cfg, params, hidden, positions,
                                         labels)
    return loss_sum / jnp.maximum(n, 1.0)


def make_loss_fn(cfg: BertConfig, mesh, gathered: bool = False):
    from ..compat import shard_map
    specs = param_specs(cfg)

    if gathered:
        def body(p, t, pos, l):
            return forward_loss(cfg, p, t, l, positions=pos)
        n_data = 3  # tokens, positions, labels
    else:
        def body(p, t, l):
            return forward_loss(cfg, p, t, l)
        n_data = 2  # tokens, labels

    def loss_of(params, *batch):
        fn = shard_map(
            body, mesh=mesh, in_specs=(specs,) + (P("dp"),) * n_data,
            out_specs=P(), check_vma=False)
        return fn(params, *batch)

    return loss_of


def make_train_step(cfg: BertConfig, mesh, optimizer,
                    gathered: bool = False):
    """(params, opt_state, tokens, [positions,] labels) ->
    (params, opt_state, loss), jitted over the (dp, mp) mesh; gradient
    reductions come from AD.  With ``gathered`` the step takes the
    masked-position tensor and runs the P-position MLM head."""
    from jax.sharding import NamedSharding
    specs = param_specs(cfg)
    loss_of = make_loss_fn(cfg, mesh, gathered=gathered)

    def train_step(params, opt_state, *batch):
        # batch = (tokens, positions, labels) when gathered else
        # (tokens, labels); value_and_grad differentiates argnum 0 only.
        loss, grads = jax.value_and_grad(loss_of)(params, *batch)
        with scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(lambda p, u: p + u, params,
                                            updates)
        return params, opt_state, loss

    def shard_params(params):
        return jax.device_put(
            params, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P)))

    return jax.jit(train_step, donate_argnums=(0, 1)), shard_params


def synthetic_batch(key, cfg: BertConfig, batch: int,
                    mask_rate: float = 0.15) -> Tuple[jax.Array, jax.Array]:
    """Random tokens with `mask_rate` positions masked for MLM: masked
    inputs get the [MASK]-like id 0; labels hold the original id at masked
    positions and IGNORE_INDEX elsewhere."""
    kt, km = jax.random.split(key)
    tokens = jax.random.randint(kt, (batch, cfg.seq_len), 1, cfg.vocab_size,
                                dtype=jnp.int32)
    masked = jax.random.uniform(km, (batch, cfg.seq_len)) < mask_rate
    inputs = jnp.where(masked, 0, tokens)
    labels = jnp.where(masked, tokens, IGNORE_INDEX)
    return inputs, labels


def max_predictions(cfg: BertConfig, mask_rate: float = 0.15) -> int:
    """max_predictions_per_seq for the gathered MLM head, rounded up to a
    lane-friendly multiple of 8 (76.8 -> 80 at seq 512, matching the
    canonical BERT pretraining recipe's 76-80).

    For short sequences the 8-rounding is clamped: it applies only while
    it stays within 2x the exact mask count, so toy configs (seq 16:
    2.4 -> 3 masked, not 8 = 50%) keep roughly the stated mask rate
    instead of silently over-masking."""
    exact = max(1, int(-(-cfg.seq_len * mask_rate // 1)))
    padded = int(-(-exact // 8) * 8)
    return min(padded if padded <= 2 * exact else exact, cfg.seq_len)


def synthetic_mlm_batch(key, cfg: BertConfig, batch: int,
                        mask_rate: float = 0.15):
    """Gathered-head variant of `synthetic_batch`: returns
    (inputs, positions, labels) where positions (B, P) holds P distinct
    masked positions per sequence (P = `max_predictions`), inputs has
    those positions replaced by the [MASK]-like id 0, and labels holds
    the original token ids (no padded slots in the synthetic case)."""
    n_pred = max_predictions(cfg, mask_rate)
    kt, km = jax.random.split(key)
    tokens = jax.random.randint(kt, (batch, cfg.seq_len), 1, cfg.vocab_size,
                                dtype=jnp.int32)
    # P distinct positions per row: top-P of per-row random scores.
    scores = jax.random.uniform(km, (batch, cfg.seq_len))
    positions = jnp.argsort(-scores, axis=-1)[:, :n_pred].astype(jnp.int32)
    labels = jnp.take_along_axis(tokens, positions, axis=1)
    mask = jnp.zeros((batch, cfg.seq_len), jnp.bool_)
    mask = jnp.put_along_axis(mask, positions, True, axis=1,
                              inplace=False)
    inputs = jnp.where(mask, 0, tokens)
    return inputs, positions, labels


def train_flops_per_seq(cfg: BertConfig, n_pred: Optional[int] = None
                        ) -> float:
    """Exact matmul-FLOPs accounting for one BERT MLM training sequence
    (train = 3x fwd) — the bench's audited accounting, importable so
    training loops can feed ``hvd.metrics.set_step_flops()`` with the
    same figure MFU reports use.

    Encoder: per token per layer qkv 6d^2 + proj 2d^2 + mlp 4*d*ff;
    attention 4*S^2*d per layer per seq (scores + AV).  MLM head: the
    transform (2d^2) and tied-vocab projection (2dV) run per predicted
    position — S positions on the dense path, ``n_pred`` on the gathered
    path (real-BERT max_predictions_per_seq semantics), so the gathered
    step's reported MFU counts only the FLOPs it actually executes."""
    d, ff, L, s, v = (cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.seq_len,
                      cfg.vocab_size)
    enc = s * L * (8.0 * d * d + 4.0 * d * ff)
    attn = L * 4.0 * s * s * d
    pos = s if n_pred is None else n_pred
    head = pos * (2.0 * d * d + 2.0 * d * v)
    return 3.0 * (enc + attn + head)
