"""MoE workload class: a decoder LM trained over a (dp, ep) mesh.

The flagship transformer (transformer.py) treats MoE as an optional MLP
mode riding the ``dp`` axis.  This module makes expert parallelism a
first-class workload: a third mesh dimension ``ep`` owns the experts,
tokens cross it through capacity-bounded all_to_all dispatch/combine
(parallel/moe.py), and the training loss carries the router's
load-balancing auxiliary term plus dropped-token accounting as
replicated step metrics.

Layout
------
* batch sharded over the *product* of ``("dp", "ep")`` — every device
  contributes tokens AND hosts experts, the GShard arrangement;
* expert weights ``w_in``/``w_out`` sharded over ``ep`` only
  (each ep member owns ``n_experts / ep`` experts, replicated over dp);
* everything else (embeddings, attention, gates, norms) replicated.

Dispatch may ride the int8/int4 block-scaled wire from
ops/quantization.py (``dispatch_bits``); the combine accumulates in
fp32 regardless.  ``flops_matched_dense_config`` derives the dense
baseline with identical per-token matmul FLOPs (d_ff' = top_k * d_ff)
for loss-parity experiments at equal compute.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.quantization import QuantSpec
from ..parallel import moe as moe_lib
from ..parallel import ring_attention as ra
from . import transformer as tfm


class MoEConfig(NamedTuple):
    vocab_size: int = 32768
    d_model: int = 512
    n_heads: int = 8
    d_ff: int = 2048              # PER-EXPERT hidden width
    n_layers: int = 8
    seq_len: int = 512
    n_experts: int = 8
    top_k: int = 1
    capacity_factor: float = 1.25
    aux_weight: float = 0.01      # load-balancing loss coefficient
    dispatch_bits: int = 0        # 0 → fp32 wire; 8/4 → block-scaled
    dispatch_block: int = 256
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def quant_spec(self) -> Optional[QuantSpec]:
        """The dispatch wire format, or None for fp32."""
        if self.dispatch_bits == 0:
            return None
        return QuantSpec(bits=self.dispatch_bits, block=self.dispatch_block)


class MoEParallelConfig(NamedTuple):
    dp: int = 1
    ep: int = 1

    @property
    def axis_names(self) -> Tuple[str, str]:
        return ("dp", "ep")


def init_params(key, cfg: MoEConfig,
                par: MoEParallelConfig) -> Dict[str, Any]:
    """Full (unsharded) parameter pytree; layers stacked (n_layers, ...)."""
    d, ff, v, s, e = (cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.seq_len,
                      cfg.n_experts)
    h, hd = cfg.n_heads, cfg.head_dim
    if e % par.ep != 0:
        raise ValueError(
            f"n_experts {e} not divisible by ep degree {par.ep}")
    L = cfg.n_layers
    k = iter(jax.random.split(key, 8))
    std = 0.02

    def rand(kk, *shape, scale=std):
        return (jax.random.normal(kk, shape) * scale).astype(jnp.float32)

    return {
        "embed": rand(next(k), v, d),
        "pos": rand(next(k), s, d),
        "final_norm": jnp.ones((d,), jnp.float32),
        "layers": {
            "ln1": jnp.ones((L, d), jnp.float32),
            "ln2": jnp.ones((L, d), jnp.float32),
            "wqkv": rand(next(k), L, d, 3 * h * hd),
            "wo": rand(next(k), L, h * hd, d,
                       scale=std / math.sqrt(2 * L)),
            "gate": rand(next(k), L, d, e),
            "w_in": rand(next(k), L, e, d, ff),
            "w_out": rand(next(k), L, e, ff, d,
                          scale=std / math.sqrt(2 * L)),
        },
    }


def param_specs(cfg: MoEConfig, par: MoEParallelConfig) -> Dict[str, Any]:
    """PartitionSpec pytree: experts over ``ep``, the rest replicated."""
    return {
        "embed": P(),
        "pos": P(),
        "final_norm": P(),
        "layers": {
            "ln1": P(),
            "ln2": P(),
            "wqkv": P(),
            "wo": P(),
            "gate": P(),
            "w_in": P(None, "ep", None, None),
            "w_out": P(None, "ep", None, None),
        },
    }


def _attention(cfg: MoEConfig, lp: Dict[str, jax.Array],
               x: jax.Array) -> jax.Array:
    """Local full-sequence causal attention (batch-sharded stream)."""
    hd = cfg.head_dim
    h = tfm._rmsnorm(x, lp["ln1"])
    qkv = jnp.einsum("bsd,de->bse", h, lp["wqkv"].astype(x.dtype))
    b, s = qkv.shape[:2]
    qkv = qkv.reshape(b, s, cfg.n_heads, 3, hd)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    o = ra.full_attention(q, k, v, causal=True)
    return jnp.einsum("bse,ed->bsd", o.reshape(b, s, -1),
                      lp["wo"].astype(x.dtype))


def _layer(cfg: MoEConfig, lp: Dict[str, jax.Array], x: jax.Array,
           axis_name: str) -> Tuple[jax.Array, jax.Array]:
    """One block: attention + routed-MoE MLP.  Returns (x, stats (3,))
    with stats = [aux_loss, dropped, routed] for this layer."""
    x = x + _attention(cfg, lp, x)
    h = tfm._rmsnorm(x, lp["ln2"])
    b, s, d = h.shape
    tok = h.reshape(b * s, d)
    mp = moe_lib.MoEParams(
        gate=lp["gate"].astype(jnp.float32),
        w_in=lp["w_in"],        # (E_local, d, ff) after ep sharding
        w_out=lp["w_out"],
    )
    y, stats = moe_lib.moe_layer(
        mp, tok, axis_name, capacity_factor=cfg.capacity_factor,
        top_k=cfg.top_k, quant=cfg.quant_spec(), return_stats=True)
    x = x + y.reshape(b, s, d).astype(x.dtype)
    return x, jnp.stack([stats.aux_loss,
                         stats.dropped.astype(jnp.float32),
                         stats.routed.astype(jnp.float32)])


def forward_loss(cfg: MoEConfig, par: MoEParallelConfig,
                 params: Dict[str, Any], tokens: jax.Array,
                 labels: jax.Array
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Per-device loss body; call inside shard_map over mesh (dp, ep).

    tokens/labels: (B_local, S) int32 shards (batch over dp×ep).
    Returns (replicated scalar total loss, replicated metrics dict):
    ``ce`` mean cross-entropy, ``aux`` mean per-layer load-balancing
    loss, ``dropped``/``routed`` global token counts for the step.
    """
    x = (params["embed"][tokens] + params["pos"][None]).astype(cfg.dtype)

    def layer_fn(carry, lp):
        return _layer(cfg, lp, carry, "ep")

    body = jax.checkpoint(layer_fn) if cfg.remat else layer_fn
    x, per_layer = lax.scan(body, x, params["layers"])   # (L, 3)

    hidden = tfm._rmsnorm(x, params["final_norm"])
    logits = jnp.einsum("bsd,vd->bsv", hidden.astype(jnp.float32),
                        params["embed"].astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    ce = lax.pmean(-jnp.mean(ll), ("dp", "ep"))

    # The aux loss is computed from per-ep-member counts inside
    # moe_layer; average over layers, then over the mesh.
    aux = lax.pmean(jnp.mean(per_layer[:, 0]), ("dp", "ep"))
    dropped = lax.psum(jnp.sum(per_layer[:, 1]), ("dp", "ep"))
    routed = lax.psum(jnp.sum(per_layer[:, 2]), ("dp", "ep"))
    total = ce + cfg.aux_weight * aux
    return total, {"ce": ce, "aux": aux, "dropped": dropped,
                   "routed": routed}


def make_loss_fn(cfg: MoEConfig, par: MoEParallelConfig, mesh):
    """Global-array loss: shard_map of ``forward_loss`` over (dp, ep)."""
    from ..compat import shard_map
    specs = param_specs(cfg, par)
    data_spec = P(("dp", "ep"))

    def loss_of(params, tokens, labels):
        fn = shard_map(
            lambda p, t, l: forward_loss(cfg, par, p, t, l),
            mesh=mesh, in_specs=(specs, data_spec, data_spec),
            out_specs=(P(), {"ce": P(), "aux": P(), "dropped": P(),
                             "routed": P()}),
            check_vma=False)
        return fn(params, tokens, labels)

    return loss_of


def make_train_step(cfg: MoEConfig, par: MoEParallelConfig, mesh,
                    optimizer):
    """Jitted train step over the (dp, ep) mesh.

    Returns (train_step, shard_params) with ``train_step(params,
    opt_state, tokens, labels) -> (params, opt_state, loss, metrics)``.
    Differentiation happens outside shard_map — expert-grad reductions
    over dp and dense-grad reductions over (dp, ep) come from AD
    transposes of the pmean/psum, no hand-written sync.
    """
    specs = param_specs(cfg, par)
    loss_of = make_loss_fn(cfg, par, mesh)

    def train_step(params, opt_state, tokens, labels):
        (loss, metrics), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params, tokens, labels)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss, metrics

    from jax.sharding import NamedSharding

    def shard_params(params):
        return jax.device_put(
            params, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P)))

    jitted = jax.jit(train_step, donate_argnums=(0, 1))

    quant = cfg.quant_spec()
    if quant is None:
        return jitted, shard_params

    # Quantized dispatch wire: account the per-step all_to_all bytes
    # analytically (2 exchanges x n_layers per member; the compiled
    # plane has no per-op host hook) into the kind="gspmd" wire
    # counters — see docs/metrics.md.
    from ..ops import xla_collectives as XC
    members = par.dp * par.ep
    n_local_experts = cfg.n_experts // par.ep
    plans: Dict[int, XC.StepWireBytes] = {}

    def _wire_plan(global_batch: int) -> XC.StepWireBytes:
        n_local_tok = max(1, global_batch // members) * cfg.seq_len
        cap = moe_lib.expert_capacity(
            n_local_tok, cfg.n_experts, cfg.capacity_factor, cfg.top_k)
        raw = 2 * cfg.n_layers * moe_lib.dispatch_wire_bytes(
            par.ep, n_local_experts, cap, cfg.d_model, None)
        sent = 2 * cfg.n_layers * moe_lib.dispatch_wire_bytes(
            par.ep, n_local_experts, cap, cfg.d_model, quant)
        return XC.StepWireBytes(raw=raw, sent=sent)

    def metered_step(params, opt_state, tokens, labels):
        out = jitted(params, opt_state, tokens, labels)
        b = int(tokens.shape[0])
        plan = plans.get(b)
        if plan is None:
            plan = plans[b] = _wire_plan(b)
        XC.record_wire_bytes(plan.raw, plan.sent)
        return out

    return metered_step, shard_params


def serial_forward_logits(cfg: MoEConfig, params: Dict[str, Any],
                          tokens: jax.Array) -> jax.Array:
    """Unsharded per-token-routed oracle: full fp32 logits (B, S, V).

    Routes top-k per token WITHOUT the capacity clamp — identical to the
    sharded forward exactly when nothing drops (capacity_factor high
    enough that ``dropped == 0``), which is how tests pin the sharded
    dispatch/combine math.  Shares the serving MLP helper, so serving
    and the training oracle are one implementation.
    """
    s_in = tokens.shape[1]
    x = (params["embed"][tokens] + params["pos"][None, :s_in]).astype(
        cfg.dtype)
    L = cfg.n_layers
    for l in range(L):
        lp = {k: v[l] for k, v in params["layers"].items()}
        x = x + _attention(cfg, lp, x)
        h = tfm._rmsnorm(x, lp["ln2"])
        b, s, d = h.shape
        y = tfm._moe_mlp_serving(cfg, lp, h.reshape(b * s, d))
        x = x + y.reshape(b, s, d)
    hidden = tfm._rmsnorm(x, params["final_norm"])
    return jnp.einsum("bsd,vd->bsv", hidden.astype(jnp.float32),
                      params["embed"].astype(jnp.float32))


def serial_forward_loss(cfg: MoEConfig, params: Dict[str, Any],
                        tokens: jax.Array, labels: jax.Array) -> jax.Array:
    """Cross-entropy of the no-capacity serial oracle (no aux term)."""
    logits = serial_forward_logits(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def _flagship_config(cfg: MoEConfig, **mlp) -> tfm.TransformerConfig:
    """``cfg``'s geometry as the flagship's config, MLPs of ``mlp``."""
    return tfm.TransformerConfig(
        vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        n_heads=cfg.n_heads, n_layers=cfg.n_layers, seq_len=cfg.seq_len,
        dtype=cfg.dtype, remat=cfg.remat, **mlp)


def flops_matched_dense_config(cfg: MoEConfig) -> tfm.TransformerConfig:
    """The dense baseline with identical per-token matmul FLOPs.

    Each token visits top_k experts of hidden ff, so the equal-compute
    dense width is d_ff' = top_k * d_ff (the 2*d*E gate is the only
    remainder — negligible and counted by ``train_flops_per_seq``).
    Loss-parity-at-equal-FLOPs experiments train both from the same
    seed and compare trajectories.
    """
    return _flagship_config(cfg, d_ff=cfg.top_k * cfg.d_ff, n_experts=0)


def train_flops_per_seq(cfg: MoEConfig) -> float:
    """Audited matmul-FLOPs for one training sequence (3x forward);
    counts the routed top_k experts + gate per token, as the flagship
    accounts its own ungated experts."""
    return tfm.train_flops_per_seq(_flagship_config(
        cfg, d_ff=cfg.d_ff, n_experts=cfg.n_experts, top_k=cfg.top_k))


def synthetic_batch(key, cfg: MoEConfig, batch: int):
    return tfm.synthetic_batch(key, _flagship_config(cfg), batch)
