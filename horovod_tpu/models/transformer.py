"""Flagship model: Transformer LM composed with dp / pp / tp / sp / ep.

The reference framework is model-agnostic middleware; its benchmark models
(ResNet-50, BERT — BASELINE.md) are external.  This framework ships its
models, and this one is the flagship: a decoder-only Transformer whose
training step exercises every parallelism axis the framework supports:

* **dp**   — batch sharded over the ``dp`` mesh axis; gradient reduction is
  inserted by AD/XLA when the step is differentiated over the mesh.
* **pp**   — layers split into stages over ``pp``; GPipe microbatch schedule
  (parallel/pipeline.py) with ppermute hops.
* **tp**   — Megatron column/row parallel attention heads and MLP over the
  ``mp`` axis (parallel/tensor_parallel.py).
* **sp**   — sequence parallelism over the same ``mp`` axis: the residual
  stream stays sequence-sharded (Megatron-SP); ``attn_mode="ring"`` keeps it
  sharded *through* attention via ring attention
  (parallel/ring_attention.py).
* **ep**   — optional switch-MoE MLPs with experts sharded over the ``dp``
  axis and all_to_all routing (parallel/moe.py, capacity path).

``TransformerConfig``'s defaults describe the repository's own block
(learned positions, GELU MLP, tied head).  Its architecture fields turn the
same training path into a published one: ``rope_theta``, ``qk_norm``,
``norm_eps``, ``gated_experts`` + ``dropless`` (parallel/moe.py, dropless
path, experts replicated over the mesh), ``tied_head=False`` and the
router's two auxiliary losses give OLMoE-1B-7B (arXiv:2409.02060) for
training.  ``layer_pattern`` turns the one block into a period of blocks of
several kinds, ``x + mixer(RMSNorm(x))`` each and each kind one row of
``BLOCKS``: ``M`` a Mamba-2 state-space mixer (ops/ssd.py), ``E`` an expert
MLP, ``*`` grouped-query attention, ``W`` sliding-window attention with its
own head count and rotary base, ``D`` a gated dense MLP, ``C`` a gated short
convolution.  With ``n_experts_held`` (a share of the routed experts), a
sigmoid router, experts in a latent space and a shared expert they give
Nemotron 3's hybrid (``nemotron_h``) as one rank of its deployment, on
``dp`` alone; with ``leading_pattern`` (blocks that run once, in front of
the scanned periods), rotary positions on a share of the head with
YaRN-scaled frequencies and a per-head output gate, Laguna's mix of windowed
and full attention (``laguna``); with the router's renormalisation over
``sum + router_renorm_eps``, LFM2's hybrid of convolution and attention
blocks (``lfm2_moe``); with ``router_before_attention`` (an ``E`` block's
router reading the stream as the block before it received it), ReLU-gated
experts and full layers without positions beside rotating windowed ones,
SmallThinker's layer (``smallthinker``); with "S" blocks (attention over
the ``index_topk`` keys a learned indexer ranks highest for each query,
ops/sparse_index.py, the indexer's own loss beside the model's) and rotary
positions read from three streams of a batch array (``rope_sections``),
Keye-VL-2.0's language model (``keye_vl``).  ``diffusion_block`` turns the
step itself into block-diffusion training (BD3-LMs, arXiv:2503.09573; SDAR,
arXiv:2510.06303; see ``forward_loss``), the noise being data
(:func:`noised_batch`).  The serving entry points below cover learned
positions only.

Compute dtype defaults to bfloat16 (MXU-native); normalization, softmax and
loss accumulate in fp32.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import math
from types import SimpleNamespace
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..compat import axis_size
from jax.sharding import PartitionSpec as P

from ..metrics.registry import registry
from ..ops import qk_position
from ..ops import sparse_index as si
from ..ops import ssd
from ..parallel import moe as moe_lib
from ..parallel import pipeline as pp_lib
from ..parallel import ring_attention as ra
from ..parallel import tensor_parallel as tp
from ..utils.profiler import (ATTN_OPERAND_SCOPES, CONV_SCOPES,
                              DENSE_MLP_SCOPE, INDEX_SCOPES, LAYERS_SCOPE,
                              TP_RING_SCOPES, scope)

GATHER_RING, SCATTER_RING = TP_RING_SCOPES


class TransformerConfig(NamedTuple):
    vocab_size: int = 32768
    d_model: int = 512
    n_heads: int = 8
    d_ff: int = 2048
    n_layers: int = 8
    seq_len: int = 512
    n_experts: int = 0            # 0 → dense MLP; >0 → switch MoE
    capacity_factor: float = 1.25
    attn_mode: str = "megatron"   # "megatron" (tp heads) | "ring" | "ulysses" (sp)
    dtype: Any = jnp.bfloat16
    remat: bool = True
    top_k: int = 1                # MoE routes per token (serving + routing)
    # The architecture, beyond the widths.  The defaults are the block
    # above; see the module docstring.
    rope_theta: Optional[float] = None  # None → learned ``pos`` table
    qk_norm: bool = False         # RMSNorm of the whole projected q and k
    norm_eps: float = 1e-6
    gated_experts: bool = False   # (silu(x Wg) * x Wu) Wd; needs dropless
    dropless: bool = False        # MoE: sort + grouped matmul, no capacity
    tied_head: bool = True        # False → ``lm_head``, apart from ``embed``
    aux_loss_coef: float = 0.0    # x router load-balancing loss (dropless)
    z_loss_coef: float = 0.0      # x router z-loss (dropless)
    # Blocks of several kinds: one letter of ``BLOCKS`` a block of one
    # period, ``n_layers`` a multiple of its length.  None → the block above
    # (attention then MLP) ``n_layers`` times.
    layer_pattern: Optional[str] = None
    learned_positions: bool = True  # False (and no rope_theta) → none at all
    n_kv_heads: Optional[int] = None    # "*" blocks; None → n_heads
    attn_head_dim: Optional[int] = None  # None → d_model // n_heads
    # "M" blocks (Mamba-2): heads of ``ssm_head_dim``, ``ssm_groups`` groups
    # of B / C of ``ssm_state``, a causal conv of ``ssm_conv`` taps, the scan
    # in chunks of ``ssm_chunk``, dt log-uniform in [min, max], floored.
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_dt_range: Tuple[float, float, float] = (1e-3, 1e-1, 1e-4)
    # The dropless router and what it routes to (parallel/moe.py).
    router_scoring: str = "softmax"   # | "sigmoid": choice by score + bias
    router_renormalise: bool = False  # chosen weights over their sum
    router_scale: float = 1.0
    # "E" blocks: the router reads the stream as the block before received
    # it, unnormed (a layer's input, ahead of its attention); the experts
    # still read this block's normed input.
    router_before_attention: bool = False
    n_experts_held: Optional[int] = None  # experts 0..held-1 live here
    expert_buffer_factor: float = 4.0  # held experts' rows: x the mean
    moe_latent: int = 0           # > 0: experts work in a latent space
    shared_expert_ff: int = 0     # > 0: an expert every token takes
    expert_activation: Optional[str] = None  # None → silu gated, else gelu
    # Blocks that run once, in front of the scanned periods (letters as
    # ``layer_pattern``'s, but for "E"); they count in ``n_layers``.
    leading_pattern: str = ""
    # "W" blocks: "*" with ``attn_window`` keys a query (its own included),
    # ``window_heads`` query heads (None → n_heads) over the same kv heads,
    # and the whole head rotated at ``window_rope_theta`` (None → not).
    attn_window: Optional[int] = None
    window_heads: Optional[int] = None
    window_rope_theta: Optional[float] = None
    # "*" blocks of a pattern rotate at ``rope_theta``: the first
    # ``rope_fraction`` of each head, the rest passing; ``rope_yarn`` =
    # (factor, original positions, beta_fast, beta_slow, attention factor)
    # scales the frequencies as HF ``_compute_yarn_parameters`` does.
    rope_fraction: float = 1.0
    rope_yarn: Optional[Tuple[float, int, float, float, float]] = None
    attn_gate: bool = False       # "*" / "W": head i's output x sigmoid(h Wg)_i
    dense_ff: int = 0             # "D" blocks: (silu(h W1) * h W3) W2
    # Block-diffusion training of a patterned model, in blocks of
    # ``diffusion_block`` positions; see ``forward_loss``.
    diffusion_block: Optional[int] = None
    # "S" blocks: "*" in which a query sees, of the keys at or before it, the
    # ``index_topk`` that an indexer of ``index_heads`` heads of
    # ``index_head_dim`` over one key head scores highest (ops/
    # sparse_index.py), trained by ``index_loss_coef`` x its own loss; and
    # q, k and the indexer's rotate by ``rope_sections`` (frequencies a
    # stream, of head_dim / 2) of the three position streams a batch
    # brings as a third array, (B, 3, S): ``forward_loss(positions=)``.
    index_heads: int = 0
    index_head_dim: int = 64
    index_topk: int = 0
    index_loss_coef: float = 1.0
    rope_sections: Optional[Tuple[int, ...]] = None
    head_qk_norm: bool = False    # "*" / "W": RMSNorm over each head of q, k
    conv_taps: int = 0            # "C" blocks: the convolution's taps
    router_renorm_eps: float = 0.0  # renormalised weights: over sum + eps

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.d_model // self.n_heads


class ParallelConfig(NamedTuple):
    dp: int = 1
    pp: int = 1
    mp: int = 1                   # shared tensor/sequence axis
    n_microbatches: int = 1
    pp_schedule: str = "gpipe"    # "gpipe" | "1f1b" (bounded-stash backward)

    @property
    def axis_names(self) -> Tuple[str, str, str]:
        return ("dp", "pp", "mp")


def _routes_dropless(cfg: TransformerConfig) -> bool:
    return cfg.n_experts > 0 and cfg.dropless


def _experts_held(cfg: TransformerConfig) -> int:
    return cfg.n_experts if cfg.n_experts_held is None else cfg.n_experts_held


def _holds_a_share(cfg: TransformerConfig) -> bool:
    return _routes_dropless(cfg) and _experts_held(cfg) < cfg.n_experts


def _has_pos_table(cfg: TransformerConfig) -> bool:
    return cfg.rope_theta is None and cfg.learned_positions


def _selects(cfg: TransformerConfig) -> bool:
    """Whether the pattern has blocks of a learned selection, whose batch
    brings position streams and whose loss has the indexers' term."""
    return cfg.layer_pattern is not None and any(
        BLOCKS[c].selects for c in cfg.layer_pattern)


def batch_extras(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The arrays a batch brings after ``tokens`` and ``labels``, by
    ``forward_loss``'s names for them."""
    if cfg.diffusion_block is not None:
        return ("weights",)
    return ("positions",) if _selects(cfg) else ()


_ACTIVATIONS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
                "relu": jax.nn.relu,
                "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def _check_layout(cfg: TransformerConfig, par: ParallelConfig) -> None:
    """What a patterned or share-holding model runs on, ``dp`` alone, and
    what the kinds of block its pattern names (``BLOCKS``) ask of it."""
    if cfg.layer_pattern is not None:
        lead, period = cfg.leading_pattern, cfg.layer_pattern
        present = set(lead + period)
        if present - set(BLOCKS) or not period:
            raise ValueError(
                f"layer_pattern {period!r} after leading_pattern {lead!r}: "
                f"letters are {sorted(BLOCKS)}")
        if (cfg.n_layers - len(lead)) % len(period):
            raise ValueError(
                f"n_layers {cfg.n_layers} is not {len(lead)} leading blocks "
                f"and a multiple of the pattern's {len(period)}")
        if cfg.qk_norm:
            raise NotImplementedError(
                "a patterned model's attention blocks take rotary positions "
                "(rope_theta, window_rope_theta) and no QK-norm yet")
        for c, row in BLOCKS.items():
            if (row.routes or row.selects) and lead.count(c):
                raise NotImplementedError(
                    f'an "{c}" block cannot lead: the router statistics '
                    "and the indexers' losses are stacked by period")
            refusal = row.asks(cfg, c in present)
            if refusal:
                raise ValueError(refusal)
            if row.before(cfg) and c in (lead[:1], period[0]):
                raise ValueError(
                    f'an "{c}" block that reads the stream as the block '
                    "before it received it cannot open the model or the "
                    f"period {period!r}: no block of its scan step lies "
                    "before it")
        if cfg.attn_mode != "megatron" and any(
                BLOCKS[c].selects for c in present):
            raise NotImplementedError(
                f"learned sparse attention with attn_mode {cfg.attn_mode!r}: "
                "a query's chosen keys live on other shards of the sequence, "
                "and the selection runs through selected_attention (attn_mode "
                "'megatron', mp 1; ROADMAP M11)")
        if cfg.diffusion_block is not None:
            if cfg.diffusion_block < 1 or cfg.seq_len % cfg.diffusion_block:
                raise ValueError(
                    f"seq_len {cfg.seq_len} is not whole blocks of "
                    f"diffusion_block {cfg.diffusion_block}")
            if any(BLOCKS[c].crosses for c in present):
                *some, last = [r.crosses for r in BLOCKS.values() if r.crosses]
                raise NotImplementedError(
                    "diffusion_block goes with \"*\" attention blocks: "
                    f"{', '.join(some)} or {last} over the doubled sequence "
                    "would cross from the noised copy into the clean one")
            if _has_pos_table(cfg):
                raise NotImplementedError(
                    "diffusion_block wraps rotary positions (rope_theta) at "
                    "seq_len; a learned position table is not laid over the "
                    "doubled sequence")
            if cfg.attn_mode != "megatron":
                raise NotImplementedError(
                    f"diffusion_block with attn_mode {cfg.attn_mode!r}: the "
                    "block-diffusion mask runs through full_attention "
                    "(attn_mode 'megatron'); ring and Ulysses attention "
                    "refuse it")
        if par.mp > 1 or par.pp > 1 or par.pp_schedule != "gpipe":
            raise NotImplementedError(
                "a model with a layer_pattern runs on dp alone: its mixers "
                "are neither sharded over mp nor staged over pp (ROADMAP "
                "M0); nor is a diffusion_block's doubled sequence, nor a "
                "learned selection's keys (ROADMAP M11)")
    else:
        # The pattern's own fields and every kind's stay at their defaults.
        own = {"leading_pattern", "diffusion_block"}.union(
            *(row.fields for row in BLOCKS.values()))
        *some, last = [f for f in cfg._fields if f in own]
        if cfg.n_kv_heads == cfg.n_heads:      # what its default means
            own.remove("n_kv_heads")
        if any(getattr(cfg, f) != cfg._field_defaults[f] for f in own):
            raise ValueError(f"{', '.join(some)} and {last} are a patterned "
                             "model's: set layer_pattern")
    if _holds_a_share(cfg) and (par.mp > 1 or par.pp > 1):
        raise NotImplementedError(
            f"a layer that holds {_experts_held(cfg)} of {cfg.n_experts} "
            "experts runs on dp alone: the exchange that brings the other "
            "ranks' tokens is not written (ROADMAP M2)")


def init_params(key, cfg: TransformerConfig,
                par: ParallelConfig) -> Dict[str, Any]:
    """Initialize the full (unsharded) parameter pytree; shardings are
    applied by ``param_specs`` + jit in_shardings."""
    d, ff, v, s = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.seq_len
    h, hd = cfg.n_heads, cfg.head_dim
    n_pp = par.pp
    _check_layout(cfg, par)
    if cfg.n_layers % n_pp != 0:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp {n_pp}")
    lps = cfg.n_layers // n_pp  # layers per stage
    k = iter(jax.random.split(key, 16))
    std = 0.02

    def norm_init(*shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def rand(kk, *shape, scale=std):
        return (jax.random.normal(kk, shape) * scale).astype(jnp.float32)

    if cfg.gated_experts and not _routes_dropless(cfg):
        raise ValueError("gated_experts are the dropless path's: the "
                         "capacity path has GELU experts (parallel/moe.py)")
    k_embed, k_pos, k_qkv, k_wo = (next(k) for _ in range(4))
    params: Dict[str, Any] = {
        "embed": rand(k_embed, v, d),
        "final_norm": norm_init(d),
    }
    if _has_pos_table(cfg):
        params["pos"] = rand(k_pos, s, d)
    if not cfg.tied_head:
        params["lm_head"] = rand(next(k), v, d)
    if cfg.layer_pattern is not None:
        params["layers"] = _init_pattern_layers(next(k), cfg)
        return params
    params["layers"] = {
        "ln1": norm_init(n_pp, lps, d),
        "ln2": norm_init(n_pp, lps, d),
        "wqkv": rand(k_qkv, n_pp, lps, d, 3 * h * hd),
        "wo": rand(k_wo, n_pp, lps, h * hd, d,
                   scale=std / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qk_norm:
        params["layers"]["q_norm"] = norm_init(n_pp, lps, h * hd)
        params["layers"]["k_norm"] = norm_init(n_pp, lps, h * hd)
    if _routes_dropless(cfg):
        e, held = cfg.n_experts, _experts_held(cfg)
        params["layers"]["gate"] = rand(next(k), n_pp, lps, d, e)
        if cfg.gated_experts:
            params["layers"]["w_gate"] = rand(next(k), n_pp, lps, held, d, ff)
        params["layers"]["w_up"] = rand(next(k), n_pp, lps, held, d, ff)
        params["layers"]["w_down"] = rand(
            next(k), n_pp, lps, held, ff, d,
            scale=std / math.sqrt(2 * cfg.n_layers))
    elif cfg.n_experts > 0:
        if cfg.n_experts % par.dp != 0:
            raise ValueError("n_experts must be divisible by dp (=ep) degree")
        params["layers"]["gate"] = rand(next(k), n_pp, lps, d, cfg.n_experts)
        params["layers"]["w_in"] = rand(next(k), n_pp, lps, cfg.n_experts,
                                        d, ff)
        params["layers"]["w_out"] = rand(
            next(k), n_pp, lps, cfg.n_experts, ff, d,
            scale=std / math.sqrt(2 * cfg.n_layers))
    else:
        params["layers"]["w1"] = rand(next(k), n_pp, lps, d, ff)
        params["layers"]["w2"] = rand(next(k), n_pp, lps, ff, d,
                                      scale=std / math.sqrt(2 * cfg.n_layers))
    return params


def _row(kind: str) -> "BlockKind":
    return next(row for row in BLOCKS.values() if row.key == kind)


def pattern_counts(cfg: TransformerConfig, leading: bool = False
                   ) -> Dict[str, int]:
    """{kind: its blocks in one period} for the kinds the pattern has, in
    the order of ``BLOCKS``; ``leading``: {kind: its blocks} of
    ``leading_pattern``."""
    pattern = cfg.leading_pattern if leading else cfg.layer_pattern
    return {row.key: pattern.count(c) for c, row in BLOCKS.items()
            if c in pattern}


def _n_periods(cfg: TransformerConfig) -> int:
    return (cfg.n_layers - len(cfg.leading_pattern)) // len(cfg.layer_pattern)


def _init_pattern_layers(key, cfg: TransformerConfig) -> Dict[str, Any]:
    """A patterned model's blocks, stacked by kind: every leaf is (1 stage,
    periods, the kind's blocks a period, ...); under ``leading`` the
    blocks of ``leading_pattern`` by kind, (1 stage, blocks of the kind,
    ...).  A kind's leaves are its row's to make (``BlockKind.init``), each
    ``lead + shape`` in fp32, the drawn ones off one key stream in the order
    they are asked for; weights as the block above's, normal ``std``, the
    projections that write the residual ``out_scale``."""
    std = 0.02

    # 24 keys at a time, the first 24 as they always were drawn.
    keys = itertools.chain.from_iterable(
        jax.random.split(jax.random.fold_in(key, n) if n else key, 24)
        for n in itertools.count())

    def init_kind(kind, lead):
        def ones(*shape):
            return jnp.ones(lead + shape, jnp.float32)

        def rand(*shape, scale=std):
            return (jax.random.normal(next(keys), lead + shape)
                    * scale).astype(jnp.float32)

        def uniform(*shape, lo, hi):
            return jax.random.uniform(next(keys), lead + shape,
                                      jnp.float32, lo, hi)

        return _row(kind).init(cfg, SimpleNamespace(
            ones=ones, rand=rand, uniform=uniform, std=std,
            out_scale=std / math.sqrt(2 * cfg.n_layers)))

    layers = {kind: init_kind(kind, (1, _n_periods(cfg), n))
              for kind, n in pattern_counts(cfg).items()}
    if cfg.leading_pattern:
        layers["leading"] = {
            kind: init_kind(kind, (1, n))
            for kind, n in pattern_counts(cfg, leading=True).items()}
    return layers


def param_specs(cfg: TransformerConfig, par: ParallelConfig) -> Dict[str, Any]:
    """PartitionSpec pytree matching ``init_params`` (mesh axes dp/pp/mp)."""
    _check_layout(cfg, par)
    specs = {"embed": P(), "final_norm": P()}
    if _has_pos_table(cfg):
        specs["pos"] = P()
    if not cfg.tied_head:
        specs["lm_head"] = P()
    if cfg.layer_pattern is not None:
        # On dp alone: every leaf replicated, its gradient reduced by AD.
        shapes = jax.eval_shape(lambda: _init_pattern_layers(
            jax.random.PRNGKey(0), cfg))
        return {**specs, "layers": jax.tree_util.tree_map(
            lambda _: P("pp"), shapes)}
    megatron = cfg.attn_mode == "megatron"
    layers: Dict[str, Any] = {
        "ln1": P("pp"),
        "ln2": P("pp"),
        # Megatron: qkv column-parallel (heads over mp), wo row-parallel.
        # Ring/Ulysses: attention weights replicated over mp (sequence sharded).
        "wqkv": P("pp", None, None, "mp") if megatron else P("pp"),
        "wo": P("pp", None, "mp", None) if megatron else P("pp"),
    }
    if cfg.qk_norm:
        # One scale a feature of q (k), so sharded as the heads are.
        layers["q_norm"] = P("pp", None, "mp") if megatron else P("pp")
        layers["k_norm"] = layers["q_norm"]
    if _routes_dropless(cfg):
        # Every expert on every device: the sequence-parallel stream is
        # token-wise, so the block needs no gather, and the gradients
        # reduce over dp and mp by AD as the other replicated leaves' do.
        layers["gate"] = P("pp")
        if cfg.gated_experts:
            layers["w_gate"] = P("pp")
        layers["w_up"] = P("pp")
        layers["w_down"] = P("pp")
    elif cfg.n_experts > 0:
        layers["gate"] = P("pp")
        layers["w_in"] = P("pp", None, "dp", None, None)   # experts over dp
        layers["w_out"] = P("pp", None, "dp", None, None)
    else:
        layers["w1"] = P("pp", None, None, "mp")
        layers["w2"] = P("pp", None, "mp", None)
    return {**specs, "layers": layers}


def _rmsnorm(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _qk_norm(t, scale, eps: float, axis_name: Optional[str]):
    """RMSNorm over every feature of a projected q or k — all heads at
    once, as OLMoE normalises before it splits the heads.  ``t``: (mb, S,
    local heads, hd); ``scale``: (local heads * hd,).  With the heads
    sharded over ``axis_name`` the mean square is over all members'."""
    tf = t.astype(jnp.float32)
    sq = jnp.sum(tf * tf, axis=(-2, -1), keepdims=True)
    n = t.shape[-2] * t.shape[-1]
    if axis_name is not None:
        sq = lax.psum(sq, axis_name)
        n *= axis_size(axis_name)
    return (tf * lax.rsqrt(sq / n + eps)
            * scale.reshape(t.shape[-2:])).astype(t.dtype)


def _yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """The ``dim // 2`` rotary frequencies ``theta^(-2i/dim)`` scaled as HF
    ``_compute_yarn_parameters`` scales them, ``yarn`` = (factor, original
    positions, beta_fast, beta_slow, _): a frequency that turns more than
    ``beta_fast`` times over the original context stays, one that turns
    less than ``beta_slow`` times is divided by ``factor``, and between the
    two correction dimensions (floored, ceiled) a linear ramp blends them."""
    factor, original, beta_fast, beta_slow = yarn[:4]
    inv_freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
            ).astype(np.float32)


def _rope_angles(positions, rot: int, theta: float, yarn=None, lanes=None):
    """(cos, sin), (S, rot // 2) fp32 each: the angle ``position *
    theta^(-2i/rot)`` of the frequencies that rotate the first ``rot``
    features of a head; with ``yarn`` the frequencies are
    :func:`_yarn_inv_freq`'s and cos and sin are multiplied by its attention
    factor.  ``positions``: (S,) global token positions.  With ``lanes``
    (static ints) the columns are those frequencies' instead, one a lane of
    the kernel's tables (``ops/qk_position.lanes``)."""
    half = rot // 2
    index = np.arange(half) if lanes is None else lanes
    if yarn is None:
        inv_freq = 1.0 / (theta ** (jnp.asarray(index, jnp.float32) / half))
    else:
        inv_freq = jnp.asarray(_yarn_inv_freq(rot, theta, yarn)[index])
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if yarn is not None:
        cos, sin = cos * yarn[4], sin * yarn[4]
    return cos, sin


def _stream_angles(positions, head_dim: int, theta: float, sections,
                   lanes=None):
    """(cos, sin), (mb, S, head_dim // 2) fp32 each, with the angle of
    frequency i read from one of several position streams (Qwen2-VL's
    sectioned layout): ``positions`` (mb, streams, S) integers, ``sections``
    the frequencies a stream takes, in order, ``sum(sections)`` = half the
    head: frequency i turns by ``positions[:, c(i)] * theta^(-2i/hd)``, c(i)
    the section i falls in.  ``lanes`` as :func:`_rope_angles` takes it."""
    half = head_dim // 2
    if sum(sections) != half or positions.shape[1] != len(sections):
        raise ValueError(
            f"rope sections {tuple(sections)} over {positions.shape[1]} "
            f"position streams do not cover the {half} frequencies of a head "
            f"of {head_dim}")
    index = np.arange(half) if lanes is None else lanes
    inv_freq = 1.0 / (theta ** (jnp.asarray(index, jnp.float32) / half))
    stream = np.repeat(np.arange(len(sections)), sections)[index]
    at = jnp.moveaxis(positions.astype(jnp.float32), 1, 2)   # (mb, S, streams)
    angle = at[..., stream] * inv_freq                   # (mb, S, frequencies)
    return jnp.cos(angle), jnp.sin(angle)


def _rotate_half(t, cos, sin):
    """Rotate-half rotary embedding (HF ``apply_rotary_pos_emb``) on the
    first ``2 half`` features of each head, the rest passing: with the
    rotary part ``[t1, t2]`` split at its half, ``[t1 cos - t2 sin, t2 cos +
    t1 sin]``, in fp32.  ``t``: (mb, S, heads, hd); ``cos``, ``sin``: (S,
    half), or (mb, S, half) where a sequence has its own angles."""
    half = cos.shape[-1]
    cos, sin = cos[..., None, :], sin[..., None, :]
    tf = t.astype(jnp.float32)
    t1, t2 = tf[..., :half], tf[..., half:2 * half]
    parts = [t1 * cos - t2 * sin, t2 * cos + t1 * sin]
    if 2 * half < t.shape[-1]:
        parts.append(tf[..., 2 * half:])
    return jnp.concatenate(parts, axis=-1).astype(t.dtype)


def _rope(t, positions, theta: float, fraction: float = 1.0, yarn=None):
    """:func:`_rotate_half` of ``t`` (mb, S, heads, hd) on the first
    ``fraction`` of each head at :func:`_rope_angles`: the rotation as the
    tests and the references read it."""
    return _rotate_half(t, *_rope_angles(
        positions, int(t.shape[-1] * fraction), theta, yarn))


def _rope_streams(t, positions, theta: float, sections):
    """:func:`_rotate_half` over the whole head at :func:`_stream_angles`."""
    return _rotate_half(t, *_stream_angles(
        positions, t.shape[-1], theta, sections))


def _position(q, k, cos, sin, scales=(), eps: float = 0.0):
    """q and k's position prologue, the one statement of its mathematics:
    each head normalised by ``scales`` — (q's, k's) of a ``head_qk_norm``,
    or () — then rotated (:func:`_rotate_half`).  What the kernel
    ``ops/qk_position.py`` is tested against, and what runs where it does
    not: off a TPU, and at shapes it does not fit."""
    if scales:
        q, k = (_rmsnorm(t, scale, eps) for t, scale in zip((q, k), scales))
    return _rotate_half(q, cos, sin), _rotate_half(k, cos, sin)


def _position_pullback(q, k, cos, sin, scales, eps: float, gq, gk):
    """:func:`_position`'s pullback of (gq, gk) in the same form: the
    transpose of a rotation is the rotation by the opposite angle, and the
    norm's is AD's.  (dq, dk, the scales' gradients or ())."""
    gq, gk = _rotate_half(gq, cos, -sin), _rotate_half(gk, cos, -sin)
    if not scales:
        return gq, gk, ()
    pulled = [jax.vjp(functools.partial(_rmsnorm, eps=eps), t, scale)[1](g)
              for t, scale, g in zip((q, k), scales, (gq, gk))]
    return pulled[0][0], pulled[1][0], (pulled[0][1], pulled[1][1])


def _for_tpu_or(kernel, xla, *operands):
    """``kernel(*operands)`` where the program is lowered for a TPU,
    ``xla(*operands)`` elsewhere: on a TPU host the kernel is traced alone,
    off one ``lax.platform_dependent`` decides as the program is lowered
    (``parallel/moe.py`` ``_token_sums``), so the CPU suite runs no Pallas
    interpreter and a step lowered for a described TPU holds the kernel."""
    if jax.default_backend() == "tpu":
        return kernel(*operands)
    return lax.platform_dependent(*operands, tpu=kernel, default=xla)


def _rows(t):
    """(mb, S, heads, hd) as the (mb, S, heads * hd) rows it is; None as
    None."""
    return t if t is None else t.reshape(t.shape[:2] + (-1,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _qk_position(q, k, cos, sin, scales, half: int, eps: float, block: int):
    """:func:`_position` as the kernel ``hvd_qk_position``: q and k go as
    the (mb, S, heads * hd) rows they are, one call for both, ``block``
    positions a program (``ops/qk_position.block``); ``cos`` and ``sin``
    come a lane (``ops/qk_position.lanes``), the first ``half`` of them the
    frequencies' own.  The backward is one call too, and saves nothing the
    layer's checkpoint would keep: q, k and the angles are recomputed with
    the block."""
    return _qk_position_fwd(q, k, cos, sin, scales, half, eps, block)[0]


def _qk_position_fwd(q, k, cos, sin, scales, half, eps, block):
    hd = q.shape[-1]

    def kernel(q, k, cos, sin, scales):
        out = qk_position.forward(
            _rows(q), _rows(k), qk_position.tables(cos, sin, hd, half),
            scales, head_dim=hd, half=half, eps=eps, block=block)
        return tuple(o.reshape(t.shape) for o, t in zip(out, (q, k)))

    def xla(q, k, cos, sin, scales):
        return _position(q, k, cos[..., :half], sin[..., :half], scales, eps)

    out = _for_tpu_or(kernel, xla, q, k, cos, sin, scales)
    # The rotation's transpose reads neither q nor k.
    return out, ((q, k) if scales else (None, None), cos, sin, scales)


def _qk_position_bwd(half, eps, block, saved, g):
    (q, k), cos, sin, scales = saved
    hd = g[0].shape[-1]

    def kernel(q, k, cos, sin, scales, gq, gk):
        dq, dk, sums = qk_position.backward(
            _rows(q), _rows(k), qk_position.tables(cos, sin, hd, half),
            scales, _rows(gq), _rows(gk), head_dim=hd, half=half, eps=eps,
            block=block)
        with scope("attn_qknorm"):      # the programs' partial sums
            sums = tuple(
                t.reshape(-1, hd).sum(axis=0).astype(scale.dtype)
                for t, scale in zip(sums, scales))
        return dq.reshape(gq.shape), dk.reshape(gk.shape), sums

    def xla(q, k, cos, sin, scales, gq, gk):
        return _position_pullback(q, k, cos[..., :half], sin[..., :half],
                                  scales, eps, gq, gk)

    dq, dk, dscales = _for_tpu_or(kernel, xla, q, k, cos, sin, scales, *g)
    return dq, dk, None, None, dscales


_qk_position.defvjp(_qk_position_fwd, _qk_position_bwd)


def _position_heads(site: str, q, k, angles, half: int, scales=(),
                    eps: float = 0.0):
    """:func:`_position` of q (mb, S, heads, hd) and k (mb, S, kv heads, hd)
    as the shapes allow: the kernel, the norm inside it, where they fit it
    (``ops/qk_position.block``: heads that tile 128 lanes, positions a
    multiple of a block); for anything else the jnp form, the norm under
    its own scope.  ``angles(lanes=None)``: (cos, sin) of the
    ``half`` frequencies, or of those ``lanes`` names
    (:func:`_rope_angles`, :func:`_stream_angles`).  ``site`` labels the
    trace-time counter ``hvd_qk_position_built_total{site, form}``."""
    hd = q.shape[-1]
    block = qk_position.block(q.shape[1], q.shape[2] * hd, k.shape[2] * hd,
                              hd, q.dtype.itemsize)
    if scales and block is None:
        with scope("attn_qknorm"):
            q, k = (_rmsnorm(t, scale, eps)
                    for t, scale in zip((q, k), scales))
        scales = ()
    registry().counter(
        "hvd_qk_position_built_total",
        "position prologues of q and k traced, by site and by form: the "
        "kernel hvd_qk_position, or XLA's slices and concatenate",
        site=site, form="xla" if block is None else "kernel").inc()
    with scope("attn_rope"):
        if block is None:
            return _position(q, k, *angles())
        return _qk_position(q, k, *angles(qk_position.lanes(hd, half)),
                            tuple(scales), half, eps, block)


def _position_qk(cfg: TransformerConfig, lp, q, k, positions, axis_name):
    """What the configuration does to q and k between the projection and
    the attention: nothing (learned positions were added to the stream),
    or OLMoE's QK-norm — over every feature of a row, XLA's — and rotary
    positions (:func:`_position_heads`)."""
    if cfg.qk_norm:
        with scope("attn_qknorm"):
            q = _qk_norm(q, lp["q_norm"], cfg.norm_eps, axis_name)
            k = _qk_norm(k, lp["k_norm"], cfg.norm_eps, axis_name)
    if cfg.rope_theta is not None:
        hd = q.shape[-1]
        q, k = _position_heads("layer", q, k, functools.partial(
            _rope_angles, positions, hd, cfg.rope_theta, None), hd // 2)
    return q, k


def _ring_scope(form: str):
    """The scope of one form of ``parallel/tensor_parallel.py``'s ring —
    where there is a ring: one ``mp`` member's calls are the plain
    collectives', and named as they were."""
    return (scope(form) if axis_size("mp") > 1
            else contextlib.nullcontext())


def _attention_block(cfg: TransformerConfig, lp: Dict[str, jax.Array],
                     x: jax.Array) -> jax.Array:
    """x: (mb, s_local, d) sequence-sharded over mp. Returns residual add."""
    h_heads, hd = cfg.n_heads, cfg.head_dim
    hnorm = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
    # wqkv is stored (d, h*3*hd) with heads outermost in the fused dim, so an
    # mp shard of the fused dim is a whole-head slice (q,k,v interleaved
    # per head), making column-parallel == head-parallel; the step multiplies
    # by its [q | k | v] reordering, three (d, heads*hd) slabs.
    slabs = tp.qkv_slabs(lp["wqkv"].astype(x.dtype), hd)
    if cfg.attn_mode == "megatron":
        # gather sequence → heads-sharded attention → scatter sequence back.
        with _ring_scope(GATHER_RING):            # each (mb, S, heads/mp * hd)
            qkv = tp.gather_column_parallel(hnorm, slabs, "mp")
        q, k, v = (t.reshape(t.shape[:2] + (-1, hd)) for t in qkv)
        # The sequence was gathered: positions 0 .. S-1; heads over mp.
        q, k = _position_qk(cfg, lp, q, k, jnp.arange(q.shape[1]), "mp")
        o = ra.full_attention(q, k, v, causal=True)
        o = o.reshape(o.shape[:2] + (-1,))           # (mb, S, heads/mp * hd)
        with _ring_scope(SCATTER_RING):
            return tp.row_parallel(o, lp["wo"], "mp", scatter_sequence=True)
    else:  # ring/ulysses: sequence stays sharded through attention
        mb, s_local = hnorm.shape[:2]
        q, k, v = (tp.column_parallel(hnorm, w).reshape(
            mb, s_local, h_heads, hd) for w in slabs)
        # This member's chunk of the sequence, every head of it.
        q, k = _position_qk(
            cfg, lp, q, k,
            lax.axis_index("mp") * s_local + jnp.arange(s_local), None)
        if cfg.attn_mode == "ulysses":
            from ..parallel.ulysses import ulysses_attention
            o = ulysses_attention(q, k, v, axis_name="mp", causal=True)
        else:
            o = ra.ring_attention(q, k, v, axis_name="mp", causal=True)
        o = o.reshape(mb, s_local, h_heads * hd)
        return jnp.einsum("bse,ed->bsd", o, lp["wo"].astype(x.dtype))


def _expert_activation(cfg: TransformerConfig):
    return _ACTIVATIONS[cfg.expert_activation or
                        ("silu" if cfg.gated_experts else "gelu")]


def _route_experts(cfg: TransformerConfig, lp: Dict[str, jax.Array],
                   tok: jax.Array, router_x: Optional[jax.Array] = None):
    """``moe.dropless_moe`` as the configuration spells it: the router's
    scoring, the experts held and their activation.  ``tok``: (T, width)."""
    return moe_lib.dropless_moe(
        moe_lib.GatedMoEParams(
            gate=lp["gate"], w_gate=lp.get("w_gate"), w_up=lp["w_up"],
            w_down=lp["w_down"], bias=lp.get("router_bias")),
        tok, cfg.top_k, activation=_expert_activation(cfg),
        router=moe_lib.Router(cfg.router_scoring, cfg.router_renormalise,
                              cfg.router_scale, cfg.router_renorm_eps),
        router_x=router_x, buffer_factor=cfg.expert_buffer_factor)


def _mlp_block(cfg: TransformerConfig, lp: Dict[str, jax.Array],
               x: jax.Array):
    """(The residual add of the MLP, the layer's ``moe.RouterStats`` where
    a dropless MoE routes and None elsewhere)."""
    hnorm = _rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if _routes_dropless(cfg):
        mb, s_local, d = hnorm.shape
        y, stats = _route_experts(cfg, lp, hnorm.reshape(mb * s_local, d))
        return y.reshape(mb, s_local, d), stats
    if cfg.n_experts > 0:
        mb, s_local, d = hnorm.shape
        tok = hnorm.reshape(mb * s_local, d)
        mp_params = moe_lib.MoEParams(
            gate=lp["gate"].astype(jnp.float32),
            w_in=lp["w_in"],    # (E_local, d, ff) after dp sharding
            w_out=lp["w_out"],
        )
        y = moe_lib.moe_layer(mp_params, tok, "dp",
                              capacity_factor=cfg.capacity_factor,
                              top_k=cfg.top_k)
        return y.reshape(mb, s_local, d).astype(x.dtype), None
    with _ring_scope(GATHER_RING):   # tokenwise from here: rows in ring order
        u = tp.gather_column_parallel_ring(hnorm, lp["w1"], "mp")
    u = jax.tree_util.tree_map(jax.nn.gelu, u)
    with _ring_scope(SCATTER_RING):
        return tp.row_parallel(u, lp["w2"], "mp", scatter_sequence=True), None


def _init_ssm(cfg: TransformerConfig, new) -> Dict[str, jax.Array]:
    """What a state-space scan's behaviour hangs on, by Mamba-2's published
    scheme: ``dt_bias`` the inverse softplus of a log-uniform draw in
    ``ssm_dt_range``, ``a_log = log U(1, 16)``, ``d_skip`` 1, the depthwise
    conv U(+-1 / sqrt(taps)) as ``nn.Conv1d`` draws it."""
    d, h, hp = cfg.d_model, cfg.ssm_heads, cfg.ssm_heads * cfg.ssm_head_dim
    conv = hp + 2 * cfg.ssm_groups * cfg.ssm_state
    dt_min, dt_max, dt_floor = cfg.ssm_dt_range
    dt = jnp.maximum(jnp.exp(new.uniform(h, lo=math.log(dt_min),
                                         hi=math.log(dt_max))), dt_floor)
    bound = 1.0 / math.sqrt(cfg.ssm_conv)
    return {
        "ln": new.ones(d), "d_skip": new.ones(h), "norm": new.ones(hp),
        # columns [z | x | B | C | dt]: z and x head-major (H, P), B and C
        # group-major (G, N), dt a head.
        "w_in": new.rand(d, hp + conv + h),
        "conv_w": new.uniform(conv, cfg.ssm_conv, lo=-bound, hi=bound),
        "conv_b": new.uniform(conv, lo=-bound, hi=bound),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "a_log": jnp.log(new.uniform(h, lo=1.0, hi=16.0)),
        "w_out": new.rand(hp, d, scale=new.out_scale),
    }


def _ssm_mixer(cfg: TransformerConfig, lp: Dict[str, jax.Array],
               x: jax.Array) -> jax.Array:
    """A Mamba-2 mixer (ops/ssd.py) on the normed stream.  x: (mb, S, d)."""
    mb, s, _ = x.shape
    h, p, g, n = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    hp, gn = h * p, g * n
    hnorm = _rmsnorm(x, lp["ln"], cfg.norm_eps)
    proj = jnp.einsum("bsd,de->bse", hnorm, lp["w_in"].astype(x.dtype))
    z, xbc, dt = jnp.split(proj, [hp, 2 * hp + 2 * gn], axis=-1)
    with scope("ssm_conv"):
        xbc = ssd.causal_conv1d(xbc, lp["conv_w"], lp["conv_b"])
        xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(x.dtype)
    xs, b, c = jnp.split(xbc, [hp, hp + gn], axis=-1)
    with scope("ssm_scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        y = ssd.ssd_scan(xs.reshape(mb, s, h, p), dt, -jnp.exp(lp["a_log"]),
                         b.reshape(mb, s, g, n), c.reshape(mb, s, g, n),
                         lp["d_skip"], cfg.ssm_chunk)
    y = ssd.gated_group_rmsnorm(y.reshape(mb, s, hp), z, lp["norm"], g,
                                cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, lp["w_out"].astype(x.dtype))


def _ssm_flops(cfg: TransformerConfig) -> float:
    """The projections, the conv, the scan as the chunked algorithm's four
    products."""
    d, h, p, g, n, q = (cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim,
                        cfg.ssm_groups, cfg.ssm_state, cfg.ssm_chunk)
    proj = 2.0 * d * (2 * h * p + 2 * g * n + h) + 2.0 * h * p * d
    conv = 2.0 * cfg.ssm_conv * (h * p + 2 * g * n)
    scan = 2.0 * q * n * g + 2.0 * q * p * h + 4.0 * p * n * h
    return proj + conv + scan


class _Attention(NamedTuple):
    """What separates one kind of block over ``_gqa_mixer`` from another."""
    heads: int                    # query heads, over the same n_kv_heads
    window: Optional[int]         # keys a query sees, its own included
    theta: Optional[float]        # rotary base; None: no position encoding
    fraction: float = 1.0         # the share of each head that rotates
    yarn: Optional[Tuple[float, int, float, float, float]] = None
    # Frequencies a position stream (``_stream_angles``); None: one stream.
    sections: Optional[Tuple[int, ...]] = None
    # (heads, head width, keys a query) of a learned indexer; None: none.
    index: Optional[Tuple[int, int, int]] = None


def _init_gqa(cfg: TransformerConfig, new, kind: str) -> Dict[str, jax.Array]:
    d, hq = cfg.d_model, _row(kind).attention(cfg).heads
    hkv, hd = cfg.n_kv_heads or cfg.n_heads, cfg.head_dim
    blk = {
        "ln": new.ones(d), "wq": new.rand(d, hq * hd),
        "wk": new.rand(d, hkv * hd), "wv": new.rand(d, hkv * hd),
        "wo": new.rand(hq * hd, d, scale=new.out_scale),
    }
    if cfg.attn_gate:
        blk["w_head_gate"] = new.rand(d, hq)
    if cfg.head_qk_norm:
        blk["q_norm"], blk["k_norm"] = new.ones(hd), new.ones(hd)
    index = _row(kind).attention(cfg).index
    if index is not None:
        j, di, _ = index
        blk.update(
            index_wq=new.rand(d, j * di), index_wk=new.rand(d, di),
            index_ww=new.rand(d, j), index_k_norm=new.ones(di),
            index_k_bias=0.0 * new.ones(di))
    return blk


def _layernorm(x, scale, bias, eps: float):
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def _index(cfg: TransformerConfig, lp, hnorm, positions, a: "_Attention"):
    """A learned indexer's operands from the block's normed input, held
    constant (its leaves learn from their own loss alone): ``qi`` (mb, S,
    J, Di), the one key head ``ki`` (mb, S, Di) under a LayerNorm, both
    rotated as q and k are with the sections scaled to their head, and the
    weight a query and head ``w`` (mb, S, J) fp32, the scale J^-1/2 Di^-1/2
    folded in (DeepSeek-V3.2-Exp's lightning indexer)."""
    j, di, _ = a.index
    mb, s, _ = hnorm.shape
    hnorm = lax.stop_gradient(hnorm)

    def project(name):
        return jnp.einsum("bsd,de->bse", hnorm, lp[name].astype(hnorm.dtype))

    qi = project("index_wq").reshape(mb, s, j, di)
    ki = _layernorm(project("index_wk"), lp["index_k_norm"],
                    lp["index_k_bias"], cfg.norm_eps)
    sections = [n * di // cfg.head_dim for n in a.sections]
    qi, ki = _position_heads(
        "index", qi, ki[:, :, None], functools.partial(
            _stream_angles, positions, di, a.theta, sections), di // 2)
    ki = ki[:, :, 0]
    w = project("index_ww").astype(jnp.float32) * (j * di) ** -0.5
    return qi, ki, w


def _gqa_mixer(cfg: TransformerConfig, lp: Dict[str, jax.Array],
               x: jax.Array, kind: str = "attn", positions=None):
    """Causal attention with ``n_kv_heads`` key / value heads, query head i
    reading head i // (heads / n_kv_heads); the row of ``kind`` says how
    many query heads, how many keys a query sees, and q and k's rotation
    (``_Attention``; "attn" a "*" block, "swa" a "W" block).  Positions are
    0 .. S-1, or with ``diffusion_block`` 0 .. S/2-1 twice (the noised copy
    and the clean copy of the same tokens), and the mask is then the
    block-diffusion one: a noised query sees its own noised block and the
    clean blocks before it, a clean query the clean blocks up to its own.
    ``head_qk_norm`` normalises each head of q and of k (one scale vector of
    ``head_dim`` each, shared by the heads) before the rotation; the two are
    q and k's position prologue, :func:`_position_heads`: one pass of the
    kernel ``hvd_qk_position`` over the rows the projections wrote, which the
    flash kernels (q) and the repeat (k) read as they are, where the shapes
    fit it and the step is lowered for a TPU, the jnp form elsewhere.
    ``attn_gate`` multiplies head i's output by ``sigmoid(h Wg)_i``, a scalar
    a head and token from the block's normed input.  Each K / V head is
    repeated across its query heads before the kernels (their index maps
    taking several query heads a K / V block is ROADMAP M4).

    A variant with ``sections`` rotates by ``positions`` (mb, streams, S), a
    batch array (:func:`_stream_angles`); causality stays the index in the
    sequence.  A variant with an ``index`` (an "S" block) returns ``(y, the
    indexer's loss)``: the indexer ranks every query's causal keys
    (``ops/sparse_index.select``, exact), the attention runs over the
    chosen ones (``ring_attention.selected_attention``), and the loss is
    ``ops/sparse_index.index_loss`` on the attention's own operands and
    saved ``lse``, all held constant: no gradient passes between the two."""
    mb, s, _ = x.shape
    a = _row(kind).attention(cfg)
    hq, hkv, hd = a.heads, cfg.n_kv_heads or cfg.n_heads, cfg.head_dim
    hnorm = _rmsnorm(x, lp["ln"], cfg.norm_eps)

    def heads(w, n):
        return jnp.einsum("bsd,de->bse", hnorm,
                          w.astype(x.dtype)).reshape(mb, s, n, hd)

    q, k, v = heads(lp["wq"], hq), heads(lp["wk"], hkv), heads(lp["wv"], hkv)
    scales = (lp["q_norm"], lp["k_norm"]) if cfg.head_qk_norm else ()
    if a.sections is not None:
        if positions is None:
            raise ValueError(
                f"rope_sections {a.sections}: the batch brings the position "
                "streams as its third array, (B, streams, S)")
        rot, angles = hd, functools.partial(
            _stream_angles, positions, hd, a.theta, a.sections)
    elif a.theta is not None:
        at = jnp.arange(s)
        rot = int(hd * a.fraction)
        angles = functools.partial(
            _rope_angles,
            at if cfg.diffusion_block is None else at % (s // 2),
            rot, a.theta, a.yarn)
    else:
        angles = None
    if angles is not None:
        q, k = _position_heads("gqa", q, k, angles, rot // 2, scales,
                               cfg.norm_eps)
    elif scales:
        with scope("attn_qknorm"):
            q, k = (_rmsnorm(t, scale, cfg.norm_eps)
                    for t, scale in zip((q, k), scales))
    k_own = k
    if hkv != hq:
        with scope(ATTN_OPERAND_SCOPES[0]):
            k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))
    if a.index is None:
        o = ra.full_attention(q, k, v, causal=True, window=a.window,
                              diffusion_block=cfg.diffusion_block)
    else:
        with scope(INDEX_SCOPES[0]):
            qi, ki, w = _index(cfg, lp, hnorm, positions, a)
            visible_t = si.select(qi, ki, w, a.index[2])
        o, lse = ra.selected_attention(q, k, v, visible_t)
        index_loss = si.index_loss(
            qi, ki, w, *(lax.stop_gradient(t) for t in (q, k_own, lse)),
            visible_t, hd ** -0.5)
    if cfg.attn_gate:
        with scope("attn_gate"):
            gate = jax.nn.sigmoid(jnp.einsum(
                "bsd,dh->bsh", hnorm, lp["w_head_gate"].astype(x.dtype)
            ).astype(jnp.float32))
            o = (o.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    y = jnp.einsum("bse,ed->bsd", o.reshape(mb, s, hq * hd),
                   lp["wo"].astype(x.dtype))
    return y if a.index is None else (y, index_loss)


def _gqa_flops(cfg: TransformerConfig, kind: str) -> float:
    """The projections, the gate and the scores, by the (query, key) pairs
    a query: the causal half, or the band's window S - window (window - 1)
    / 2 pairs a sequence, or under the block-diffusion mask S^2 + S block
    pairs over 2 S positions, or the chosen keys of a learned selection
    (every causal key of a query's first ``topk``, then ``topk``), whose
    indexer adds its projections and its scores over the causal half, once:
    the pass its loss makes over the main scores is not model work."""
    a, d, s = _row(kind).attention(cfg), cfg.d_model, cfg.seq_len
    hq, hkv, hd = a.heads, cfg.n_kv_heads or cfg.n_heads, cfg.head_dim
    w = min(a.window, s) if a.window else 0
    pairs = w - w * (w - 1) / (2.0 * s) if w else s / 2.0
    if cfg.diffusion_block is not None:
        pairs = (s + cfg.diffusion_block) / 2.0
    gate = 2.0 * d * hq if cfg.attn_gate else 0.0
    index = 0.0
    if a.index is not None:
        j, di, topk = a.index
        k = min(topk, s)
        pairs = (k * (k + 1) / 2.0 + (s - k) * k) / s
        index = 2.0 * d * (j * di + di + j) + 2.0 * j * di * (s + 1) / 2.0
    return (2.0 * d * hd * (2 * hq + 2 * hkv) + gate + index
            + 4.0 * pairs * hq * hd)


def _init_dense(cfg: TransformerConfig, new) -> Dict[str, jax.Array]:
    d, ff = cfg.d_model, cfg.dense_ff
    return {"ln": new.ones(d), "w_gate": new.rand(d, ff),
            "w_up": new.rand(d, ff),
            "w_down": new.rand(ff, d, scale=new.out_scale)}


def _dense_mixer(cfg: TransformerConfig, lp: Dict[str, jax.Array],
                 x: jax.Array) -> jax.Array:
    """A "D" block: the gated MLP ``(silu(h W1) * h W3) W2`` at
    ``dense_ff`` on the normed stream, silu and the product in fp32."""
    hnorm = _rmsnorm(x, lp["ln"], cfg.norm_eps)
    with scope(DENSE_MLP_SCOPE):
        def up(w):
            return jnp.einsum("bsd,df->bsf", hnorm,
                              w.astype(x.dtype)).astype(jnp.float32)
        hidden = jax.nn.silu(up(lp["w_gate"])) * up(lp["w_up"])
        return jnp.einsum("bsf,fd->bsd", hidden.astype(x.dtype),
                          lp["w_down"].astype(x.dtype))


def _init_conv(cfg: TransformerConfig, new) -> Dict[str, jax.Array]:
    """``w_in``'s columns [B | C | u], d_model each; the depthwise conv
    U(+-1 / sqrt(taps)) as ``nn.Conv1d`` draws it."""
    d, bound = cfg.d_model, 1.0 / math.sqrt(cfg.conv_taps)
    return {"ln": new.ones(d), "w_in": new.rand(d, 3 * d),
            "conv_w": new.uniform(d, cfg.conv_taps, lo=-bound, hi=bound),
            "w_out": new.rand(d, d, scale=new.out_scale)}


def _conv_mixer(cfg: TransformerConfig, lp: Dict[str, jax.Array],
                x: jax.Array) -> jax.Array:
    """A "C" block, LFM2's gated short convolution, on the normed stream:
    ``[B, C, u] = h W_in`` (three times ``d_model`` columns), ``c =
    conv(B * u)`` — depthwise and causal over ``conv_taps`` positions
    (ops/ssd.py ``gated_causal_conv1d``), position t reading t - taps + 1 .. t,
    no activation — and ``(C * c) W_out``.  Both gates and the convolution
    are evaluated in fp32 between the two matmuls and rounded once, under
    ``hvd_conv_gate``; ``B * u`` is taken tap by tap from the shifted
    factors and never written out.  x: (mb, S, d)."""
    hnorm = _rmsnorm(x, lp["ln"], cfg.norm_eps)
    proj = jnp.einsum("bsd,de->bse", hnorm, lp["w_in"].astype(x.dtype))
    with scope(CONV_SCOPES[1]):
        b, c, u = jnp.split(proj, 3, axis=-1)
        conv = ssd.gated_causal_conv1d(b, u, lp["conv_w"])
        gated = (c.astype(jnp.float32) * conv).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", gated, lp["w_out"].astype(x.dtype))


def _init_experts(cfg: TransformerConfig, new) -> Dict[str, jax.Array]:
    d, e, held, ff = cfg.d_model, cfg.n_experts, _experts_held(cfg), cfg.d_ff
    width = cfg.moe_latent or d
    blk = {"ln": new.ones(d), "gate": new.rand(d, e)}
    if cfg.router_scoring == "sigmoid":
        # The choice's correction bias: a buffer, zero until a trainer's
        # balancing rule moves it; outside the gradient.
        blk["router_bias"] = 0.0 * new.ones(e)
    if cfg.moe_latent:
        blk["w_latent_in"] = new.rand(d, width)
        blk["w_latent_out"] = new.rand(width, d, scale=new.out_scale)
    if cfg.gated_experts:
        blk["w_gate"] = new.rand(held, width, ff)
    blk["w_up"] = new.rand(held, width, ff)
    blk["w_down"] = new.rand(
        held, ff, width, scale=new.std if cfg.moe_latent else new.out_scale)
    if cfg.shared_expert_ff:
        if cfg.gated_experts:
            blk["shared_gate"] = new.rand(d, cfg.shared_expert_ff)
        blk["shared_up"] = new.rand(d, cfg.shared_expert_ff)
        blk["shared_down"] = new.rand(cfg.shared_expert_ff, d,
                                      scale=new.out_scale)
    return blk


def _expert_mixer(cfg: TransformerConfig, lp: Dict[str, jax.Array],
                  x: jax.Array, before: Optional[jax.Array] = None):
    """(An "E" block's output, its ``moe.RouterStats``): the routed experts
    on the normed stream, or on its projection into ``moe_latent`` features
    with the router still reading the stream (LatentMoE), plus the shared
    expert on the stream: ``act(h V1) V2``, or with ``gated_experts``
    ``(act(h Vg) * h V1) V2`` as the routed experts are.  ``before``
    (``router_before_attention``): the stream as the block before this one
    received it, which the router then reads as it is, unnormed."""
    mb, s, d = x.shape
    tok = _rmsnorm(x, lp["ln"], cfg.norm_eps).reshape(mb * s, d)
    router_x = (before.reshape(mb * s, d) if before is not None
                else tok if cfg.moe_latent else None)
    if cfg.moe_latent:
        with scope("moe_latent"):
            latent = jnp.dot(tok, lp["w_latent_in"].astype(x.dtype))
        y, stats = _route_experts(cfg, lp, latent, router_x=router_x)
        with scope("moe_latent"):
            y = jnp.dot(y, lp["w_latent_out"].astype(x.dtype))
    else:
        y, stats = _route_experts(cfg, lp, tok, router_x=router_x)
    if cfg.shared_expert_ff:
        with scope("moe_shared"):
            def up(w):
                return jnp.dot(tok, w.astype(x.dtype)).astype(jnp.float32)
            hidden = _expert_activation(cfg)(
                up(lp["shared_gate" if cfg.gated_experts else "shared_up"]))
            if cfg.gated_experts:
                hidden = hidden * up(lp["shared_up"])
            y = y + jnp.dot(hidden.astype(x.dtype),
                            lp["shared_down"].astype(x.dtype))
    return y.reshape(mb, s, d), stats


def _expert_flops(cfg: TransformerConfig) -> float:
    """As this device computes it: the router, the latent projections, the
    share of the routed experts it holds, the shared expert."""
    d, width = cfg.d_model, cfg.moe_latent or cfg.d_model
    mats = 3.0 if cfg.gated_experts else 2.0
    routed = (cfg.top_k * _experts_held(cfg) / cfg.n_experts
              * mats * 2.0 * width * cfg.d_ff)
    latent = 4.0 * d * width if cfg.moe_latent else 0.0
    return (2.0 * d * cfg.n_experts + latent + routed
            + mats * 2.0 * d * cfg.shared_expert_ff)


class BlockKind(NamedTuple):
    """One kind of block a ``layer_pattern`` names, a row of ``BLOCKS``: a
    block is ``x + mixer(cfg, its leaves, x)``, the mixer norming its input
    (where ``before(cfg)``: ``mixer(cfg, its leaves, x, x as the block before
    it received it)``), and all this file knows of a kind is its row and
    what the row names."""
    key: str                  # its leaves' key under ``layers``
    scope: str                # the step scope its blocks run under
    fields: Tuple[str, ...]   # the TransformerConfig fields of its own
    asks: Callable            # (cfg, has it such blocks) -> a refusal or None
    init: Callable            # (cfg, new) -> leaves: ``_init_pattern_layers``
    mixer: Callable           # (cfg, leaves, x) -> y
    flops: Callable           # (cfg) -> forward matmul-FLOPs a token
    routes: bool = False      # y is (y, moe.RouterStats): it cannot lead
    selects: bool = False     # y is (y, its indexer's loss): nor can it
    crosses: str = ""         # what of it a ``diffusion_block`` refuses
    attention: Optional[Callable] = None      # (cfg) -> _Attention
    # (cfg) -> whether the mixer also takes the block before's input
    before: Callable = lambda cfg: False


def _attention_kind(key: str, fields: Tuple[str, ...], variant,
                    needs=lambda cfg: None, crosses: str = "",
                    selects: bool = False) -> BlockKind:
    """A row over ``_gqa_mixer``: ``variant(cfg)`` the ``_Attention`` that
    separates it from the others, ``fields`` and ``needs(cfg)`` its own."""
    def asks(cfg, here):
        a, hkv = variant(cfg), cfg.n_kv_heads or cfg.n_heads
        if here and a.heads % hkv:
            return (f"n_heads {cfg.n_heads} and window_heads "
                    f"{cfg.window_heads} are not multiples of n_kv_heads "
                    f"{cfg.n_kv_heads}")
        if here and (not 0.0 < a.fraction <= 1.0
                     or cfg.head_dim * a.fraction % 2):
            return (f"rope_fraction {a.fraction} of a head of "
                    f"{cfg.head_dim} is not a whole even share")
        return here and needs(cfg)

    return BlockKind(
        key, "attn", fields + ("n_kv_heads", "attn_gate", "head_qk_norm"),
        asks, *(functools.partial(f, kind=key)
                for f in (_init_gqa, _gqa_mixer, _gqa_flops)),
        crosses=crosses, attention=variant, selects=selects)


# The kinds of block by their letter in a ``layer_pattern``.  A new kind is
# its fields on ``TransformerConfig``, its mixer and a row here.
BLOCKS: Dict[str, BlockKind] = {
    "M": BlockKind(
        "ssm", "ssm",
        tuple(f for f in TransformerConfig._fields if f.startswith("ssm_")),
        lambda cfg, here: here and (
            cfg.ssm_heads < 1 or cfg.ssm_heads % cfg.ssm_groups) and
        f"ssm_heads {cfg.ssm_heads} do not divide into ssm_groups "
        f"{cfg.ssm_groups}",
        _init_ssm, _ssm_mixer, _ssm_flops,
        crosses='a state-space scan ("M")'),
    "E": BlockKind(
        "moe", "mlp",
        ("moe_latent", "shared_expert_ff", "router_before_attention"),
        lambda cfg, here: here != _routes_dropless(cfg) and 'an "E" block '
        "is a dropless expert MLP: n_experts and dropless go with it",
        _init_experts, _expert_mixer, _expert_flops, routes=True,
        before=lambda cfg: cfg.router_before_attention),
    "*": _attention_kind(
        "attn", ("rope_fraction", "rope_yarn"),
        lambda cfg: _Attention(cfg.n_heads, None, cfg.rope_theta,
                               cfg.rope_fraction, cfg.rope_yarn)),
    "W": _attention_kind(
        "swa", ("attn_window", "window_heads", "window_rope_theta"),
        lambda cfg: _Attention(cfg.window_heads or cfg.n_heads,
                               cfg.attn_window, cfg.window_rope_theta),
        lambda cfg: not cfg.attn_window and
        'a "W" block is sliding-window attention: attn_window goes with it',
        crosses='a sliding window ("W", attn_window)'),
    "S": _attention_kind(
        "sel", ("index_heads", "index_head_dim", "index_topk",
                "index_loss_coef", "rope_sections"),
        lambda cfg: _Attention(
            cfg.n_heads, None, cfg.rope_theta, sections=cfg.rope_sections,
            index=(cfg.index_heads, cfg.index_head_dim, cfg.index_topk)),
        lambda cfg: (
            min(cfg.index_heads, cfg.index_topk) < 1 or cfg.rope_theta is None
            or sum(cfg.rope_sections or ()) * 2 != cfg.head_dim
            or any(n * cfg.index_head_dim % cfg.head_dim
                   for n in cfg.rope_sections)) and
        'an "S" block is attention over the index_topk keys an indexer of '
        "index_heads heads ranks highest, rotated at rope_theta by "
        "rope_sections: frequencies a position stream, half of head_dim "
        "together, that scale whole to index_head_dim",
        crosses='a learned selection ("S", index_topk)', selects=True),
    "D": BlockKind(
        "dense", "mlp", ("dense_ff",),
        lambda cfg, here: here and not cfg.dense_ff and
        'a "D" block is a gated dense MLP: dense_ff goes with it',
        _init_dense, _dense_mixer,
        lambda cfg: 6.0 * cfg.d_model * cfg.dense_ff),
    "C": BlockKind(
        "conv", CONV_SCOPES[0], ("conv_taps",),
        lambda cfg, here: here and cfg.conv_taps < 1 and
        'a "C" block is a gated short convolution: conv_taps goes with it',
        _init_conv, _conv_mixer,
        lambda cfg: (8.0 * cfg.d_model       # two projections, and the taps
                     + 2.0 * cfg.conv_taps) * cfg.d_model,
        crosses='a convolution ("C")'),
}
# Letter -> (the key of the kind's leaves, the scope of its blocks).
BLOCK_KINDS = {c: (row.key, row.scope) for c, row in BLOCKS.items()}


def _make_pattern_stage_fn(cfg: TransformerConfig, positions=None):
    """stage_fn(stage_params, act) for a ``layer_pattern``: the blocks of
    ``leading_pattern`` once, then a scan over the periods, inside one
    period its blocks in the pattern's order, each under its step scope and
    (``cfg.remat``) its own checkpoint, which keeps an attention block's
    flash forward output and lse.  A block whose row says ``before`` is
    also handed the input of the block before it: that block's checkpoint
    input already, so nothing more is saved, and its cotangent joins the
    stream's there.  Returns the activation and, where the pattern has
    blocks that route, their ``moe.RouterStats`` stacked (periods, blocks a
    period, ...); where it has blocks that select, a dict of those under
    ``"router"`` and the indexers' losses (periods, blocks a period) under
    ``"index"``.
    ``positions``: the batch's position streams (mb, streams, S), handed to
    the attention kinds where the batch brings them."""
    def block(row):
        more = ({"positions": positions}
                if positions is not None and row.attention else {})

        def run(act, lp, *before):
            with scope(row.scope):
                out = row.mixer(cfg, lp, act, *before, **more)
                y, stats = out if row.routes or row.selects else (out, None)
                return act + y, stats
        return ra.checkpoint_keeping_attention(run) if cfg.remat else run

    blocks = {c: block(row) for c, row in BLOCKS.items()}

    def run_blocks(pattern, act, params):
        side, before = {"router": [], "index": []}, None
        for i, c in enumerate(pattern):
            j = pattern[:i].count(c)          # which of its kind's blocks
            lp = jax.tree_util.tree_map(lambda a: a[j],
                                        params[BLOCKS[c].key])
            carried = (before,) if BLOCKS[c].before(cfg) else ()
            before = act
            act, st = blocks[c](act, lp, *carried)
            if st is not None:
                side["router" if BLOCKS[c].routes else "index"].append(st)
        return act, side

    def period_fn(act, period_params):
        act, side = run_blocks(cfg.layer_pattern, act, period_params)
        return act, {name: jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *found)
            for name, found in side.items() if found}

    def stage_fn(stage_params, act):
        with scope(LAYERS_SCOPE):
            if cfg.leading_pattern:
                stage_params = dict(stage_params)
                act, _ = run_blocks(cfg.leading_pattern, act,
                                    stage_params.pop("leading"))
            out, side = lax.scan(period_fn, act, stage_params)
        if set(side) == {"router"}:
            side = side["router"]
        return (out, side) if side else out

    return stage_fn


def _make_stage_fn(cfg: TransformerConfig, positions=None):
    """stage_fn(stage_params, act) scanning this stage's layers, each
    (``cfg.remat``) under one checkpoint that keeps the flash forward's
    output and lse; with a dropless MoE it returns the activation and the
    layers' stacked ``moe.RouterStats``."""
    if cfg.layer_pattern is not None:
        return _make_pattern_stage_fn(cfg, positions)
    with_stats = _routes_dropless(cfg)

    def layer_fn(act, lp):
        with scope("attn"):
            act = act + _attention_block(cfg, lp, act)
        with scope("mlp"):
            y, stats = _mlp_block(cfg, lp, act)
            act = act + y
        return act, stats

    def stage_fn(stage_params, act):
        body = layer_fn
        if cfg.remat:
            body = ra.checkpoint_keeping_attention(layer_fn)
        with scope(LAYERS_SCOPE):
            out, stats = lax.scan(body, act, stage_params)
        return (out, stats) if with_stats else out

    return stage_fn


def forward_loss(cfg: TransformerConfig, par: ParallelConfig,
                 params: Dict[str, Any], tokens: jax.Array,
                 labels: jax.Array, with_routing: bool = False,
                 weights: Optional[jax.Array] = None,
                 positions: Optional[jax.Array] = None):
    """Per-device loss body; call inside shard_map over mesh (dp, pp, mp).

    tokens/labels: (B_local, S) int32 shards (batch over dp).
    Returns a replicated scalar loss: the mean token cross-entropy, plus,
    with a dropless MoE, ``aux_loss_coef`` x the router's load-balancing
    loss and ``z_loss_coef`` x its z-loss, each taken over the global
    batch's tokens layer by layer and averaged over the layers.
    ``with_routing`` (dropless MoE only) returns ``(loss, routing)``, the
    replicated dict :func:`make_routing_fn` documents.

    With ``cfg.diffusion_block``: ``tokens`` (B_local, 2 S) — the noised
    copy of a sequence, then its clean copy — and ``labels`` and fp32
    ``weights`` (B_local, S).  All 2 S positions go through the stack; the
    final norm and the head run on the first S (the noised copy) alone, and
    the loss is ``sum(weights * -log softmax(logits)[labels])`` over the
    global batch's B x S positions, divided by B x S.

    With blocks of a learned selection ("S"): ``positions`` (B_local,
    streams, S) integers, the rotary position streams of every token
    (``rope_sections``), and the loss is the cross-entropy plus
    ``index_loss_coef`` x the sum over those blocks of their indexer's
    loss, each a mean over the global batch's tokens.  Causality and the
    selection go by a token's index in the sequence, not by these values.
    """
    _check_layout(cfg, par)
    s_full = cfg.seq_len
    mp_size = axis_size("mp")
    diffusion = cfg.diffusion_block is not None
    if diffusion != (weights is not None) or (
            diffusion and tokens.shape[1] != 2 * s_full):
        raise ValueError(
            "a diffusion_block configuration, and no other, takes tokens of "
            "2 x seq_len positions and, after labels, weights of seq_len: "
            f"got tokens {tokens.shape}, weights "
            f"{None if weights is None else weights.shape}")
    if _selects(cfg) != (positions is not None) or (
            positions is not None and par.n_microbatches != 1):
        raise ValueError(
            "a configuration with \"S\" blocks, and no other, takes the "
            "position streams (B, streams, seq_len) after labels, in one "
            f"microbatch: got positions "
            f"{None if positions is None else positions.shape}, "
            f"n_microbatches {par.n_microbatches}")
    s_local = tokens.shape[1] // mp_size
    mp_idx = lax.axis_index("mp")

    # Embedding (replicated weights; computed once per device, then the
    # sequence chunk for this mp member is sliced off → sp-sharded stream).
    with scope("embed"):
        emb = params["embed"][tokens]
        if _has_pos_table(cfg):
            emb = emb + params["pos"][None]
        x = lax.dynamic_slice_in_dim(emb, mp_idx * s_local, s_local, axis=1)
        x = x.astype(cfg.dtype)

    # Pipeline over pp with GPipe microbatching.
    xs = pp_lib.stack_microbatches(x, par.n_microbatches)
    stage_params = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    stage_fn = _make_stage_fn(cfg, positions)
    if _routes_dropless(cfg) and (axis_size("pp") > 1
                                  or par.pp_schedule != "gpipe"):
        raise NotImplementedError(
            "a dropless MoE's router statistics are not carried from stage "
            "to stage: pp must be 1 (schedule gpipe)")
    if par.pp_schedule == "1f1b":
        # Bounded-stash backward (O(n_stages) microbatch inputs, not
        # O(n_micro) tick residuals); rematerializes inherently, so the
        # remat flag does not apply.  Forward is bit-identical to GPipe.
        out = pp_lib.pipeline_apply_1f1b(stage_fn, stage_params, xs,
                                         axis_name="pp")
    elif par.pp_schedule == "gpipe":
        # stage_fn checkpoints each layer itself (cfg.remat).  A checkpoint
        # around the whole stage as well keeps the tick loop's stash at one
        # stage input a tick, which pays only where there are stages to
        # fill and drain; at one stage it would run the forward a third
        # time for nothing.
        out = pp_lib.pipeline_apply(
            stage_fn, stage_params, xs, axis_name="pp",
            remat=cfg.remat and axis_size("pp") > 1)
    else:
        raise ValueError(
            f"unknown pp_schedule {par.pp_schedule!r} (gpipe | 1f1b)")
    side = {}
    if _routes_dropless(cfg) or _selects(cfg):
        # (n_micro, layers, ...) sums over each microbatch's tokens.
        out, side = out
        side = jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), side)
        if not _selects(cfg):
            side = {"router": side}
    stats = side.get("router")
    hidden = pp_lib.unstack_microbatches(out)            # (B_local, s_local, d)

    # Final norm + logits (tied to the embedding, or ``lm_head``) + CE on
    # the local sequence chunk.
    with scope("head"):
        if diffusion:
            # The noised copy alone is scored (mp is 1: the halves are whole).
            hidden, s_local = hidden[:, :s_full], s_full
        hidden = _rmsnorm(hidden, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,vd->bsv", hidden.astype(jnp.float32),
                            params["embed" if cfg.tied_head
                                   else "lm_head"].astype(jnp.float32))
        labels_local = lax.dynamic_slice_in_dim(labels, mp_idx * s_local,
                                                s_local, axis=1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, labels_local[..., None],
                                 axis=-1)[..., 0]
        if diffusion:
            loss_local = -jnp.sum(ll * weights.astype(jnp.float32))
        else:
            loss_local = -jnp.mean(ll)

    # Average over sequence chunks (mp) and batch shards (dp); the loss is
    # only valid on the last pipeline stage → masked psum over pp.
    if diffusion:
        # A sum of the shards' sums over the global count, not a mean of
        # means.
        loss = lax.psum(loss_local, "dp") / (
            labels.size * axis_size("dp"))
    else:
        loss = lax.pmean(lax.pmean(loss_local, "mp"), "dp")
    loss = lax.psum(loss * pp_lib.last_stage_mask("pp"), "pp")
    if "index" in side:
        # Each block's is a mean over this shard's tokens; dp's shards are
        # equal.
        loss = loss + cfg.index_loss_coef * lax.pmean(
            jnp.sum(side["index"]), "dp")
    if not _routes_dropless(cfg):
        return loss
    # A product of two means depends on which tokens are averaged: sum the
    # statistics over every device's tokens first, so that the loss is the
    # same on every layout.
    stats = lax.psum(stats, ("dp", "mp"))
    n_tokens = tokens.size * axis_size("dp")      # the positions routed
    balance, z = moe_lib.router_losses(stats, n_tokens)      # (layers,) each
    loss = (loss + cfg.aux_loss_coef * jnp.mean(balance)
            + cfg.z_loss_coef * jnp.mean(z))
    if not with_routing:
        return loss
    return loss, {"assignments": stats.counts, "dropped": stats.dropped,
                  "load_balancing_loss": balance, "z_loss": z}


def make_loss_fn(cfg: TransformerConfig, par: ParallelConfig, mesh,
                 with_routing: bool = False):
    """Global-array loss: shard_map of ``forward_loss`` over (dp, pp, mp),
    ``loss_of(params, tokens, labels)``; a configuration whose batch brings
    a third array (``batch_extras``: a ``diffusion_block``'s ``weights``, a
    learned selection's ``positions``) takes it last."""
    from ..compat import shard_map
    specs = param_specs(cfg, par)
    data_spec = P("dp")

    # ``forward_loss`` takes ``weights``, then ``positions``.
    ahead = (None,) if batch_extras(cfg) == ("positions",) else ()

    def loss_of(params, tokens, labels, *extras):
        fn = shard_map(
            lambda p, t, l, *x: forward_loss(cfg, par, p, t, l, with_routing,
                                             *ahead, *x),
            mesh=mesh, in_specs=(specs,) + (data_spec,) * (2 + len(extras)),
            out_specs=P(), check_vma=False)
        return fn(params, tokens, labels, *extras)

    return loss_of


def make_routing_fn(cfg: TransformerConfig, par: ParallelConfig, mesh):
    """``routing(params, tokens, labels)`` (and ``weights`` with a
    ``diffusion_block``, whose 2 x seq_len positions are all routed) for a
    dropless MoE: what the router did with one global batch, through the
    training forward itself.
    A dict of ``assignments`` (layers, experts) — (token, choice) pairs each
    expert received; ``load`` (layers,) — the busiest expert's assignments
    over the mean's; ``dropped`` — pairs routed less pairs assigned (0:
    nothing is clamped); ``load_balancing_loss`` and ``z_loss`` (layers,),
    uncoefficiented; and the training ``loss``.  A patterned model's
    leading axes are (periods, "E" blocks a period).  Where a layer holds a
    share of its experts, ``assignments`` still covers every expert the
    router has (what a balancing rule reads), ``held_rows`` (layers,) are
    the pairs that fell on experts held here and ``dropped`` those of them
    that found no row in the static buffer."""
    loss_of = make_loss_fn(cfg, par, mesh, with_routing=True)
    held = _experts_held(cfg)

    def routing(params, tokens, labels, *weights):
        loss, r = loss_of(params, tokens, labels, *weights)
        counts = r["assignments"]
        routed = tokens.size * cfg.top_k * math.prod(counts.shape[:-1])
        return {**r, "loss": loss,
                "load": jnp.max(counts, axis=-1) / jnp.mean(counts, axis=-1),
                "held_rows": jnp.sum(counts[..., :held], axis=-1),
                "dropped": (jnp.sum(r["dropped"]) if _holds_a_share(cfg)
                            else routed - jnp.sum(counts))}

    return jax.jit(routing)


def make_router_balancer(cfg: TransformerConfig, par: ParallelConfig, mesh,
                         rounds: int = 12, gain: float = 0.02):
    """``balance(params, tokens, labels) -> params`` for a patterned model
    with a sigmoid router: the correction bias moved until, on this batch,
    every expert of every "E" block is chosen about equally often.

    A router with seeded weights is not balanced: past the first block the
    tokens share a direction, an expert's logit carries an offset common to
    all of them, and the busiest expert takes 5-12 x the mean.  A trained
    model's bias is what cancels that (DeepSeek-V3's rule, arXiv:2412.19437,
    moves it a step a batch towards the under-used experts); a checkpoint
    brings its value, seeded weights bring none.  This runs the rule to its
    fixed point in proportional form, ``rounds`` forward passes of ``b_e +=
    gain * log(target share / share_e)`` with the share floored at a tenth
    of the target (in score units: 0.02 damps it; 0.05 oscillates), after
    which busiest / mean is about 1.05 on the batch and 1.2 on fresh ones
    (chip runs, PERF.md PR 31).  The bias stays a buffer outside the
    gradient, and nothing updates it afterwards."""
    routers = [k for k in (cfg.layer_pattern and pattern_counts(cfg) or ())
               if _row(k).routes]
    if not routers or cfg.router_scoring != "sigmoid":
        raise ValueError("only a patterned model's sigmoid router has a "
                         "correction bias to balance")
    (routed,) = routers
    loss_of = make_loss_fn(cfg, par, mesh, with_routing=True)
    target = cfg.top_k / cfg.n_experts

    def with_bias(params, bias):
        moe = {**params["layers"][routed], "router_bias": bias}
        return {**params, "layers": {**params["layers"], routed: moe}}

    def balance(params, tokens, labels):
        def one_round(_, bias):
            _, r = loss_of(with_bias(params, bias), tokens, labels)
            share = r["assignments"][None] / tokens.size
            return bias + gain * jnp.log(
                target / jnp.maximum(share, 0.1 * target))

        return with_bias(params, lax.fori_loop(
            0, rounds, one_round, params["layers"][routed]["router_bias"]))

    return balance


def serial_forward_logits(cfg: TransformerConfig, params: Dict[str, Any],
                          tokens: jax.Array) -> jax.Array:
    """Unsharded training-path forward (dense MLP only): full fp32
    logits (B, S, V).  The numerics oracle the sharded loss AND the
    serving prefill/decode split are validated against."""
    assert cfg.n_experts == 0, "serial oracle covers the dense configuration"
    s_in = tokens.shape[1]
    x = (params["embed"][tokens] + params["pos"][None, :s_in]).astype(
        cfg.dtype)
    hd = cfg.head_dim
    n_pp, lps = params["layers"]["ln1"].shape[:2]
    for st in range(n_pp):
        for li in range(lps):
            lp = {k: v[st, li] for k, v in params["layers"].items()}
            h = _rmsnorm(x, lp["ln1"])
            qkv = jnp.einsum("bsd,de->bse", h, lp["wqkv"].astype(x.dtype))
            b, s = qkv.shape[:2]
            qkv = qkv.reshape(b, s, cfg.n_heads, 3, hd)
            q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
            o = ra.full_attention(q, k, v, causal=True)
            x = x + jnp.einsum("bse,ed->bsd", o.reshape(b, s, -1),
                               lp["wo"].astype(x.dtype))
            h = _rmsnorm(x, lp["ln2"])
            u = jax.nn.gelu(jnp.einsum("bsd,df->bsf", h,
                                       lp["w1"].astype(x.dtype)))
            x = x + jnp.einsum("bsf,fd->bsd", u, lp["w2"].astype(x.dtype))
    hidden = _rmsnorm(x, params["final_norm"])
    return jnp.einsum("bsd,vd->bsv", hidden.astype(jnp.float32),
                      params["embed"].astype(jnp.float32))


def serial_forward_loss(cfg: TransformerConfig, params: Dict[str, Any],
                        tokens: jax.Array, labels: jax.Array) -> jax.Array:
    """Unsharded oracle computing the same math as ``forward_loss`` (dense
    MLP only) — used by tests to validate the sharded step end to end."""
    logits = serial_forward_logits(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def make_train_step(cfg: TransformerConfig, par: ParallelConfig, mesh,
                    optimizer):
    """Build a jitted train step over the (dp, pp, mp) mesh.

    Returns (train_step, shard_params) where ``train_step(params, opt_state,
    tokens, labels) -> (params, opt_state, loss)`` (a ``diffusion_block``
    configuration's takes ``weights`` after ``labels``).  Differentiation
    happens *outside* shard_map, so gradient reductions over every axis come
    from AD transposes — no hand-written grad sync.
    """
    specs = param_specs(cfg, par)
    loss_of = make_loss_fn(cfg, par, mesh)

    def train_step(params, opt_state, tokens, labels, *weights):
        loss, grads = jax.value_and_grad(loss_of)(params, tokens, labels,
                                                  *weights)
        with scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(lambda p, u: p + u, params,
                                            updates)
        return params, opt_state, loss

    from jax.sharding import NamedSharding

    def shard_params(params):
        return jax.device_put(
            params, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P)))

    jitted = jax.jit(train_step, donate_argnums=(0, 1))
    return jitted, shard_params


def synthetic_batch(key, cfg: TransformerConfig, batch: int):
    """A random batch for ``cfg``: (tokens, next-token labels), or for a
    ``diffusion_block`` configuration :func:`noised_batch`'s three arrays,
    the mask token being the vocabulary's last id and the data the others,
    or with blocks of a learned selection also the position streams."""
    kt, kl = jax.random.split(key)
    if cfg.diffusion_block is not None:
        ids = jax.random.randint(kt, (batch, cfg.seq_len), 0,
                                 cfg.vocab_size - 1, dtype=jnp.int32)
        return noised_batch(kl, ids, cfg.diffusion_block, cfg.vocab_size - 1)
    tokens = jax.random.randint(kt, (batch, cfg.seq_len), 0, cfg.vocab_size,
                                dtype=jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    if _selects(cfg):
        # Text: the three streams all count the tokens.
        return tokens, labels, jnp.broadcast_to(
            jnp.arange(cfg.seq_len, dtype=jnp.int32),
            (batch, len(cfg.rope_sections), cfg.seq_len))
    return tokens, labels


def noised_batch(key, ids: jax.Array, block: int, mask_id: int,
                 floor: float = 1e-3):
    """The three arrays of a block-diffusion step from clean ``ids`` (B, L),
    made outside the step (the program knows nothing of the schedule).  Each
    block of ``block`` positions draws ``t = floor + (1 - floor) u``, ``u ~
    U(0, 1)`` (the linear schedule ``alpha_t = 1 - t``, one t a block as
    BD3-LMs, the floor as LLaDA), and each of its positions is replaced by
    ``mask_id`` independently with probability t.  Returns ``tokens`` (B,
    2 L) int32 — the noised copy, then the clean one — ``labels`` = ``ids``,
    and ``weights`` (B, L) fp32 = masked / t, the NELBO's ``-alpha'_t / (1 -
    alpha_t)`` on the masked positions and 0 elsewhere."""
    b, length = ids.shape
    if length % block:
        raise ValueError(f"{length} positions are not whole blocks of {block}")
    ku, km = jax.random.split(key)
    t = floor + (1.0 - floor) * jax.random.uniform(ku, (b, length // block))
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(km, (b, length)) < t
    noised = jnp.where(masked, jnp.int32(mask_id), ids)
    return (jnp.concatenate([noised, ids], axis=1).astype(jnp.int32), ids,
            (masked / t).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Serving: one chunk forward over a paged KV cache
# ---------------------------------------------------------------------------
#
# Inference is ONE entry point over a page-pool KV cache
# (``hvd.serving`` builds the continuous-batching engine on top —
# docs/serving.md): :func:`chunk_forward` advances a whole BATCH of
# sequences by K tokens each.  Per layer it writes the chunk's K/V at
# each slot's write positions and attends the K queries against that
# slot's gathered pages under a per-query causal mask.  A whole prompt
# is ``lengths = 0`` with K the padded prompt; a decode tick is K = 1.
# Shapes depend only on (slots, K, pages-per-slot, page size) — never on
# which requests occupy the slots — so the engine compiles it once per
# geometry and chunk length.
#
# Numerics: scores/softmax/PV accumulate in fp32 exactly like
# ``ra.reference_attention``; normalization and the vocab head are fp32
# like the training path.  Cache pages store K/V in the compute dtype.
# Padded/masked positions score ``-1e30`` → their softmax weight
# underflows to exactly 0.0, so a decode step reproduces the training
# forward's next-token distribution up to fp32 summation-order effects
# (the gathered key axis is the padded page extent, not the exact
# prefix length) — goldens assert tight ``allclose`` + argmax equality,
# not bit equality (see tests/test_serving.py).

_NEG_INF = -1e30


def _check_servable(cfg: TransformerConfig) -> None:
    """The serving forward below is the block of the defaults: a learned
    position table and GELU MLPs / ``w_in``-``w_out`` experts."""
    refused = [name for name, on in [
        ("rotary positions", cfg.rope_theta is not None),
        ("no position table", not cfg.learned_positions),
        ("QK-norm", cfg.qk_norm), ("gated experts", cfg.gated_experts),
        ("an untied head", not cfg.tied_head),
        ("a layer_pattern (state-space, grouped-query and latent-expert "
         "blocks)", cfg.layer_pattern is not None),
        ("a share of the experts held", _holds_a_share(cfg)),
        ("a diffusion_block (generation by blocks: ROADMAP M10)",
         cfg.diffusion_block is not None),
        ("per-head QK-norm", cfg.head_qk_norm),
        ("a sigmoid router", cfg.router_scoring != "softmax")] if on]
    if refused:
        raise NotImplementedError(
            "chunk_forward serves learned positions, a tied head, equal "
            "q / k / v heads and ungated MLPs; this configuration has "
            + ", ".join(refused) + " and trains only (ROADMAP M1)")


def init_kv_pages(cfg: TransformerConfig, n_pages: int,
                  page_size: int) -> Dict[str, jax.Array]:
    """Allocate the paged KV pool: ``k``/``v`` arrays of shape
    (n_layers, n_pages, page_size, n_heads, head_dim) in the compute
    dtype.  Pages are the allocation unit — a sequence's cache is the
    ordered list of page rows its page table names."""
    shape = (cfg.n_layers, int(n_pages), int(page_size),
             cfg.n_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def _flat_layers(params: Dict[str, Any]) -> Dict[str, jax.Array]:
    """Collapse the (n_pp, layers_per_stage, ...) stacked layer params
    into (n_layers, ...) for layer-indexed serving loops."""
    return {k: v.reshape((-1,) + v.shape[2:])
            for k, v in params["layers"].items()}


def _moe_mlp_serving(cfg: TransformerConfig, lp: Dict[str, jax.Array],
                     tok: jax.Array) -> jax.Array:
    """Per-token routed MoE MLP for serving.  tok: (T, d) → (T, d).

    The router runs per token (fp32 softmax → top-k, same gating math
    as training ``moe_layer``); combine weights are the raw top-k
    softmax probabilities, matching training.  No capacity clamp:
    capacity is a training-throughput construct (fixed dispatch
    buffers), not part of the learned function — at inference every
    token gets all of its routed experts.  The expert dim of the
    all-experts einsums partitions over an ``ep`` mesh axis when
    ``w_in``/``w_out`` are placed with a NamedSharding over experts
    (serving/engine.py) — GSPMD inserts the dispatch/combine
    collectives, so expert weights never gather onto one device.
    """
    e = cfg.n_experts
    logits = jnp.einsum("td,de->te", tok.astype(jnp.float32),
                        lp["gate"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)          # (T, E) fp32
    top_p, top_i = lax.top_k(probs, cfg.top_k)
    w = jnp.zeros_like(probs)
    for j in range(cfg.top_k):
        w = w + jax.nn.one_hot(top_i[:, j], e,
                               dtype=probs.dtype) * top_p[:, j:j + 1]
    h = jax.nn.gelu(jnp.einsum("td,edf->tef", tok.astype(jnp.float32),
                               lp["w_in"].astype(jnp.float32)))
    y = jnp.einsum("tef,efd->ted", h, lp["w_out"].astype(jnp.float32))
    out = jnp.einsum("te,ted->td", w, y)             # fp32 combine
    return out.astype(tok.dtype)


def _serving_layer(cfg: TransformerConfig, lp: Dict[str, jax.Array],
                   l: int, x: jax.Array, kv: Dict[str, jax.Array],
                   page_tables: jax.Array, write_page: jax.Array,
                   write_off: jax.Array, mask: jax.Array
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One transformer layer of :func:`chunk_forward`: layer ``l``'s
    parameters ``lp`` and its pages ``kv[...][l]``.  x: (B, K, d).
    write_page/write_off: (B, K) where each chunk position's K/V lands.
    mask: (B, K, max_len) — which cached positions each query may read.
    The layer's pages are addressed inside the stacked pool, not handed
    in as a slab: the write is then a scatter into the donated buffer,
    where a slab in and out costs a copy of the layer's pool each way.
    Returns (x, kv)."""
    b, kq, _ = x.shape
    hd = cfg.head_dim
    scale = 1.0 / (hd ** 0.5)
    h = _rmsnorm(x, lp["ln1"])
    qkv = jnp.einsum("bkd,de->bke", h, lp["wqkv"].astype(x.dtype))
    qkv = qkv.reshape(b, kq, cfg.n_heads, 3, hd)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    kv["k"] = kv["k"].at[l, write_page, write_off].set(k)
    kv["v"] = kv["v"].at[l, write_page, write_off].set(v)
    # Gather AFTER the write: the chunk attends to itself, with the
    # per-query causal mask keeping later chunk positions out.
    k_ctx = kv["k"][l][page_tables].reshape(b, -1, cfg.n_heads, hd)
    v_ctx = kv["v"][l][page_tables].reshape(b, -1, cfg.n_heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k_ctx.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p,
                   v_ctx.astype(jnp.float32)).astype(x.dtype)
    x = x + jnp.einsum("bke,ed->bkd", o.reshape(b, kq, -1),
                       lp["wo"].astype(x.dtype))
    h = _rmsnorm(x, lp["ln2"])
    if cfg.n_experts > 0:
        y = _moe_mlp_serving(cfg, lp, h.reshape(b * kq, -1))
        x = x + y.reshape(b, kq, -1)
    else:
        u = jax.nn.gelu(jnp.einsum("bkd,df->bkf", h,
                                   lp["w1"].astype(x.dtype)))
        x = x + jnp.einsum("bkf,fd->bkd", u, lp["w2"].astype(x.dtype))
    return x, kv


def chunk_forward(cfg: TransformerConfig, params: Dict[str, Any],
                  tokens: jax.Array, lengths: jax.Array,
                  kv: Dict[str, jax.Array],
                  page_tables: jax.Array
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Advance every slot by K tokens in ONE forward against its paged
    cache — the only function that writes the cache.

    tokens: (B, K) int32 — token j of slot b is written at position
    ``lengths[b] + j``.  lengths: (B,) int32 context sizes BEFORE the
    call.  page_tables: (B, pages_per_slot) int32 — logical position p
    of slot b lives in physical page ``page_tables[b, p // page_size]``
    at offset ``p % page_size``.  Returns (fp32 logits (B, K, V),
    updated kv) where ``logits[b, j]`` predicts the token AFTER
    ``tokens[b, j]`` — position ``lengths[b] + j`` attends every cached
    position ``<=`` itself.  Slots the caller considers inactive should
    point their page-table row at scratch page 0 — the math still runs,
    the writes land somewhere harmless, and the logits are ignored.

    Four callers share this one entry point (docs/serving.md):

    * **decode tick** — K = 1: every slot consumes its last sampled
      token and ``logits[:, 0]`` predicts the next;
    * **(chunked) prefill** — a whole prompt is ``lengths[b]`` = 0 with
      K the padded prompt; a prompt chunk at offset ``lengths[b]``
      interleaves into decode iterations instead of stalling them;
    * **prefix-cache suffix prefill** — ``lengths[b]`` > 0 names the
      cached-prefix length, only the suffix recomputes;
    * **speculative verify** — K = k+1 draft proposals are scored by
      the target in one batched forward.

    Padding/garbage contract: positions past a caller's valid chunk
    (padded tail, rejected speculative proposals) DO write K/V, but
    every such position is ≥ the slot's post-call valid length, so it
    is masked out of every later read until the position is rewritten
    with real content.  Positions at or past the table's extent route
    their writes to scratch page 0, and their position-table index is
    clamped to the table's last row.
    """
    _check_servable(cfg)
    kq = tokens.shape[1]
    page_size = kv["k"].shape[2]
    max_len = page_tables.shape[1] * page_size
    pos = lengths[:, None] + jnp.arange(kq, dtype=lengths.dtype)[None]
    pos_c = jnp.minimum(pos, max_len - 1)
    write_page = jnp.take_along_axis(page_tables, pos_c // page_size,
                                     axis=1)
    write_page = jnp.where(pos < max_len, write_page, 0)
    write_off = pos_c % page_size
    x = (params["embed"][tokens]
         + params["pos"][jnp.minimum(pos, cfg.seq_len - 1)]
         ).astype(cfg.dtype)                              # (B, K, d)
    layers = _flat_layers(params)
    mask = jnp.arange(max_len)[None, None, :] <= pos[:, :, None]
    for l in range(cfg.n_layers):
        lp = {k: v[l] for k, v in layers.items()}
        x, kv = _serving_layer(cfg, lp, l, x, kv, page_tables, write_page,
                               write_off, mask)
    hidden = _rmsnorm(x, params["final_norm"])           # (B, K, d)
    logits = jnp.einsum("bkd,vd->bkv", hidden.astype(jnp.float32),
                        params["embed"].astype(jnp.float32))
    return logits, kv


def draft_config(cfg: TransformerConfig, n_layers: int) -> TransformerConfig:
    """The speculative draft's config: the target's geometry with a
    layer-prefix depth (same vocab and positional table, so the draft
    and target share token/position spaces by construction)."""
    if not (0 < n_layers <= cfg.n_layers):
        raise ValueError(
            f"draft n_layers {n_layers} not in 1..{cfg.n_layers}")
    return cfg._replace(n_layers=n_layers, remat=False)


def draft_params_from(params: Dict[str, Any],
                      n_layers: int) -> Dict[str, Any]:
    """Slice a target parameter tree down to its first ``n_layers``
    layers (pp-restacked to one stage) for :func:`draft_config` —
    embeddings, positional table and final norm are SHARED (no copy),
    so a layer-prefix draft costs only the sliced layer stacks."""
    flat = {k: v.reshape((-1,) + v.shape[2:])
            for k, v in params["layers"].items()}
    total = next(iter(flat.values())).shape[0]
    if not (0 < n_layers <= total):
        raise ValueError(f"draft n_layers {n_layers} not in 1..{total}")
    out = dict(params)
    out["layers"] = {k: v[:n_layers][None] for k, v in flat.items()}
    return out


def train_flops_per_seq(cfg: TransformerConfig) -> float:
    """Matmul-FLOPs for one training sequence of ``seq_len`` data tokens
    (train = 3x fwd), importable so training loops can feed
    ``hvd.metrics.set_step_flops()``.  Dense per token 8d^2 (qkv+proj)
    + 4*d*ff (mlp) per layer + 2dV vocab head; causal attention
    2*S^2*d per layer per seq (half the bidirectional 4*S^2*d — the
    mask zeroes the upper triangle).  MoE configs count the routed top_k
    experts a token (4*d*ff each, 6*d*ff with the gate projection of
    ``gated_experts``) and the 2*d*E router."""
    d, L, s, v = (cfg.d_model, cfg.n_layers, cfg.seq_len,
                  cfg.vocab_size)
    if cfg.layer_pattern is not None:
        blocks = cfg.leading_pattern + _n_periods(cfg) * cfg.layer_pattern
        # Block diffusion: a token is two positions in the blocks, one at
        # the head.
        through = 2.0 if cfg.diffusion_block is not None else 1.0
        return 3.0 * s * (2.0 * d * v + through * sum(
            BLOCKS[c].flops(cfg) for c in blocks))
    mlp = 4.0 * d * cfg.d_ff
    if cfg.n_experts > 0:
        mlp = (cfg.top_k * ((6.0 if cfg.gated_experts else 4.0) * d * cfg.d_ff)
               + 2.0 * d * cfg.n_experts)
    dense = s * (L * (8.0 * d * d + mlp) + 2.0 * d * v)
    attn = L * 2.0 * s * s * d
    return 3.0 * (dense + attn)
