"""Expert parallelism — Mixture-of-Experts layers, two paths.

**Capacity path** (:func:`moe_layer`, :func:`top_k_routing`): Switch
Transformer semantics.  Top-k gating with a per-expert capacity, dispatch
einsum into a static (experts, capacity, d) buffer, two ``lax.all_to_all``
exchanges over the expert axis, GELU experts (``w_in`` / ``w_out``);
(token, route) pairs over capacity are dropped and pass through on the
residual path.  Its ``(T, E, C)`` one-hot tensors bound it to small token
counts.  Kept for its tests, ``models/moe_transformer.py`` and the
``n_experts > 0`` configurations of ``models/transformer.py`` that do not
ask for ``dropless``.

**Dropless path** (:func:`dropless_moe`): what the catalog's MoE models
use (OLMoE, arXiv:2409.02060).  Softmax over all experts in fp32, top-k,
the k probabilities used as they are; the (token, choice) pairs sorted by
expert; one grouped matmul per projection over the ragged groups
(``lax.ragged_dot``: on TPU a Mosaic kernel whose cost follows the rows,
not experts x rows); the rows put back in token order and summed with
their weights in fp32.  No capacity, no ``(T, E, C)`` tensor, nothing
dropped.  Experts are replicated over the mesh here; experts over an
``ep`` axis with a ragged exchange is the follow-up (ROADMAP M2).

The router is a function of a :class:`Router`: ``softmax`` as above, or
``sigmoid`` scores chosen by score + a correction bias and weighed by the
scores alone, renormalised over the chosen and scaled (DeepSeek-V3's
scheme, arXiv:2412.19437, which Nemotron 3's LatentMoE uses).  A layer may
also **hold a share** of the experts it routes over (the one-chip half of
expert parallelism): ``w_up`` / ``w_down`` then have fewer experts than the
router has outputs, the layer computes the part of the result its own
experts give, through a static buffer of rows, and what the absent experts
would have added is left out.  Nothing stands in for them.  The buffer is
sized for the worst case and mostly padding, so what moves rows of width d
walks its live rows only (``_token_sums``, ``_live_prefix``,
``_combine_rows_bwd``), never its padding and never the (token, held expert)
pairs, of which one in sixteen or fewer is chosen.  The two sums that bring
rows back to their tokens (the combine; the dispatch's backward) scatter
nothing into HBM: the live rows are listed by token (``_token_order``, one
sort of integers a forward), gathered in that order, and added in VMEM by
the Pallas kernel ``hvd_moe_token_sum`` (``ops/token_sum.py``), which writes
each tile of the (tokens, d) result once.

The reference's only layout-shuffling primitive is alltoall with uneven
splits (operations.cc:1136-1198, SURVEY.md §2.3 "the only primitive that
would serve EP/SP-style layouts"); the capacity path's exchanges are its
TPU-native form.  They optionally ride the EQuARX block-scaled int8/int4
wire from ``ops/quantization.py`` — each destination rank's chunk is
quantized independently (payload + one fp32 scale per block travel as two
all_to_alls), dequantized to fp32 on arrival.  The combine einsum always
accumulates in fp32; the wire dtype is never the accumulation dtype (the
module-wide contract of ops/quantization.py).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..compat import axis_size
from ..metrics.registry import registry
from ..ops.quantization import QuantSpec, wire_bytes
from ..utils.profiler import scope


class MoEParams(NamedTuple):
    gate: jax.Array    # (d_model, n_experts_total) — replicated
    w_in: jax.Array    # (n_local, d_model, d_ff)   — sharded over expert axis
    w_out: jax.Array   # (n_local, d_ff, d_model)   — sharded over expert axis


class RoutingInfo(NamedTuple):
    """Static-shape routing decision for one batch of local tokens."""
    dispatch: jax.Array   # (T, E, C) f32 in {0, 1} — token t → expert e slot c
    combine: jax.Array    # (T, E, C) f32 — dispatch weighted by gate prob
    aux_loss: jax.Array   # scalar f32 — load-balancing auxiliary loss
    dropped: jax.Array    # scalar f32 — (token, route) slots over capacity
    capacity: int         # static per-expert slot count


class MoEStats(NamedTuple):
    """Per-call accounting returned by ``moe_layer(..., return_stats=True)``."""
    aux_loss: jax.Array   # scalar f32
    dropped: jax.Array    # scalar f32 — dropped (token, route) assignments
    routed: jax.Array     # scalar f32 — total (token, route) assignments (T*k)
    capacity: int


def init_moe_params(key, d_model: int, d_ff: int, n_experts_total: int,
                    n_local: int, dtype=jnp.float32) -> MoEParams:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    return MoEParams(
        gate=(jax.random.normal(k1, (d_model, n_experts_total)) * s_in
              ).astype(dtype),
        w_in=(jax.random.normal(k2, (n_local, d_model, d_ff)) * s_in
              ).astype(dtype),
        w_out=(jax.random.normal(k3, (n_local, d_ff, d_model)) * s_out
               ).astype(dtype),
    )


def expert_capacity(tokens: int, n_experts: int, capacity_factor: float,
                    top_k: int = 1) -> int:
    """Per-expert slot count: ``ceil(tokens * top_k / n_experts * factor)``,
    clamped to at least 1 so a small ``capacity_factor`` (or tiny microbatch)
    can never round the buffer to zero slots and drop every token."""
    cap = int(math.ceil(tokens * top_k / n_experts * capacity_factor))
    return max(1, cap)


def top_k_routing(logits: jax.Array, capacity: int,
                  top_k: int = 1) -> RoutingInfo:
    """Top-k token→expert routing with capacity and drop accounting.

    Args:
      logits: (T, E) gating logits (any float dtype; softmax runs in fp32).
      capacity: static per-expert slot count (see :func:`expert_capacity`).
      top_k: routes per token.  Slots are filled greedily in gate-prob
        order; each route's combine weight is its raw softmax prob (the
        ``top_k=1`` case is exactly Switch Transformer semantics).

    Expert positions are assigned in token order, k-th choices after all
    (k-1)-th choices — an expert that overflows on earlier choices drops
    later ones, and the dropped count includes both.
    """
    t, e = logits.shape
    if top_k < 1 or top_k > e:
        raise ValueError(f"top_k must be in [1, {e}], got {top_k}")
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_i = lax.top_k(probs, top_k)                       # (T, k)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    counts = jnp.zeros((e,), jnp.float32)    # slots claimed so far per expert
    kept = jnp.float32(0.0)
    for j in range(top_k):
        onehot = jax.nn.one_hot(top_i[:, j], e, dtype=jnp.float32)  # (T, E)
        position = jnp.cumsum(onehot, axis=0) - 1.0 + counts[None, :]
        keep = (position < capacity) & (onehot > 0)                 # (T, E)
        pos_cap = jax.nn.one_hot(position.astype(jnp.int32), capacity,
                                 dtype=jnp.float32) * keep[..., None]
        dispatch = dispatch + pos_cap
        combine = combine + pos_cap * top_p[:, j][:, None, None]
        kept = kept + jnp.sum(keep.astype(jnp.float32))
        counts = counts + jnp.sum(onehot, axis=0)

    routed = jnp.float32(t * top_k)
    dropped = routed - kept
    # GShard/Switch load-balancing loss: fraction-of-routes per expert
    # (pre-drop, so overflow pressure is visible) × mean gate prob, scaled
    # by E so a perfectly uniform router scores 1.0.
    frac = counts / routed
    mean_prob = jnp.mean(probs, axis=0)
    aux = jnp.float32(e) * jnp.sum(frac * mean_prob)
    return RoutingInfo(dispatch=dispatch, combine=combine, aux_loss=aux,
                       dropped=dropped, capacity=capacity)


def _all_to_all_wire(v: jax.Array, axis_name: str,
                     quant: Optional[QuantSpec]) -> jax.Array:
    """Exchange rows of ``v`` (leading dim = mesh axis size) over
    ``axis_name``, optionally on the block-scaled quantized wire.

    Each destination's chunk ``v[p]`` is quantized independently so the
    receiver can dequantize without cross-rank metadata: the int8/int4
    payload and the fp32 per-block scales travel as two all_to_alls —
    exactly the EQuARX first-pass wire.  Output is fp32.

    The primitive lives in ops/xla_collectives.py (the compiled-plane
    collective layer); this alias keeps the historical call site.
    """
    from ..ops import xla_collectives as XC
    return XC.all_to_all_wire(v, axis_name, quant)


def dispatch_wire_bytes(ep: int, n_local: int, capacity: int, d_model: int,
                        quant: Optional[QuantSpec] = None) -> int:
    """Analytic bytes one member puts on the wire for ONE dispatch (or
    combine) all_to_all.  Quantization is per destination chunk, so the
    quantized wire is ``ep`` independent payload+scales rows."""
    chunk = n_local * capacity * d_model
    if quant is None:
        return 4 * ep * chunk
    return ep * wire_bytes(chunk, quant)


def moe_layer(params: MoEParams, x: jax.Array, axis_name: str,
              capacity_factor: float = 1.25,
              activation: Callable = jax.nn.gelu,
              top_k: int = 1,
              quant: Optional[QuantSpec] = None,
              return_stats: bool = False):
    """Apply an expert-parallel MoE MLP to local tokens.

    Args:
      params: local shard of the MoE parameters (n_local experts held here).
      x: (tokens, d_model) local token activations.
      axis_name: the expert-parallel mesh axis (size P; total experts
        E = P * n_local).
      capacity_factor: slack over the uniform-routing slot count; capacity
        is clamped to >= 1 (see :func:`expert_capacity`).
      top_k: routes per token (1 = Switch semantics, the default).
      quant: optional block-scaled wire format for the two all_to_all
        exchanges; compute and combine stay fp32.
      return_stats: also return :class:`MoEStats` (aux loss, drop counts).

    Returns:
      (tokens, d_model) combined expert outputs (zeros for dropped tokens —
      add the residual in the caller), or ``(out, MoEStats)`` when
      ``return_stats`` is set.
    """
    ep = axis_size(axis_name)
    t, d = x.shape
    n_local = params.w_in.shape[0]
    n_experts = ep * n_local
    capacity = expert_capacity(t, n_experts, capacity_factor, top_k)

    logits = jnp.einsum("td,de->te", x, params.gate)
    route = top_k_routing(logits, capacity, top_k)

    # --- dispatch: (T,E,C) x (T,d) -> (E,C,d), exchange over experts ----
    x_send = jnp.einsum("tec,td->ecd", route.dispatch, x.astype(jnp.float32))
    x_send = x_send.reshape(ep, n_local, capacity, d)
    # all_to_all: dim0 indexes destination rank before, source rank after.
    x_recv = _all_to_all_wire(x_send, axis_name, quant)           # (P,L,C,d)
    tokens = x_recv.transpose(1, 0, 2, 3).reshape(
        n_local, ep * capacity, d)                                # (L,P*C,d)

    # --- expert MLPs (batched over local experts; big MXU matmuls) ------
    h = activation(jnp.einsum("lcd,ldf->lcf", tokens,
                              params.w_in.astype(jnp.float32)))
    y = jnp.einsum("lcf,lfd->lcd", h, params.w_out.astype(jnp.float32))

    # --- return route: reverse the exchange, combine (fp32 accumulate) --
    y = y.reshape(n_local, ep, capacity, d).transpose(1, 0, 2, 3)
    y_back = _all_to_all_wire(y, axis_name, quant)                # (P,L,C,d)
    y_back = y_back.reshape(n_experts, capacity, d)
    out = jnp.einsum("tec,ecd->td", route.combine, y_back)
    out = out.astype(x.dtype)
    if not return_stats:
        return out
    stats = MoEStats(aux_loss=route.aux_loss, dropped=route.dropped,
                     routed=jnp.float32(t * top_k), capacity=capacity)
    return out, stats


def moe_load_balancing_loss(x: jax.Array, gate: jax.Array,
                            n_experts: int) -> jax.Array:
    """Switch Transformer auxiliary load-balancing loss (mean over tokens of
    fraction-routed × mean-prob per expert, scaled by E)."""
    logits = jnp.einsum("td,de->te", x, gate)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(expert_idx, n_experts), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(frac * mean_prob)


# ---------------------------------------------------------------------------
# Dropless path
# ---------------------------------------------------------------------------

class GatedMoEParams(NamedTuple):
    """One layer's router and the experts this device holds: all of them,
    or the first ``n_held`` of the router's ``n_experts`` (a share).
    ``w_gate`` is None for plain (``activation(x w_up) w_down``) experts;
    ``bias`` is a sigmoid router's correction bias, a buffer that moves the
    choice and not the weights and takes no gradient."""
    gate: jax.Array              # (d_router_in, n_experts) router
    w_gate: Optional[jax.Array]  # (n_held, d_model, d_ff)
    w_up: jax.Array              # (n_held, d_model, d_ff)
    w_down: jax.Array            # (n_held, d_ff, d_model)
    bias: Optional[jax.Array] = None    # (n_experts,)


class Router(NamedTuple):
    """How a dropless router scores the experts and weighs the chosen."""
    scoring: str = "softmax"     # "softmax" | "sigmoid" (choice by s + bias)
    renormalise: bool = False    # chosen weights over their sum
    scale: float = 1.0           # x the weights (``routed_scaling_factor``)
    renorm_eps: float = 0.0      # added to that sum (LFM2's 1e-6)


class RouterStats(NamedTuple):
    """What the router's auxiliary losses are made of, as sums over this
    device's tokens, so that a caller can add them up over microbatches and
    mesh axes before it multiplies (:func:`router_losses`)."""
    counts: jax.Array     # (E,) f32 — (token, choice) pairs sent to expert e
    prob_sum: jax.Array   # (E,) f32 — sum over tokens of the softmax
    z_sum: jax.Array      # () f32 — sum over tokens of logsumexp(logits)^2
    # () f32 — pairs routed to an expert held here that found no row in the
    # buffer (a layer that holds every expert has no buffer: 0).
    dropped: jax.Array = 0.0


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_tokens(x, token_of_row, row_of_pair, top_k):
    """``x[token_of_row]``: every token's row once for each of its ``top_k``
    choices, in expert order.  Backward is a gather too (row ``row_of_pair[t
    * top_k + j]`` holds token t's j-th copy), not the scatter-add AD would
    make of it."""
    return x[token_of_row]


def _rows_of_tokens_fwd(x, token_of_row, row_of_pair, top_k):
    return x[token_of_row], (row_of_pair, x.shape[0])


def _rows_of_tokens_bwd(top_k, res, g):
    row_of_pair, t = res
    g = g[row_of_pair].reshape(t, top_k, g.shape[-1])
    return jnp.sum(g.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@jax.custom_vjp
def _permute_rows(y, perm, inverse):
    """``y[perm]`` for a permutation and its inverse: the transpose of a
    permutation is the inverse permutation, a gather again."""
    return y[perm]


def _permute_rows_fwd(y, perm, inverse):
    return y[perm], inverse


def _permute_rows_bwd(inverse, g):
    return g[inverse], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _scores(params: GatedMoEParams, x: jax.Array, top_k: int,
            router: Router):
    """(scores (T, E) fp32, the chosen experts (T, k), logsumexp of the
    logits (T,) — zeros for a sigmoid router, which has no z-loss).  The
    logits at the highest matmul precision: a near-tie between the k-th
    and the next expert is decided by their last bits."""
    logits = jnp.dot(x.astype(jnp.float32),
                     params.gate.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if router.scoring == "softmax":
        lse = jax.nn.logsumexp(logits, axis=-1)
        probs = jnp.exp(logits - lse[:, None])
        _, top_i = lax.top_k(probs, top_k)                      # (T, k)
        return probs, top_i, lse
    if router.scoring != "sigmoid":
        raise ValueError(f"unknown router scoring {router.scoring!r} "
                         "(softmax | sigmoid)")
    scores = jax.nn.sigmoid(logits)
    choice = scores if params.bias is None else (
        scores + lax.stop_gradient(params.bias.astype(jnp.float32)))
    _, top_i = lax.top_k(choice, top_k)
    return scores, top_i, jnp.zeros(logits.shape[:1], jnp.float32)


def _weigh(p, router: Router):
    """The chosen experts' scores (0 elsewhere) as the weights of their
    outputs: as they are, or over their sum (plus ``renorm_eps`` where a
    model adds one), and scaled."""
    if router.renormalise:
        total = jnp.sum(p, axis=-1, keepdims=True)
        if router.renorm_eps:
            total = total + router.renorm_eps
        p = p / total
    return p if router.scale == 1.0 else p * router.scale


# Shares of the buffer ``_live_prefix`` may gather: the smallest that holds
# the live rows.  A buffer of 4 x the mean is a quarter live, a few percent
# either way.  ``_token_sums`` takes the first or the whole list: each share
# is a kernel to trace, lower and load, which is set-up time.
_LIVE_PREFIXES = (5 / 16, 1 / 2, 1)
_SUM_PREFIXES = (5 / 16, 1)


class _TokenOrder(NamedTuple):
    """The buffer's live rows listed by token (a token's by expert), int32,
    the list padded to whole chunks of the kernel ``hvd_moe_token_sum``;
    what is past the live rows is the padding, in no order.  With the
    kernel's walk over the list (``ops/token_sum.walk``), which both sums
    and each of their prefixes share."""
    row: jax.Array     # (L,) the buffer row in this place of the list
    token: jax.Array   # (L,) its token; ``tokens`` for the padding
    pair: jax.Array    # (L,) its (token, held expert) pair; T x held there
    steps: tuple       # ((2, steps) tile and chunk of each step, (1,) live)


def _token_order(pair_of_row, n_live, n_held: int, tokens: int) -> _TokenOrder:
    """One sort of the buffer's R integers: a live row's pair is token x held
    + expert, so the pairs ascending are the rows by token; the padding's key
    is past the last pair.  Made once a block's forward, for both sums."""
    from ..ops import token_sum          # Pallas: not at the package's import
    rows = pair_of_row.shape[0]
    tile, chunk = token_sum.tiling(tokens, rows)
    place = jnp.arange(rows, dtype=jnp.int32)
    pair, row = lax.sort(
        (jnp.where(place < n_live, pair_of_row.astype(jnp.int32),
                   tokens * n_held), place), num_keys=1)
    pad = (0, -rows % chunk)
    pair = jnp.pad(pair, pad, constant_values=tokens * n_held)
    token = pair // n_held
    return _TokenOrder(row=jnp.pad(row, pad), token=token, pair=pair,
                       steps=token_sum.walk(token, tokens, tile, chunk))


def _token_sums(z, weights, order: _TokenOrder, n_live, tokens: int,
                site: str):
    """(tokens, d) fp32: token t's sum of the buffer's live rows that are its
    own, ``z[r] * weights[pair of r]`` in fp32 (``weights`` None: ``z[r]``);
    0 for a token with no row.  Both sums of the held path are this one: the
    combine's forward (``weights`` the flat (T x held,) routing weights) and
    the dispatch's backward.

    No row is scattered into HBM.  The live rows are gathered into token
    order (``order``: one plain gather of a static prefix of the list, 5/16
    of it where that holds ``n_live`` rows and all of it where not, as
    :func:`_live_prefix` takes the buffer's, masked past ``n_live``; their
    weights with them, a scalar a listed row), where a token's rows are
    neighbours, and the kernel ``hvd_moe_token_sum`` (``ops/token_sum.py``)
    adds them into a token tile that stays in VMEM until it is whole and is
    written once.  Nothing but the result is as large as a token's row for
    every token, no (R, d) fp32 array is made, and the padding is neither
    read nor added.  Compiled by Mosaic where the program is lowered for a
    TPU, run by the Pallas interpreter elsewhere: on a TPU host the program
    is lowered for the TPU it is traced on; off one
    ``lax.platform_dependent`` decides as the program is lowered, so the CPU
    tests run the kernel's own code and a step compiled for a described TPU
    holds the kernel.  (Both branches traced on the TPU host cost set-up time
    for nothing: PERF.md section 6, PR 47.)  Inside custom-VJP rules, which
    AD never sees."""
    from ..ops import token_sum
    _token_sums_built(site)
    _token_sum_kernels_built(site)
    rows, listed = z.shape[0], order.row.shape[0]
    tile, chunk = token_sum.tiling(tokens, rows)

    def prefix(n):
        def sums():
            live = jnp.arange(n) < n_live
            picked = jnp.where(live[:, None], z[order.row[:n]], 0)
            scale = None if weights is None else jnp.where(
                live, weights[jnp.minimum(order.pair[:n], weights.size - 1)],
                0)
            kernel = partial(token_sum.token_sum, tokens=tokens, tile=tile,
                             chunk=chunk)
            operands = picked, order.token[:n], scale, order.steps
            if jax.default_backend() == "tpu":
                return kernel(*operands)
            return lax.platform_dependent(
                *operands, tpu=kernel, default=partial(kernel, interpret=True))
        return sums

    # Whole chunks, so that the kernel pads nothing.
    sizes = sorted({min(listed, -(-int(rows * share) // chunk) * chunk)
                    for share in _SUM_PREFIXES})
    which = sum((n_live > n).astype(jnp.int32) for n in sizes[:-1])
    return lax.switch(which, [prefix(n) for n in sizes])


def _token_sums_built(site: str) -> None:
    """Trace-time count of the row-space sums built, by site: none for a
    layer that holds every expert."""
    registry().counter(
        "hvd_moe_token_sums_built_total",
        "held experts' sums of buffer rows into their tokens traced, by site",
        site=site).inc()


def _token_sum_kernels_built(site: str) -> None:
    """Trace-time count of the sums that are the kernel ``hvd_moe_token_sum``,
    by site: none for a layer that holds every expert."""
    registry().counter(
        "hvd_moe_token_sum_kernels_built_total",
        "held experts' token sums traced as the Pallas kernel, by site",
        site=site).inc()


def _live_prefix(x, token_of_row, n_live):
    """(R, d): ``x[token_of_row]`` in the buffer's live prefix, rows 0 ..
    ``n_live`` - 1, zeros in its padding.  One plain gather of the shortest
    of a few static prefixes that holds ``n_live`` rows (a device scalar:
    ``lax.switch``), padded to the buffer, so a buffer that is a quarter
    live costs 5/16 of its rows and a full one all of them.  Not a loop that
    carries the buffer: that one held 0.4 GiB more where Laguna's step
    peaks (PERF.md section 6, PR 44)."""
    _live_gathers_built("rows")
    rows = token_of_row.shape[0]

    def prefix(n):
        def gather():
            live = (jnp.arange(n) < n_live)[:, None]
            return jnp.pad(jnp.where(live, x[token_of_row[:n]], 0),
                           ((0, rows - n), (0, 0)))
        return gather

    sizes = sorted({min(rows, -(-int(rows * share) // 8) * 8)
                    for share in _LIVE_PREFIXES})
    which = sum((n_live > n).astype(jnp.int32) for n in sizes[:-1])
    return lax.switch(which, [prefix(n) for n in sizes])


def _live_gathers_built(site: str) -> None:
    """Trace-time count of the row gathers built over the live prefix, by
    site: none for a layer that holds every expert."""
    registry().counter(
        "hvd_moe_live_gathers_built_total",
        "held experts' row gathers over the buffer's live prefix traced, "
        "by site", site=site).inc()


@jax.custom_vjp
def _held_rows(x, token_of_row, n_live, order: _TokenOrder):
    """The rows of the buffer, in expert order: ``x[token_of_row]`` for the
    first ``n_live``, which are live, and zeros for the padding
    (:func:`_live_prefix`).  Backward adds a token's live rows of ``g``:
    :func:`_token_sums`, the rows taken in ``order`` and added in VMEM by
    the kernel ``hvd_moe_token_sum``."""
    return _live_prefix(x, token_of_row, n_live)


def _held_rows_fwd(x, token_of_row, n_live, order):
    return (_live_prefix(x, token_of_row, n_live),
            (order, n_live, x.shape[0]))


def _held_rows_bwd(res, g):
    order, n_live, tokens = res
    dx = _token_sums(g, None, order, n_live, tokens, "dispatch_bwd")
    return dx.astype(g.dtype), None, None, None


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


@jax.custom_vjp
def _combine_rows(y, weights, pair_of_row, n_live, order: _TokenOrder):
    """(T, d) fp32: ``sum_e weights[t, e] * y[row of (t, e)]`` over the
    pairs that have a row, forward and backward in row space (row r came
    from pair ``pair_of_row[r]``; the first ``n_live`` rows are live).
    Forward: :func:`_token_sums` of the rows times their weights — the live
    rows taken in ``order``, by token, and added in VMEM by the kernel
    ``hvd_moe_token_sum``; nothing is scattered.  Backward
    (:func:`_combine_rows_bwd`): ``dy[r] = g[token of r] * weight of r``
    and one dot a row for ``dweights``, over the live prefix.  No array has
    a row for every (token, held expert) pair: AD's transpose of the
    pair-space sum broadcast ``g`` to (T, held, d) in fp32, 3 GiB at 24,576
    positions, 16 held and 2048 features."""
    return _token_sums(y, weights.reshape(-1), order, n_live,
                       weights.shape[0], "combine")


def _combine_rows_fwd(y, weights, pair_of_row, n_live, order):
    return (_combine_rows(y, weights, pair_of_row, n_live, order),
            (y, weights, pair_of_row, n_live))


# Rows of the buffer's live prefix a trip of ``_combine_rows_bwd``'s loop
# writes.
_GATHER_CHUNK = 512


def _combine_rows_bwd(res, g):
    """``dy`` (R, d) and ``dweights`` (T, held) of the combine, over the
    buffer's live prefix: a trip gathers its ``_GATHER_CHUNK`` rows' tokens'
    rows of ``g`` in fp32, writes them times the rows' weights into the
    ``dy`` the loop carries, and sets each row's dot with ``y`` at its pair
    in ``dweights`` (a live row is one kept pair's, so every kept pair is
    set once and the others stay 0).  Trip count read from ``n_live``; the
    padding's ``dy`` is zeros, and no (R, d) fp32 array is made."""
    y, weights, pair_of_row, n_live = res
    _live_gathers_built("combine_bwd")
    rows, d = y.shape
    pairs = weights.size
    n_held = weights.shape[1]
    flat = weights.reshape(-1)
    chunk = min(_GATHER_CHUNK, rows)

    def trip(c, carry):
        dy, dw = carry
        # The last trip of a buffer that is no multiple of the chunk starts
        # early and writes the rows it shares with the one before again.
        at = jnp.minimum(c * chunk, rows - chunk)
        live = at + jnp.arange(chunk) < n_live
        pair = lax.dynamic_slice(pair_of_row, (at,), (chunk,))
        g_rows = g[pair // n_held]                        # (chunk, d) fp32
        w_rows = jnp.where(live, flat[pair], 0)
        dy = lax.dynamic_update_slice(
            dy, (g_rows * w_rows[:, None]).astype(y.dtype), (at, 0))
        ys = lax.dynamic_slice(y, (at, 0), (chunk, d)).astype(jnp.float32)
        dw = dw.at[jnp.where(live, pair, pairs)].set(
            jnp.sum(g_rows * ys, axis=-1), mode="drop", unique_indices=True)
        return dy, dw

    dy, dw = lax.fori_loop(
        0, (n_live + chunk - 1) // chunk, trip,
        (jnp.zeros_like(y), jnp.zeros((pairs,), jnp.float32)))
    return (dy, dw.reshape(weights.shape).astype(weights.dtype), None, None,
            None)


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _grouped_experts(params: GatedMoEParams, rows, group_sizes, activation,
                     row_used=None):
    """``(activation(rows w_gate) * (rows w_up)) w_down`` (without
    ``w_gate``: ``activation(rows w_up) w_down``), each row by the expert
    of its group, as grouped matmuls.  ``row_used`` (a held share's buffer)
    masks the rows past the last group, which belong to no expert:
    whatever the grouped matmul leaves there goes no further."""
    dtype = rows.dtype
    up = lax.ragged_dot(rows, params.w_up.astype(dtype), group_sizes)
    if params.w_gate is None:
        hidden = activation(up.astype(jnp.float32))
    else:
        gate = lax.ragged_dot(rows, params.w_gate.astype(dtype), group_sizes)
        hidden = (activation(gate.astype(jnp.float32))
                  * up.astype(jnp.float32))
    if row_used is not None:
        hidden = jnp.where(row_used[:, None], hidden, 0)
    return lax.ragged_dot(hidden.astype(dtype), params.w_down.astype(dtype),
                          group_sizes)


def held_row_buffer(tokens: int, top_k: int, n_held: int, n_experts: int,
                    factor: float) -> int:
    """Rows of the static buffer a share-holding layer computes: ``factor``
    x the mean (tokens x top_k x held / routed), a multiple of 8, never
    more than every (token, held expert) pair."""
    mean = tokens * top_k * n_held / n_experts
    rows = -(-int(math.ceil(mean * factor)) // 8) * 8
    return max(8, min(rows, tokens * n_held))


def _held_experts(params: GatedMoEParams, x, weights, chosen, activation,
                  row_buffer: int):
    """The held experts' part of the result.  ``weights``: (T, n_held)
    fp32 and ``chosen`` (T, n_held) bool, whether the token chose the
    expert.  The chosen (token, held expert) pairs are sorted by expert;
    the first ``row_buffer`` rows are computed, the rest dropped and
    counted.  The buffer's rows are in expert order with the unchosen pairs
    behind all chosen ones, so rows 0 .. ``n_live`` - 1 are live and the rest
    is padding.  Rows go into the buffer by one gather of its live prefix
    (:func:`_live_prefix`) and come back to their tokens (the combine; the
    dispatch's backward) by :func:`_token_sums`: the live rows listed by
    token (:func:`_token_order`, made here once for both), gathered in that
    order and added in VMEM by the kernel ``hvd_moe_token_sum``, no row
    scattered into HBM.  The combine's backward walks the live prefix
    (:func:`_combine_rows_bwd`).  Nothing in this path has a row of width d
    for every (token, held expert) pair or for every (token, choice) pair
    beyond the buffer itself, and nothing moves the padding.  Returns (out
    (T, d) fp32, pairs dropped ())."""
    t, d = x.shape
    n_held = params.w_up.shape[0]
    with scope("moe_route"):
        counts = jnp.sum(chosen, axis=0, dtype=jnp.int32)        # (n_held,)
        # Pair (t, e) sorts under its expert, an unchosen one after all.
        key = jnp.where(chosen, jnp.arange(n_held, dtype=jnp.int32), n_held)
        pair_of_row = jnp.argsort(key.reshape(t * n_held),
                                  stable=True)[:row_buffer]
        ends = jnp.minimum(jnp.cumsum(counts), row_buffer)
        group_sizes = jnp.diff(ends, prepend=0)
        n_live = ends[-1]
        row_used = jnp.arange(row_buffer) < n_live
        dropped = (jnp.sum(counts) - n_live).astype(jnp.float32)
    with scope("moe_dispatch"):
        order = _token_order(pair_of_row, n_live, n_held, t)
        rows = _held_rows(x, pair_of_row // n_held, n_live, order)
    with scope("moe_experts"):
        y = _grouped_experts(params, rows, group_sizes, activation, row_used)
    with scope("moe_dispatch"):
        out = _combine_rows(y, weights, pair_of_row, n_live, order)
    return out, dropped


def dropless_moe(params: GatedMoEParams, x: jax.Array, top_k: int,
                 activation: Callable = jax.nn.silu,
                 router: Router = Router(),
                 router_x: Optional[jax.Array] = None,
                 buffer_factor: float = 4.0):
    """Dropless top-k MoE MLP over this device's tokens.

    ``x``: (tokens, d_model) in the compute dtype.  Router logits and
    scores in fp32 (the logits at the highest matmul precision: a near-tie
    between the k-th and the next expert is decided by their last bits, and
    the matmul is 2 T d E FLOPs) from ``router_x`` where the router reads
    another tensor than the experts do (LatentMoE: the hidden state, the
    experts its latent projection), else from ``x``.  With the default
    :class:`Router` the k largest softmax probabilities weigh their experts
    as they are, not renormalised.  Expert e computes ``(activation(x
    w_gate[e]) * (x w_up[e])) w_down[e]`` (without ``w_gate``:
    ``activation(x w_up[e]) w_down[e]``) for exactly the rows routed to it.
    Returns ``(out, RouterStats)``, ``out`` (tokens, d_model) in
    ``x.dtype``, to be added to the residual by the caller.

    Where ``params`` holds fewer experts than the router has outputs, they
    are experts 0 .. n_held - 1 of a layer whose others live elsewhere: the
    router scores, chooses and weighs over all of them, the result is the
    held experts' part alone, and their rows go through a static buffer of
    ``buffer_factor`` x the mean (:func:`held_row_buffer`); a row beyond it
    is dropped and counted in ``RouterStats.dropped``.  ``counts`` stays
    what the router chose, over all its outputs, held or not.  The buffer's
    rows are gathered, weighed and added back into their tokens, and their
    cotangents made and added into the tokens', over the buffer's live rows
    (``_live_prefix``, ``_token_sums``, ``_combine_rows_bwd``; the
    trace-time counters ``hvd_moe_live_gathers_built_total{site}`` and
    ``hvd_moe_token_sums_built_total{site}`` say they engaged), the sums in
    fp32 and in VMEM: the rows are taken in token order and added by the
    Pallas kernel ``hvd_moe_token_sum``, one call a sum
    (``hvd_moe_token_sum_kernels_built_total{site}``), so no row is
    read-modified-written in HBM.
    """
    t, d = x.shape
    e = params.gate.shape[1]
    n_held = params.w_up.shape[0]
    if not 1 <= top_k <= e:
        raise ValueError(f"top_k must be in [1, {e}], got {top_k}")
    if n_held > e:
        raise ValueError(f"{n_held} experts held of {e} routed")
    with scope("moe_route"):
        probs, top_i, lse = _scores(
            params, x if router_x is None else router_x, top_k, router)
    if n_held < e:
        with scope("moe_route"):
            # (T, E) in {0, 1}: the token chose the expert.  Summed over
            # the choices inside one fusion; no (T, k, E) tensor is kept.
            member = jnp.sum(jax.nn.one_hot(top_i, e, dtype=probs.dtype),
                             axis=1)
            weights = _weigh(probs * member, router)[:, :n_held]
        out, dropped = _held_experts(
            params, x, weights, member[:, :n_held] > 0, activation,
            held_row_buffer(t, top_k, n_held, e, buffer_factor))
        stats = RouterStats(
            counts=jnp.sum(member, axis=0), prob_sum=jnp.sum(probs, axis=0),
            z_sum=jnp.sum(lse * lse), dropped=dropped)
        return out.astype(x.dtype), stats
    with scope("moe_route"):
        # The chosen probabilities by a one-hot product, not ``top_k``'s
        # values or a gather: their transpose is a product too, where
        # those scatter (T k) scalars into (T, E).
        top_p = _weigh(jnp.sum(probs[:, None, :] * jax.nn.one_hot(
            top_i, e, dtype=probs.dtype), axis=-1), router)
    with scope("moe_route"):
        expert_of_pair = top_i.reshape(t * top_k)
        # Row r of the sorted order holds pair ``pair_of_row[r]`` = token *
        # top_k + choice; stable, so an expert's rows stay in token order.
        pair_of_row = jnp.argsort(expert_of_pair, stable=True)
        row_of_pair = jnp.argsort(pair_of_row)
        group_sizes = jnp.sum(
            expert_of_pair[:, None] == jnp.arange(e, dtype=top_i.dtype),
            axis=0, dtype=jnp.int32)
        stats = RouterStats(counts=group_sizes.astype(jnp.float32),
                            prob_sum=jnp.sum(probs, axis=0),
                            z_sum=jnp.sum(lse * lse),
                            dropped=jnp.float32(0.0))
    with scope("moe_dispatch"):
        rows = _rows_of_tokens(x, pair_of_row // top_k, row_of_pair, top_k)
    with scope("moe_experts"):
        y = _grouped_experts(params, rows, group_sizes, activation)
    with scope("moe_dispatch"):
        y = _permute_rows(y, row_of_pair, pair_of_row).reshape(t, top_k, d)
        out = jnp.sum(y.astype(jnp.float32) * top_p[..., None], axis=1)
    return out.astype(x.dtype), stats


def router_losses(stats: RouterStats, n_tokens):
    """(load-balancing loss, router z-loss) of one layer from statistics
    already summed over every token the losses are taken over.

    Load balancing as HF ``load_balancing_loss_func``: ``E * sum_e f_e *
    P_e`` with ``f_e`` the share of tokens that chose expert e among their
    ``top_k`` (the f_e add up to ``top_k``, so a uniform router scores
    ``top_k``) and ``P_e`` the mean router probability of e.  A product of
    two means: it depends on which tokens are averaged, hence the sums.
    z-loss: the mean of ``logsumexp(logits)^2`` (ST-MoE, arXiv:2202.08906).
    """
    e = stats.counts.shape[-1]
    f = stats.counts / n_tokens
    p = stats.prob_sum / n_tokens
    return e * jnp.sum(f * p, axis=-1), stats.z_sum / n_tokens
