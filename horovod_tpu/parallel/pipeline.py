"""Pipeline parallelism — GPipe and 1F1B microbatch schedules inside
shard_map.

Stages are laid out along a mesh axis; activations travel stage→stage over
``lax.ppermute`` (one ICI hop when the pipeline axis is laid out along a
physical ring).

Two schedules:

* **GPipe** (:func:`pipeline_apply`): a ``lax.scan`` over
  ``n_microbatches + n_stages - 1`` ticks, so XLA sees a static loop;
  the backward pass — obtained by differentiating through the scan —
  reverses the permutes automatically.  Autodiff stashes one activation
  per scan tick, so the stash grows with ``n_micro``.  A pipeline of one
  stage is its stage: when the axis has one member there is nothing to
  fill, drain or send, and :func:`pipeline_apply` maps the stage over the
  microbatches in order — no tick loop, no output buffer, no permute of
  the activation to itself.

* **1F1B** (:func:`pipeline_apply_1f1b`): the Megatron one-forward-
  one-backward schedule as a ``jax.custom_vjp``.  The primal forward IS
  the GPipe tick loop (outputs are bit-identical); the backward replays
  forward and backward work interleaved along a host-precomputed static
  schedule table, holding a rolling activation stash bounded by the
  pipeline depth — O(``n_stages``) microbatch inputs, not O(``n_micro``)
  tick residuals.  The backward rematerializes stage forwards (the
  memory/compute trade 1F1B-with-remat makes); gradients equal GPipe's
  up to summation order.

Bubble arithmetic: with P stages and M microbatches both schedules idle
``(P-1)/(M+P-1)`` of their work slots (1F1B's win is memory, not bubble).
:func:`bubble_fraction` is the analytic bound; the schedule builder
measures the realized fraction from its own table, and
:func:`note_bubble` feeds the bubble share of a measured pipeline span to
the step-attribution engine as the ``pipeline_bubble`` wall component.

The reference framework has no pipeline support (SURVEY.md §2.3); this is
TPU-native scope.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from ..compat import axis_size

# Schedule-table op kinds (static int32 constants baked into the scan).
_IDLE, _FWD, _BWD = 0, 1, 2


def _gpipe_forward(fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any,
                   x_microbatches: jax.Array,
                   axis_name: str) -> jax.Array:
    """The GPipe tick loop — shared by :func:`pipeline_apply` and the
    1F1B primal so their outputs are bit-identical by construction."""
    n_stages = axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    n_micro = x_microbatches.shape[0]
    ticks = n_micro + n_stages - 1
    # Forward chain i -> i+1; the last stage sends to 0 (its payload is
    # ignored there — stage 0 always injects a fresh microbatch) keeping the
    # permutation a pure ring for ICI friendliness.
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    act0 = jnp.zeros_like(x_microbatches[0])
    out0 = jnp.zeros_like(x_microbatches)

    def body(carry, t):
        act, outbuf = carry
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        x_t = lax.dynamic_index_in_dim(x_microbatches, mb_idx, axis=0,
                                       keepdims=False)
        a_in = jnp.where(stage == 0, x_t, act)
        y = fn(stage_params, a_in)
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        write = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
        updated = lax.dynamic_update_index_in_dim(outbuf, y, out_idx, axis=0)
        outbuf = jnp.where(write, updated, outbuf)
        act = lax.ppermute(y, axis_name, perm)
        return (act, outbuf), None

    (_, outbuf), _ = lax.scan(body, (act0, out0), jnp.arange(ticks))
    return outbuf


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any,
                   x_microbatches: jax.Array,
                   axis_name: str,
                   remat: bool = True) -> jax.Array:
    """Run ``stage_fn`` as a GPipe pipeline over ``axis_name``.

    Args:
      stage_fn: ``(params_for_this_stage, activation) -> activation`` with
        identical activation shapes in and out (embed/unembed live outside
        the pipeline).
      stage_params: this member's stage parameters (shard the full stacked
        stage dim over the pipeline axis in the caller's in_specs).
      x_microbatches: (n_micro, mb, ...) input; consumed by stage 0.
        ``n_micro < n_stages`` is legal — the pipeline just never fills
        (bubble fraction ``(P-1)/(M+P-1)`` grows accordingly); the fill/
        drain ticks recompute clamped microbatches whose results are
        never written to the output buffer.
      axis_name: the pipeline mesh axis.
      remat: rematerialize each stage in the backward pass, so that the
        tick loop stashes one stage *input* a tick and not the stage's
        inner residuals.  A ``stage_fn`` that checkpoints its own layers
        is then recomputed twice in the backward pass (once as a stage,
        once a layer): with several stages that buys a stash of ticks x
        one activation where it would be ticks x layers x one activation;
        with one stage it buys nothing, and such a caller passes
        ``remat=False`` there (``models/transformer.forward_loss`` does).

    One stage (``axis_size(axis_name) == 1``, static at trace time): the
    result is ``stage_fn`` applied to each microbatch in order (a plain
    call for one microbatch, ``lax.map`` for more) — the same operations
    on the same values as the tick loop, whose ``where(stage == 0, ...)``,
    masked write and ring permute are identities there.  ``remat`` keeps
    its meaning.  Here, and only here, the stage may return a pytree (its
    activation and side outputs, e.g. a MoE router's statistics): every
    leaf comes back with the leading microbatch axis.  What a
    self-checkpointing stage costs without the stage
    checkpoint is one saved activation a layer and microbatch
    (layers x mb x sequence x width x itemsize) in place of one a stage;
    what it saves is one whole forward of the stage a step.

    Returns:
      (n_micro, mb, ...) outputs — valid on the **last** stage; other stages
      hold zeros (reduce with a stage mask, see ``last_stage_mask``).
    """
    n_micro = x_microbatches.shape[0]
    if n_micro < 1:
        raise ValueError("need at least one microbatch")
    fn = jax.checkpoint(stage_fn) if remat else stage_fn
    if axis_size(axis_name) == 1:
        if n_micro == 1:
            return jax.tree_util.tree_map(
                lambda y: y[None], fn(stage_params, x_microbatches[0]))
        return lax.map(partial(fn, stage_params), x_microbatches)
    return _gpipe_forward(fn, stage_params, x_microbatches, axis_name)


# ---------------------------------------------------------------------------
# 1F1B schedule
# ---------------------------------------------------------------------------

def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Analytic pipeline-bubble fraction ``(P-1)/(M+P-1)`` — the share of
    work slots each stage idles in either schedule (GPipe drains what 1F1B
    interleaves; the slot count is the same)."""
    if n_stages < 1 or n_micro < 1:
        raise ValueError(f"need n_stages, n_micro >= 1, got "
                         f"{(n_stages, n_micro)}")
    return (n_stages - 1) / (n_micro + n_stages - 1)


class Schedule1F1B(NamedTuple):
    """Static per-(stage, slot) op tables for the 1F1B backward replay."""
    kind: np.ndarray        # (n_stages, n_slots) int32 in {IDLE, FWD, BWD}
    mb: np.ndarray          # (n_stages, n_slots) int32 microbatch, -1 idle
    n_slots: int
    stash_depth: int        # max live microbatch inputs held by any stage
    measured_bubble: float  # idle work slots / total slots, from the table


def build_1f1b_schedule(n_stages: int, n_micro: int) -> Schedule1F1B:
    """Greedy discrete-event build of the non-interleaved 1F1B schedule.

    One op (forward of one microbatch, backward of one microbatch, or
    idle) per stage per slot.  Dependencies: F(s, m) needs F(s-1, m) a
    slot earlier (activation hop); B(s, m) needs B(s+1, m) a slot earlier
    (cotangent hop) and F(s, m) already done.  Each stage admits a new
    forward only while forwards-minus-backwards stays below
    ``n_stages - s`` — the Megatron warmup depth plus one — which bounds
    the live activation stash by the pipeline depth, independent of
    ``n_micro``.  The builder verifies every invariant and measures the
    realized bubble fraction from its own table.
    """
    P, M = int(n_stages), int(n_micro)
    if P < 1 or M < 1:
        raise ValueError(f"need n_stages, n_micro >= 1, got {(P, M)}")
    f_done = [0] * P             # forwards completed per stage
    b_done = [0] * P             # backwards completed per stage
    f_slot = [[-1] * M for _ in range(P)]   # slot F(s, m) ran
    b_slot = [[-1] * M for _ in range(P)]   # slot B(s, m) ran
    kind_rows, mb_rows = [], []
    t = 0
    cap = 4 * (M + P) + 8        # safety: greedy must finish well before
    while any(b < M for b in b_done):
        if t >= cap:
            raise AssertionError("1F1B schedule builder failed to converge")
        krow, mrow = [_IDLE] * P, [-1] * P
        for s in range(P):
            # Backward first (that is what 1F1B means after warmup).
            m = b_done[s]
            b_ready = (m < M and f_slot[s][m] != -1
                       and (s == P - 1 or (0 <= b_slot[s + 1][m] < t)))
            if b_ready:
                krow[s], mrow[s] = _BWD, m
                b_slot[s][m] = t
                b_done[s] += 1
                continue
            m = f_done[s]
            f_ready = (m < M and (s == 0 or (0 <= f_slot[s - 1][m] < t))
                       and f_done[s] - b_done[s] < P - s)
            if f_ready:
                krow[s], mrow[s] = _FWD, m
                f_slot[s][m] = t
                f_done[s] += 1
        kind_rows.append(krow)
        mb_rows.append(mrow)
        t += 1
    n_slots = t
    kind = np.array(kind_rows, dtype=np.int32).T     # (P, n_slots)
    mb = np.array(mb_rows, dtype=np.int32).T

    # --- invariants -----------------------------------------------------
    # A stage's activation buffer holds microbatch m from the slot the
    # input arrives (upstream F + 1 hop; own F slot for stage 0) until its
    # backward retires it.  Live sets are contiguous microbatch ranges, so
    # a depth-D ring indexed mb % D is clobber-free iff D >= max live.
    depth = 0
    for s in range(P):
        for m in range(M):
            assert f_slot[s][m] != -1 and b_slot[s][m] != -1
            assert f_slot[s][m] <= b_slot[s][m]
            if s > 0:
                assert f_slot[s][m] > f_slot[s - 1][m]
            if s < P - 1:
                assert b_slot[s][m] > b_slot[s + 1][m]
        enter = [f_slot[0][m] if s == 0 else f_slot[s - 1][m] + 1
                 for m in range(M)]
        for tt in range(n_slots):
            live = sum(1 for m in range(M)
                       if enter[m] <= tt <= b_slot[s][m])
            depth = max(depth, live)
    assert depth <= P + 1, f"stash depth {depth} exceeds pipeline bound"
    measured = 1.0 - (2.0 * M * P) / (P * n_slots)
    return Schedule1F1B(kind=kind, mb=mb, n_slots=n_slots,
                        stash_depth=depth, measured_bubble=measured)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _pipeline_1f1b(stage_fn, axis_name, stage_params, x_microbatches):
    return _gpipe_forward(stage_fn, stage_params, x_microbatches, axis_name)


def _1f1b_fwd(stage_fn, axis_name, stage_params, x_microbatches):
    out = _gpipe_forward(stage_fn, stage_params, x_microbatches, axis_name)
    return out, (stage_params, x_microbatches)


def _1f1b_bwd(stage_fn, axis_name, residuals, g):
    """Backward replay on the 1F1B table: forwards rematerialize stage
    inputs into a rolling depth-``stash_depth`` ring, backwards consume
    them as cotangents hop back up the ring.  Every member executes both
    lanes every slot and masks by its table entry — the same masked-SPMD
    idiom as the GPipe fill/drain ticks — which keeps all collectives
    (including any inside ``stage_fn``) unconditional."""
    stage_params, x_microbatches = residuals
    n_stages = axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    n_micro = x_microbatches.shape[0]
    sched = build_1f1b_schedule(n_stages, n_micro)
    D = sched.stash_depth
    kind_tab = jnp.asarray(sched.kind)
    mb_tab = jnp.asarray(sched.mb)
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    up = (stage - 1) % n_stages
    down = (stage + 1) % n_stages

    act0 = jnp.zeros_like(x_microbatches[0])
    abuf0 = jnp.zeros((D,) + act0.shape, act0.dtype)
    cotq0 = jnp.zeros((D,) + act0.shape, g.dtype)
    dparams0 = jax.tree_util.tree_map(jnp.zeros_like, stage_params)
    dx0 = jnp.zeros_like(x_microbatches)

    def body(carry, t):
        fwd_msg, bwd_msg, abuf, cotq, dparams, dxbuf = carry
        k = kind_tab[stage, t]
        m = jnp.clip(mb_tab[stage, t], 0, n_micro - 1)
        slot = m % D

        # --- ingest last slot's hops (tables say who actually sent) -----
        tp = jnp.maximum(t - 1, 0)
        got_act = (t > 0) & (stage > 0) & (kind_tab[up, tp] == _FWD)
        m_up = jnp.clip(mb_tab[up, tp], 0, n_micro - 1)
        abuf = jnp.where(
            got_act,
            lax.dynamic_update_index_in_dim(abuf, fwd_msg, m_up % D, axis=0),
            abuf)
        got_cot = ((t > 0) & (stage < n_stages - 1)
                   & (kind_tab[down, tp] == _BWD))
        m_dn = jnp.clip(mb_tab[down, tp], 0, n_micro - 1)
        cotq = jnp.where(
            got_cot,
            lax.dynamic_update_index_in_dim(cotq, bwd_msg, m_dn % D, axis=0),
            cotq)

        # --- forward lane: rematerialize, stash the input, send down ----
        x_t = lax.dynamic_index_in_dim(x_microbatches, m, axis=0,
                                       keepdims=False)
        stashed = lax.dynamic_index_in_dim(abuf, slot, axis=0,
                                           keepdims=False)
        a_in = jnp.where(stage == 0, x_t, stashed)
        abuf = jnp.where(
            k == _FWD,
            lax.dynamic_update_index_in_dim(abuf, a_in, slot, axis=0),
            abuf)
        y = stage_fn(stage_params, a_in)

        # --- backward lane: vjp at the stashed input, send up -----------
        a_b = lax.dynamic_index_in_dim(abuf, slot, axis=0, keepdims=False)
        g_m = lax.dynamic_index_in_dim(g, m, axis=0, keepdims=False)
        cot_in = jnp.where(stage == n_stages - 1, g_m,
                           lax.dynamic_index_in_dim(cotq, slot, axis=0,
                                                    keepdims=False))
        _, vjp_fn = jax.vjp(stage_fn, stage_params, a_b)
        dp_m, da = vjp_fn(cot_in)
        is_b = (k == _BWD)
        dparams = jax.tree_util.tree_map(
            lambda acc, d: acc + jnp.where(is_b, d, jnp.zeros_like(d)),
            dparams, dp_m)
        dx_new = lax.dynamic_update_index_in_dim(dxbuf, da, m, axis=0)
        dxbuf = jnp.where(is_b & (stage == 0), dx_new, dxbuf)

        fwd_msg = lax.ppermute(y, axis_name, fwd_perm)
        bwd_msg = lax.ppermute(da, axis_name, bwd_perm)
        return (fwd_msg, bwd_msg, abuf, cotq, dparams, dxbuf), None

    carry0 = (act0, jnp.zeros_like(act0, dtype=g.dtype), abuf0, cotq0,
              dparams0, dx0)
    (_, _, _, _, dparams, dxbuf), _ = lax.scan(body, carry0,
                                               jnp.arange(sched.n_slots))
    dxbuf = jnp.where(stage == 0, dxbuf, jnp.zeros_like(dxbuf))
    return dparams, dxbuf


_pipeline_1f1b.defvjp(_1f1b_fwd, _1f1b_bwd)


def pipeline_apply_1f1b(stage_fn: Callable[[Any, jax.Array], jax.Array],
                        stage_params: Any,
                        x_microbatches: jax.Array,
                        axis_name: str) -> jax.Array:
    """Run ``stage_fn`` as a pipeline with the 1F1B backward schedule.

    Same contract as :func:`pipeline_apply`; outputs are bit-identical to
    GPipe's (the primal is the same tick loop).  Differentiating through
    it runs the Megatron 1F1B backward: activation stash bounded by the
    pipeline depth (``build_1f1b_schedule(...).stash_depth <= n_stages+1``
    microbatch inputs) instead of one residual per scan tick, at the cost
    of rematerializing stage forwards.  ``stage_params`` must be a pytree
    of inexact (float) arrays.
    """
    if x_microbatches.shape[0] < 1:
        raise ValueError("need at least one microbatch")
    return _pipeline_1f1b(stage_fn, axis_name, stage_params, x_microbatches)


def note_bubble(n_stages: int, n_micro: int, span_seconds: float) -> float:
    """Attribute the bubble share of a measured pipeline span to the
    ``pipeline_bubble`` wall component of the step-attribution engine.
    Returns the bubble seconds credited."""
    bubble = bubble_fraction(n_stages, n_micro) * max(0.0, span_seconds)
    from ..metrics import attribution
    attribution.note_pipeline_bubble(bubble)
    return bubble


def last_stage_mask(axis_name: str) -> jax.Array:
    """1.0 on the last pipeline stage, 0.0 elsewhere — for masking losses
    computed from ``pipeline_apply`` output before a psum over the axis."""
    n_stages = axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    return (stage == n_stages - 1).astype(jnp.float32)


def stack_microbatches(batch: jax.Array, n_micro: int) -> jax.Array:
    """(B, ...) -> (n_micro, B // n_micro, ...)."""
    if batch.shape[0] % n_micro != 0:
        raise ValueError(
            f"batch {batch.shape[0]} not divisible by {n_micro} microbatches")
    return batch.reshape(n_micro, batch.shape[0] // n_micro, *batch.shape[1:])


def unstack_microbatches(x: jax.Array) -> jax.Array:
    """(n_micro, mb, ...) -> (n_micro * mb, ...)."""
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])
