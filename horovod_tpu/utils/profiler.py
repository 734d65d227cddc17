"""Profiler trace ranges — the TPU-native analog of NVTX op ranges.

The reference wraps every enqueued collective in an NVTX range so Nsight
shows per-op spans (nvtx_op_range.h, operations.cc:1018-1033).  On TPU the
profiler is XProf/TensorBoard;
``jax.profiler.TraceAnnotation`` plays NVTX's role: annotated spans appear
on the host timeline of a captured trace alongside the device steps.

* ``host_span(name)`` — one span on the host timeline, on the device
  trace's clock; what ``op_range`` and the pause sentinel
  (``debug/pause.py``: ``hvd.gc.gen<n>``, ``hvd.tick``) are made of.
* ``op_range(name, payload_bytes=…)`` — context manager for one collective.
* ``start_trace(logdir)`` / ``stop_trace()`` — programmatic capture, the
  analog of ``hvd.start_timeline``/``stop_timeline`` for device profiles
  (the Chrome-trace Timeline of the native runtime is separate and remains
  the coordinator-side view).

* ``scope(name)`` — names a block of the *compiled* training step
  (``STEP_SCOPES``, ``MOE_SCOPES``, ``SSM_SCOPES``,
  ``LATENT_MOE_SCOPES``, ``ATTN_PART_SCOPES``, ``ATTN_OPERAND_SCOPES``,
  ``INDEX_SCOPES``, ``DENSE_MLP_SCOPE``, ``CONV_SCOPES``,
  ``TP_RING_SCOPES``, ``LAYERS_SCOPE``): a
  ``jax.named_scope``, so the name
  lands in every HLO operation's ``op_name`` and from there in a device
  profile.

No knob (the reference's ``HOROVOD_DISABLE_NVTX_RANGES``, common.h:96, has
no analog): a ``TraceAnnotation`` costs nothing worth naming while no trace
is being taken, and ``scope`` has no run-time cost at all.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional


def host_span(name: str):
    """A ``jax.profiler.TraceAnnotation``: entered and left (``with``, or
    ``__enter__`` / ``__exit__`` on one thread) it is a span on the host
    timeline of whatever trace is being taken, on the clock the device
    planes share; with no trace being taken it is a flag test.  None where
    the profiler cannot be had: profiling must never break what it
    names."""
    try:
        import jax.profiler as _prof
        return _prof.TraceAnnotation(name)
    except Exception:
        return None


@contextlib.contextmanager
def op_range(name: str, payload_bytes: Optional[int] = None):
    """Annotate one collective on the profiler timeline.  Cheap no-op when
    no trace is being captured.

    Only annotation *setup* is guarded — exceptions raised by the wrapped
    block must propagate untouched (a swallowed yield would mask every
    eager-collective failure behind a generator error)."""
    ann = host_span(name if payload_bytes is None
                    else f"{name}#bytes={payload_bytes}")
    if ann is None:
        yield
    else:
        with ann:
            yield


# The blocks of a compiled training step that ``models/transformer.py`` and
# ``models/bert.py`` name, each as ``scope(<name>)``.
STEP_SCOPES = ("embed", "attn", "mlp", "head", "optimizer")
# The parts of a dropless MoE MLP (``parallel/moe.dropless_moe``), named
# inside the ``mlp`` block: the router up to the sorted order, the rows
# gathered into expert order and put back, the grouped matmuls.
MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts")
# A patterned model's state-space block (``models/transformer._ssm_mixer``)
# — it stands beside ``attn`` and ``mlp`` as a block of the step — and
# inside it the causal conv and the chunked scan.
SSM_SCOPES = ("ssm", "ssm_conv", "ssm_scan")
# Inside the ``mlp`` block of a latent-space expert layer: both latent
# projections, and the shared expert.
LATENT_MOE_SCOPES = ("moe_latent", "moe_shared")
# Inside the ``attn`` block of a patterned model: the rotary positions of q
# and k, the per-head gate on the attention's output, and the per-head
# RMSNorm of q and k (``head_qk_norm``).
ATTN_PART_SCOPES = ("attn_rope", "attn_gate", "attn_qknorm")
# Inside the ``attn`` block, what XLA does to make the flash kernels'
# operands: each K / V head repeated across its query heads
# (``models/transformer._gqa_mixer``), and ``delta`` = sum(dO * O) with its
# broadcast to eight sublanes (``ops/flash_attention.py``, backward only).
ATTN_OPERAND_SCOPES = ("attn_kv_repeat", "attn_delta")
# Inside the ``attn`` block of learned sparse attention: the indexer (its
# projections, its scores, the choice), inside that the ranking alone, and
# the indexer's loss, forward and backward (``ops/sparse_index.py``).
INDEX_SCOPES = ("attn_index", "attn_select", "attn_index_loss")
# A patterned model's gated dense MLP block, inside ``mlp`` (what is left of
# ``mlp`` is then the expert blocks').
DENSE_MLP_SCOPE = "mlp_dense"
# A patterned model's gated short-convolution block
# (``models/transformer._conv_mixer``), a block of the step as ``ssm`` is,
# and inside it everything between its two matmuls: both gates and the
# depthwise convolution.
CONV_SCOPES = ("conv", "conv_gate")
# Around the ``lax.scan`` over the layer stack (and a pattern's leading
# blocks): the blocks carry it beside their own names, and what carries it
# alone is the scan's own work: the saved stacks written and read a layer at
# a time, the per-layer weight slices, the gradient stacks.
LAYERS_SCOPE = "layers"
# Inside ``attn`` and ``mlp`` over more than one ``mp`` member: the two forms
# of ``parallel/tensor_parallel.py``'s ring, whose collective-permutes a
# profile then shows by block, phase and form.
TP_RING_SCOPES = ("tp_ring_gather", "tp_ring_scatter")


def scope(name: str):
    """``jax.named_scope("hvd_" + name)`` around a block of a traced
    function: ``hvd_<name>`` becomes a component of the ``op_name`` of every
    HLO operation the block lowers to (under AD it can also sit inside
    ``jvp(...)`` / ``transpose(...)``), which a device profile shows as the
    operation's ``tf_op``.  It exists only while tracing, costs nothing when
    the compiled step runs."""
    import jax
    return jax.named_scope("hvd_" + name)


class _WaitSpan:
    """Filled in when the ``data_wait`` block exits."""

    seconds: float = 0.0


_dw_metrics = None
_dw_lock = threading.Lock()


def _data_wait_metrics():
    """The registry-backed storage of the data-wait stats (the private
    module dict this module used to keep now lives in ``hvd.metrics``,
    so the cross-rank aggregation and the Prometheus surface see the
    same numbers ``data_wait_stats()`` reports)."""
    global _dw_metrics
    if _dw_metrics is None:
        with _dw_lock:
            if _dw_metrics is None:
                from ..metrics.registry import (DEFAULT_TIME_BUCKETS,
                                                registry)
                reg = registry()
                _dw_metrics = (
                    reg.counter("hvd_data_wait_seconds_total",
                                "Cumulative input-pipeline wait"),
                    reg.counter("hvd_data_wait_spans_total",
                                "Number of input-pipeline wait spans"),
                    reg.gauge("hvd_data_wait_last_seconds",
                              "Most recent input-pipeline wait"),
                    reg.histogram("hvd_data_wait_seconds",
                                  "Input-pipeline wait per span",
                                  buckets=DEFAULT_TIME_BUCKETS),
                )
    return _dw_metrics


@contextlib.contextmanager
def data_wait(name: str = "data_wait"):
    """Annotate + time one step's blocking wait on the input pipeline.

    The span shows up on the profiler host timeline (same mechanism as
    ``op_range``) so an input-bound step is visually distinct from a
    compute-bound one, and the duration feeds the ``hvd_data_wait_*``
    metrics in the ``hvd.metrics`` registry — the same counters
    ``data_wait_stats()`` reports and the straggler detector reads.
    Yields a :class:`_WaitSpan` whose ``seconds`` is set on exit."""
    span = _WaitSpan()
    t0 = time.perf_counter()
    try:
        with op_range(name):
            yield span
    finally:
        span.seconds = time.perf_counter() - t0
        total, count, last, hist = _data_wait_metrics()
        total.inc(span.seconds)
        count.inc()
        last.set(span.seconds)
        hist.observe(span.seconds)


def data_wait_stats() -> dict:
    """Snapshot of cumulative data-wait spans: count / total_s / last_s
    (+ derived mean_s).  Backed by the ``hvd.metrics`` registry
    (``hvd_data_wait_*``); reset with :func:`reset_data_wait_stats`."""
    total, count, last, _hist = _data_wait_metrics()
    out = {"count": int(count.value), "total_s": total.value,
           "last_s": last.value}
    out["mean_s"] = out["total_s"] / out["count"] if out["count"] else 0.0
    return out


def reset_data_wait_stats() -> None:
    for metric in _data_wait_metrics():
        metric.reset()


def start_trace(logdir: str) -> None:
    """Begin capturing an XProf device+host trace into ``logdir``."""
    import jax.profiler as _prof
    _prof.start_trace(logdir)


def stop_trace() -> None:
    import jax.profiler as _prof
    _prof.stop_trace()


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a trace for the duration of the block."""
    start_trace(logdir)
    try:
        yield
    finally:
        stop_trace()
