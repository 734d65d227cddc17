"""Request-scoped distributed tracing for the serving plane.

Every observability layer before this one is step- or rank-scoped; a
single slow request on the serving plane (queue wait? chunked-prefill
backlog? speculative misfire? KV migration?) was undiagnosable.  This
module is the request-scoped equivalent of the Horovod timeline
(arXiv:1802.05799 §5): one **trace context** — a 128-bit trace id plus
a 64-bit root span id — is minted at ``POST /serve/generate`` ingress
(or accepted from an ``x-hvd-trace`` client header) and rides the
request through every stage it crosses.  Each stage emits one span
into the existing flight-recorder ring as a ``trace.<stage>`` event
whose *name* is the trace id, so the whole request reconstructs with
one filter — ``python -m horovod_tpu.debug.merge --trace <id>`` — and
stitches across replicas (the context rides the migration bundle's
state header) on the recorder's existing clock-offset alignment.

Sampling is **seeded and deterministic**: the sample decision is a
pure function of the trace id and ``HVD_TPU_TRACE_SAMPLE`` — two
replicas (or two runs under the same seed) sample the same requests,
and an unsampled request pays one attribute check per potential span
(``tests/test_tracing.py`` holds the sampling determinism and the
tracing-on/off bit-identity).  A client header's sampled flag wins
over the local rate, so an operator can force-trace one request
without touching the knob.

Tracing NEVER touches the model math, the sampling rngs, or the
admission order — greedy outputs are bit-identical tracing-on vs
tracing-off (tests/test_tracing.py pins this).

Span taxonomy (all ``trace.*`` flight events; docs/observability.md
carries the full table): ``ingress``, ``plan``, ``admit``, ``prefix``,
``prefill``, ``decode``, ``speculate``, ``swap_stall``,
``migrate_export``, ``migrate``, ``migrate_adopt``, ``finish``,
``shed``.

Knobs: ``HVD_TPU_TRACE_SAMPLE`` (sampled fraction, default 0.01),
``HVD_TPU_TRACE_SEED`` (trace-id derivation seed, default 0) —
single-sourced in ``core/config.py``.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, Optional

from ..core import config as _config

#: The propagation header, request AND response side.  Value format:
#: ``<32-hex trace id>-<16-hex span id>-<01|00>`` (sampled flag last).
HEADER = "x-hvd-trace"

_TRACE_HEX = 32      # 128-bit trace id
_SPAN_HEX = 16       # 64-bit span id


@dataclasses.dataclass
class TraceContext:
    """One request's trace identity.  ``sampled`` gates every span —
    an unsampled context propagates (ids stay stable across replicas)
    but records nothing."""

    trace_id: str
    span_id: str
    sampled: bool = False

    def header(self) -> str:
        return (f"{self.trace_id}-{self.span_id}-"
                f"{'01' if self.sampled else '00'}")


def sample_rate() -> float:
    """The live ``HVD_TPU_TRACE_SAMPLE`` value, Config-clamped."""
    return min(1.0, max(0.0, _config.get_float(
        _config.TRACE_SAMPLE, _config.Config.trace_sample)))


def trace_seed() -> int:
    return _config.get_int(_config.TRACE_SEED, _config.Config.trace_seed)


def derive_trace_id(request_id: str, seed: Optional[int] = None) -> str:
    """Deterministic 128-bit trace id: a hash of (seed, request id).
    Same seed + same id → same trace id on every replica — the
    property the cross-replica stitch and the seeded-sampling
    determinism tests rely on."""
    if seed is None:
        seed = trace_seed()
    h = hashlib.sha256(f"{seed}:{request_id}".encode()).hexdigest()
    return h[:_TRACE_HEX]


def derive_span_id(trace_id: str, stage: str, seq: int = 0) -> str:
    h = hashlib.sha256(f"{trace_id}:{stage}:{seq}".encode()).hexdigest()
    return h[:_SPAN_HEX]


def sampled(trace_id: str, rate: Optional[float] = None) -> bool:
    """Pure sampling decision: the trace id's top 64 bits against the
    rate threshold.  rate=0 samples nothing, rate=1 everything."""
    if rate is None:
        rate = sample_rate()
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    return int(trace_id[:16], 16) < int(rate * float(1 << 64))


def parse_header(value: Optional[str]) -> Optional[TraceContext]:
    """``x-hvd-trace`` value → context; None on anything malformed (a
    bad client header must never 500 the ingress — the request just
    gets a locally-minted context instead)."""
    if not value:
        return None
    parts = value.strip().lower().split("-")
    if len(parts) != 3:
        return None
    tid, sid, flag = parts
    if len(tid) != _TRACE_HEX or len(sid) != _SPAN_HEX \
            or flag not in ("00", "01"):
        return None
    try:
        int(tid, 16), int(sid, 16)
    except ValueError:
        return None
    return TraceContext(trace_id=tid, span_id=sid,
                        sampled=(flag == "01"))


def mint(request_id: str, header: Optional[str] = None,
         rate: Optional[float] = None,
         seed: Optional[int] = None) -> TraceContext:
    """The ingress entry point: honor a client ``x-hvd-trace`` header
    (its sampled flag wins — forced traces need no knob change), else
    derive a deterministic context and apply the seeded sampling
    decision."""
    ctx = parse_header(header)
    if ctx is not None:
        return ctx
    tid = derive_trace_id(request_id, seed=seed)
    return TraceContext(trace_id=tid,
                        span_id=derive_span_id(tid, "root"),
                        sampled=sampled(tid, rate=rate))


def span(ctx: Optional[TraceContext], stage: str, **fields) -> None:
    """Emit one span as a ``trace.<stage>`` flight event named by the
    trace id.  No-op (one None/flag check) when the context is absent
    or unsampled."""
    if ctx is None or not ctx.sampled:
        return
    from ..debug import flight
    flight.record(f"trace.{stage}", ctx.trace_id,
                  span=derive_span_id(ctx.trace_id, stage),
                  parent=ctx.span_id, **fields)


def to_state(ctx: Optional[TraceContext]) -> Optional[Dict[str, Any]]:
    """Context → the JSON-safe dict that rides the KV-migration
    bundle's state header (disagg.encode_bundle)."""
    if ctx is None:
        return None
    return {"trace_id": ctx.trace_id, "span_id": ctx.span_id,
            "sampled": bool(ctx.sampled)}


def from_state(d: Optional[Dict[str, Any]]) -> Optional[TraceContext]:
    if not isinstance(d, dict) or not d.get("trace_id"):
        return None
    tid = str(d["trace_id"]).lower()
    try:
        if len(tid) != _TRACE_HEX:
            return None
        int(tid, 16)
    except ValueError:
        # A corrupted wire header must never mint a bogus trace.
        return None
    return TraceContext(trace_id=tid,
                        span_id=str(d.get("span_id") or
                                    derive_span_id(tid, "root")),
                        sampled=bool(d.get("sampled")))
