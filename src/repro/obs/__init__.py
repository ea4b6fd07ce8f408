"""Engine-wide telemetry (DESIGN.md §10).

Three pieces, importable with zero heavy dependencies (no jax here — the
engines import *us*):

* :mod:`repro.obs.metrics` — labeled counter/gauge/histogram registry with
  cheap thread-safe increments, ``snapshot()``, and Prometheus text
  exposition; global kill switch ``REPRO_OBS=off``.
* :mod:`repro.obs.tracing` — :class:`TraceRing`, the bounded event log
  behind the ONLINE-UNION φ-trajectory tracer; :func:`span`, the profiler
  spans the engine and serve tier open under ``REPRO_OBS_TRACE=1`` (every
  name in :data:`SPANS`); and the device loop's phase scopes
  (:data:`LOOP_PHASES`, :func:`phase_of`, :func:`hlo_op_phases`).
* :mod:`repro.obs.http` — :class:`MetricsServer`, the background HTTP
  thread serving ``/metrics`` (Prometheus text) and ``/healthz``.

Instrumented layers: the persistent device loop carries per-piece round
counters in its jitted carry (``JaxUnionSampler.piece_stats``), the sharded
loop derives the same counters from its water-filling exchange, ONLINE-UNION
appends φ-refresh/backtrack events to its trace ring, and the serve tier
records request-latency, queue-wait and assembly histograms, queue depth,
and per-replica merged ``SamplerStats``; engine and serve tier open the
profiler spans of :data:`SPANS` under ``REPRO_OBS_TRACE=1``, and the device
loop names its phases with ``jax.named_scope``.  All of it is on by default
(the spans are not) and disabled end-to-end by ``REPRO_OBS=off`` (sampling
output is bit-identical either way — the switch only gates host-side
timers, spans and registry publication).
"""

from .http import MetricsServer, PROMETHEUS_CONTENT_TYPE
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      default_latency_buckets, enabled, get_registry,
                      set_enabled, set_registry, trace_annotations_enabled)
from .tracing import (LOOP_PHASES, PIECE_PHASES, SPANS, UNSCOPED, TraceRing,
                      hlo_op_phases, op_phases, phase_of, publish_op_phases,
                      span)

__all__ = [
    "Counter", "Gauge", "Histogram", "LOOP_PHASES", "MetricsRegistry",
    "MetricsServer", "PIECE_PHASES", "PROMETHEUS_CONTENT_TYPE", "SPANS",
    "TraceRing", "UNSCOPED", "default_latency_buckets", "enabled",
    "fallback_events", "get_registry", "hlo_op_phases", "op_phases",
    "phase_of", "publish_op_phases", "record_fallback", "set_enabled",
    "set_registry", "span", "trace_annotations_enabled",
]

# ---------------------------------------------------------------------------
# Engine fallback telemetry: every point where a device/fused path degrades
# to the host engine increments repro_engine_fallback_total{reason=...} and
# appends a TraceRing event — warnings are once-only and invisible to
# scrapes; this is the queryable record of "why was this run slow".
# ---------------------------------------------------------------------------

_fallback_trace = TraceRing(capacity=256)

_FALLBACK_HELP = ("Times a fused/device engine path degraded to the host "
                  "engine, by reason")


def record_fallback(reason: str, detail: str = "", join: str = "") -> None:
    """Record one engine degrade-to-host event.

    ``reason`` is the stable low-cardinality label (e.g.
    ``predicate_unsupported``, ``int32_domain``, ``join_method``,
    ``strict_paper_loop``, ``host_oracle``); ``detail``/``join`` carry the
    free-form context into the trace ring only.
    """
    if not enabled():
        return
    get_registry().counter("repro_engine_fallback_total", _FALLBACK_HELP,
                           ("reason",)).labels(reason=reason).inc()
    _fallback_trace.append("engine_fallback", reason=reason, detail=detail,
                           join=join)


def fallback_events():
    """The recent engine-fallback events (newest last)."""
    return _fallback_trace.events()
