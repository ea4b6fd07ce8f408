"""Serving front-end: streaming union-sample service.

:class:`SampleService` wraps any union sampling engine (host, fused device,
mesh-sharded) with a prefetched sample queue and request batching; the serve
CLI (``python -m repro.launch.serve --mode samples``) and
``examples/long_context_serving.py`` route through it.

The serve tier is instrumented (DESIGN.md §10): histograms of request
latency, of its wait on the prefetch queue and of its assembly,
queue-depth/prefetch-occupancy gauges, and per-replica merged
``SamplerStats``, all in the ``repro_serve_*`` namespace, plus the
``repro/serve/*`` profiler spans under ``REPRO_OBS_TRACE=1``.
``python -m repro.launch.serve --mode samples --metrics-port P`` exposes
them at ``http://127.0.0.1:P/metrics`` (Prometheus text exposition) with a
``/healthz`` liveness probe; ``REPRO_OBS=off`` switches it all off.
"""

from .service import SampleService

__all__ = ["SampleService"]
