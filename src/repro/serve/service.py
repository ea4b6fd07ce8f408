"""Streaming union-sample service — the serving front-end over the engines.

:class:`SampleService` turns any union sampler (host, fused device, or
mesh-sharded — anything with ``sample(n) -> SampleSet``) into a streaming
source for serving traffic:

* **prefetched sample queue** — one producer thread per engine keeps a
  bounded queue of fixed-size sample batches warm, so request latency is a
  queue pop, not an engine round.  Because probe-mode samples are i.i.d.
  ``1/|U|`` draws, any contiguous slice of the prefetched stream is itself a
  valid uniform sample — slicing batches across requests is free.
* **request batching** — concurrent ``request(n)`` calls drain the shared
  stream under a cursor lock; the engine only ever runs its own
  (device-optimal) ``batch``-sized rounds regardless of per-request sizes,
  which is exactly what the fused/sharded engines' surplus banking is built
  for.
* **replicas** — pass several engines (e.g. seed-split replicas, one per
  host or per mesh) and their streams interleave into one queue; per-engine
  cost accounting combines with :meth:`SamplerStats.merge`.
* **telemetry** — every ``request()`` lands in the
  ``repro_serve_request_seconds`` latency histogram, with its time blocked
  on the prefetch queue (``repro_serve_queue_wait_seconds``) and in
  concatenating the answer (``repro_serve_assemble_seconds``) as
  histograms of their own, request/sample counters, a queue-depth /
  prefetch-occupancy gauge, and per-replica merged ``SamplerStats`` gauges;
  ``python -m repro.launch.serve --mode samples --metrics-port P`` exposes
  all of it on ``http://127.0.0.1:P/metrics`` (Prometheus text) next to a
  ``/healthz`` liveness probe.  ``REPRO_OBS=off`` disables it.  Under
  ``REPRO_OBS_TRACE=1`` a request opens the profiler span
  ``repro/serve/request`` (its ``batches``: the ids of the prefetched
  batches it consumed) around ``repro/serve/lock_wait``,
  ``repro/serve/queue_wait`` and ``repro/serve/assemble``; a producer
  blocked on a full queue opens ``repro/serve/put_wait``.

``python -m repro.launch.serve --mode samples`` and
``examples/long_context_serving.py`` route through this class.
"""

from __future__ import annotations

import contextlib
import itertools
import queue
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .. import obs
from ..core.union_sampler import SampleSet, SamplerStats


class SampleService:
    """Prefetching, request-batching facade over one or more sample engines."""

    def __init__(self, samplers, batch: int = 4096, prefetch: int = 2,
                 registry=None):
        if not isinstance(samplers, (list, tuple)):
            samplers = [samplers]
        if not samplers:
            raise ValueError("SampleService needs at least one engine")
        self.samplers = list(samplers)
        self.batch = int(batch)
        self.prefetch = int(prefetch)
        self.attrs = list(self.samplers[0].attrs)
        # (batch id, batch): ids number the batches in production order
        self._queue: "queue.Queue[Tuple[int, SampleSet]]" = queue.Queue(
            maxsize=max(self.prefetch, 1))
        self._batch_ids = itertools.count()
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._threads: List[threading.Thread] = []
        self._cursor: Optional[SampleSet] = None    # partially drained batch
        self._cursor_id = -1
        self._cursor_pos = 0
        self._lock = threading.Lock()               # request serialisation
        self.served = 0
        self._registry = registry                   # None ⇒ global registry
        self._obs_m: Optional[Dict] = None
        self._collector = None

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "SampleService":
        """Spawn the producer threads.  A service is single-use: once
        stopped it cannot restart (a producer may still be inside a long
        engine round when ``stop`` returns, and the engines are not
        thread-safe — build a fresh service instead)."""
        if self._threads:
            return self
        if self._stop.is_set():
            raise RuntimeError("SampleService is single-use: build a new "
                               "service instead of restarting a stopped one")
        if obs.enabled():
            self._obs_handles()
        for i, s in enumerate(self.samplers):
            t = threading.Thread(target=self._produce, args=(s,),
                                 name=f"sample-producer-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self) -> None:
        self._stop.set()
        # unblock producers waiting on a full queue
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        for t in self._threads:
            t.join(timeout=5)
        self._threads = []
        if self._collector is not None:     # single-use: stop scraping us
            reg, fn = self._collector
            fn()        # final engine-stat refresh (producers quiesced)
            reg.remove_collector(fn)
            self._collector = None

    def __enter__(self) -> "SampleService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -------------------------------------------------------------- producer
    def _produce(self, sampler) -> None:
        """Keep the queue warm with ``batch``-sized sample sets.

        Engines exposing ``sample_async`` get double-buffered round
        dispatch: batch *k+1* is launched before batch *k* is drained, so
        the host-side assembly (fetch, shuffle, fingerprint) of one batch
        hides behind the device compute of the next — the fused device
        loop's top-up latency never stalls the queue.  Plain engines fall
        back to the synchronous path.
        """
        dispatch = getattr(sampler, "sample_async", None)
        pending = None
        while not self._stop.is_set():
            try:
                if dispatch is None:
                    ss = sampler.sample(self.batch)
                else:
                    if pending is None:
                        pending = dispatch(self.batch)
                    nxt = dispatch(self.batch)     # in flight while we drain
                    ss = pending.result()
                    pending = nxt
            except BaseException as e:        # surfaced on the next request
                self._error = e
                self._stop.set()
                return
            item = (next(self._batch_ids), ss)
            try:
                self._queue.put_nowait(item)
                continue
            except queue.Full:
                pass
            with obs.span("repro/serve/put_wait", batch=item[0]):
                while not self._stop.is_set():
                    try:
                        self._queue.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue

    # -------------------------------------------------------------- consumer
    def _next_batch(self, timeout: float) -> Tuple[int, SampleSet]:
        while True:
            if self._error is not None:
                raise RuntimeError("sample producer failed") from self._error
            try:
                return self._queue.get(timeout=min(timeout, 0.2))
            except queue.Empty:
                timeout -= 0.2
                if timeout <= 0:
                    raise TimeoutError(
                        "SampleService.request timed out (engine too slow "
                        "for the requested size, or service not started)")

    # ------------------------------------------------------------- telemetry
    def _obs_handles(self) -> Dict:
        """Serve-tier metric handles (get-or-create in the registry); the
        queue-depth gauge and the per-replica stat gauges refresh at scrape
        time (the latter via a registry collector, removed again on
        stop)."""
        if self._obs_m is None:
            reg = (self._registry if self._registry is not None
                   else obs.get_registry())
            m = {
                "latency": reg.histogram(
                    "repro_serve_request_seconds",
                    "end-to-end SampleService.request latency"),
                "queue_wait": reg.histogram(
                    "repro_serve_queue_wait_seconds",
                    "part of a request blocked on the prefetch queue"),
                "assemble": reg.histogram(
                    "repro_serve_assemble_seconds",
                    "part of a request concatenating its answer"),
                "requests": reg.counter(
                    "repro_serve_requests_total",
                    "sample requests served"),
                "samples": reg.counter(
                    "repro_serve_samples_total",
                    "union samples handed out by the serve tier"),
                "queue": reg.gauge(
                    "repro_serve_queue_depth",
                    "prefetch queue occupancy (batches ready to serve)"),
                "capacity": reg.gauge(
                    "repro_serve_prefetch_capacity",
                    "prefetch queue capacity (batches)"),
                "engine": reg.gauge(
                    "repro_serve_engine_stat",
                    "per-replica engine SamplerStats fields",
                    labelnames=("replica", "field")),
            }
            m["queue"].set_function(self._queue.qsize)
            m["capacity"].set(self._queue.maxsize)

            def collect():
                for i, s in enumerate(self.samplers):
                    for field, v in s.stats.as_dict().items():
                        m["engine"].labels(str(i), field).set(v)
                    # derived waste ratio: candidate draws per emitted sample
                    m["engine"].labels(str(i), "psi").set(s.stats.psi())

            reg.add_collector(collect)
            self._collector = (reg, collect)
            self._obs_m = m
        return self._obs_m

    def request(self, n: int, timeout: float = 120.0) -> SampleSet:
        """Blocking request for ``n`` uniform union samples."""
        if not self._threads:
            raise RuntimeError("SampleService not started (use start() or a "
                               "with-block)")
        t0 = time.perf_counter() if obs.enabled() else None
        if n <= 0:
            from ..core.union_sampler import empty_sample_set
            return empty_sample_set(self.attrs, self.stats())
        with obs.span("repro/serve/request", n=n) as span:
            parts, batches, waited = self._take(n, timeout)
            # TraceMe metadata is `k=v,k=v`: the ids are joined with `|`
            span.set_metadata(batches="|".join(map(str, batches)))
            t_asm = time.perf_counter() if t0 is not None else None
            with obs.span("repro/serve/assemble"):
                rows = {a: np.concatenate([p.rows[a] for p in parts])
                        for a in self.attrs}
                home = np.concatenate([p.home for p in parts])
                fp = np.concatenate([p.fingerprint for p in parts])
                out = SampleSet(self.attrs, rows, home, fp, self.stats())
        if t0 is not None:
            t1 = time.perf_counter()
            m = self._obs_handles()
            m["latency"].observe(t1 - t0)
            m["queue_wait"].observe(waited)
            m["assemble"].observe(t1 - t_asm)
            m["requests"].inc()
            m["samples"].inc(len(out))
        return out

    def _take(self, n: int, timeout: float
              ) -> Tuple[List[SampleSet], List[int], float]:
        """Slice ``n`` samples off the shared stream under the request lock:
        the slices (views), the ids of the batches they come from, and the
        seconds spent blocked on the prefetch queue."""
        parts: List[SampleSet] = []
        batches: List[int] = []
        waited = 0.0
        got = 0
        lock_wait = contextlib.ExitStack()
        with lock_wait:
            lock_wait.enter_context(obs.span("repro/serve/lock_wait"))
            with self._lock:
                lock_wait.close()           # the wait ends with the lock held
                while got < n:
                    if self._cursor is None:
                        tq = time.perf_counter()
                        with obs.span("repro/serve/queue_wait"):
                            self._cursor_id, self._cursor = \
                                self._next_batch(timeout)
                        waited += time.perf_counter() - tq
                        self._cursor_pos = 0
                    cur, lo = self._cursor, self._cursor_pos
                    hi = min(lo + n - got, len(cur))
                    parts.append(SampleSet(
                        cur.attrs, {a: c[lo:hi] for a, c in cur.rows.items()},
                        cur.home[lo:hi], cur.fingerprint[lo:hi], cur.stats))
                    batches.append(self._cursor_id)
                    got += hi - lo
                    if hi >= len(cur):
                        self._cursor = None
                    else:
                        self._cursor_pos = hi
                self.served += got
        return parts, batches, waited

    def stats(self) -> SamplerStats:
        """Merged cost accounting across all engines (associative merge)."""
        out = SamplerStats()
        for s in self.samplers:
            out.merge(s.stats)
        return out
