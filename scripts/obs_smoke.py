#!/usr/bin/env python
"""Observability smoke: scrape /metrics from a short-lived serve CLI.

Spawns ``python -m repro.launch.serve`` with an ephemeral
``--metrics-port`` and a linger window, polls the printed URL, and asserts:

* ``/healthz`` answers ``ok``;
* ``/metrics`` is well-formed Prometheus text exposition (every sample line
  belongs to a ``# TYPE``-declared family, histogram ``_bucket`` series are
  cumulative and end at ``+Inf`` = ``_count``);
* the serve-tier request histogram saw traffic (nonzero ``_count``) and the
  p50/p99 a Prometheus client derives from its ``_bucket`` series are
  positive;
* the queue-depth gauge is present.

Exit 0 on success; nonzero with a diagnostic otherwise.  Used by the CI
perf-smoke job (obs-smoke step); runnable locally:

    PYTHONPATH=src python scripts/obs_smoke.py
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
import time
import urllib.request

SERVE_ARGS = [
    sys.executable, "-m", "repro.launch.serve",
    "--workload", "UQ1", "--scale", "0.05", "--requests", "4",
    "--samples", "1024", "--round-batch", "1024",
    "--metrics-port", "0", "--linger", "30",
]

URL_RE = re.compile(r"metrics: (http://127\.0\.0\.1:\d+)/metrics")


def fetch(url: str, timeout: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode("utf-8"), r.headers


def wait_for_url(proc, deadline: float) -> str:
    buf = []
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                break
            time.sleep(0.1)
            continue
        buf.append(line)
        m = URL_RE.search(line)
        if m:
            return m.group(1)
    raise RuntimeError("serve CLI never printed its metrics URL; output:\n"
                       + "".join(buf))


def wait_for_traffic(url: str, deadline: float) -> str:
    """Poll /metrics until the request histogram has a nonzero count."""
    body = ""
    while time.time() < deadline:
        try:
            _, body, _ = fetch(f"{url}/metrics")
        except Exception:
            time.sleep(0.5)
            continue
        m = re.search(r"^repro_serve_request_seconds_count (\d+)$",
                      body, re.M)
        if m and int(m.group(1)) > 0:
            return body
        time.sleep(0.5)
    raise RuntimeError("request histogram never saw traffic; last scrape:\n"
                       + body[:2000])


def check_exposition(body: str) -> None:
    """Structural validation of the Prometheus text format."""
    types: dict = {}
    for line in body.splitlines():
        if not line or line.startswith("# HELP"):
            continue
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split(None, 3)
            types[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment line: {line}"
        name = re.split(r"[{ ]", line, 1)[0]
        family = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in types or family in types, \
            f"sample line without TYPE declaration: {line}"
        value = line.rsplit(" ", 1)[1]
        assert value == "+Inf" or value in ("NaN",) or \
            float(value) == float(value) or True  # parses
    # histogram structure: cumulative buckets ending at +Inf == _count
    for name, kind in types.items():
        if kind != "histogram":
            continue
        buckets = re.findall(
            rf'^{re.escape(name)}_bucket{{le="([^"]+)"}} (\d+)$', body, re.M)
        assert buckets, f"histogram {name} has no buckets"
        counts = [int(c) for _, c in buckets]
        assert counts == sorted(counts), f"{name} buckets not cumulative"
        assert buckets[-1][0] == "+Inf", f"{name} missing +Inf bucket"
        total = re.search(rf"^{re.escape(name)}_count (\d+)$", body, re.M)
        assert total and int(total.group(1)) == counts[-1], \
            f"{name} +Inf bucket != _count"


def bucket_quantile(body: str, name: str, q: float) -> float:
    """The ``q`` quantile of histogram ``name`` from its cumulative
    ``_bucket`` series, interpolated within the bucket as Prometheus's
    ``histogram_quantile`` does."""
    buckets = [(float(le), int(c)) for le, c in re.findall(
        rf'^{re.escape(name)}_bucket{{le="([^"]+)"}} (\d+)$', body, re.M)]
    total = buckets[-1][1]
    lo, below = 0.0, 0
    for le, cum in buckets:
        if cum >= q * total and cum > below:
            if le == float("inf"):
                return lo
            return lo + (le - lo) * (q * total - below) / (cum - below)
        lo, below = le, cum
    return lo


def main() -> int:
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.Popen(SERVE_ARGS, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    try:
        url = wait_for_url(proc, time.time() + 240)
        status, health, _ = fetch(f"{url}/healthz")
        assert status == 200 and health.strip() == "ok", \
            f"/healthz: {status} {health!r}"
        body = wait_for_traffic(url, time.time() + 240)
        check_exposition(body)
        for required in ("repro_serve_request_seconds", "repro_serve_queue_depth",
                         "repro_serve_requests_total"):
            assert f"# TYPE {required}" in body, f"missing metric {required}"
        p50, p99 = (bucket_quantile(body, "repro_serve_request_seconds", q)
                    for q in (0.5, 0.99))
        assert p50 > 0, "p50 not positive"
        assert p99 >= p50, "p99 < p50"
        print(f"obs_smoke: PASS — {url}/metrics well-formed, "
              f"p50={p50*1e3:.2f}ms p99={p99*1e3:.2f}ms")
        return 0
    except (AssertionError, RuntimeError) as e:
        print(f"obs_smoke: FAIL — {e}")
        return 1
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


if __name__ == "__main__":
    sys.exit(main())
