#!/usr/bin/env python3
"""Chip smoke test: the union sampler's main path, end to end, on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # sharded engine on four chips

One chip.  The main phase builds UQ1 (five chain joins
nation ⋈ supplier ⋈ customer ⋈ orders ⋈ lineitem, default overlap 0.2) at
TPC-H SF1 row counts (``scale=100``: 6,000,000 base lineitem rows) through
the builder ``repro.launch.serve`` uses (histogram warm-up,
union estimate, ``SetUnionSampler(backend="jax")`` with its persistent
device loop), then serves 8 requests of 4,096 samples through
``SampleService``.  A small phase then draws UQ1 (2 joins) and cyclic UQ4
at ``scale=1`` through the same path and tests their uniformity against
exact enumeration of the union.

Four chips.  ``--four-chips`` runs only the sharded engine on a 4-device
mesh over the same SF1 union and, as its comparison, the unsharded engine.

Every check is fatal: the run exits non-zero and prints no result line.
The checks are that the device is a TPU (there is no CPU fallback); that no
join degraded to the host and no engine fallback fired; that the compiled
sampling loop holds the Pallas probes (``tpu_custom_call``) and, on four
chips, the per-round all-gather and reduce-scatter; that every served sample
is a member of the union in its home cover piece and in no earlier piece,
by the numpy reference engine's membership oracle; the chi-square
uniformity of the small phase; and, on four chips, that the membership
shards sit on four devices and the home-piece shares agree with the
unsharded engine.

Times printed are single unrepeated readings of one smoke run.  The last
line of standard output is the JSON result.  The script runs in one process
and starts none.  Compiled programs go to JAX's persistent compilation
cache (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` here).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SF1_SCALE = 100          # data/tpch.py BASES are SF 0.01 at scale=1
ROUND_BATCH = 8192       # the serve default
REQUESTS, REQUEST_SAMPLES = 8, 4096
SMALL_SAMPLES_PER_TUPLE = 60
P_MIN = 1e-3             # chi-square floor, as in tests/test_device_rounds.py


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


class CompileClock:
    """Seconds JAX spends in backend compilation, persistent-cache reads
    included (JAX's ``backend_compile_duration`` monitoring event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration


def check_no_fallbacks(sampler) -> None:
    from repro import obs
    check(not sampler.backend.degraded,
          f"joins degraded to host draws: {sampler.backend.degraded}")
    counter = obs.get_registry().get("repro_engine_fallback_total")
    series = counter.snapshot() if counter is not None else {}
    check(all(v == 0 for v in series.values()),
          f"engine fallbacks fired: {series}")
    check(not obs.fallback_events(),
          f"engine fallback events: {obs.fallback_events()}")


def reference_oracle(wl):
    """The numpy reference engine's membership oracle for a workload."""
    from repro.core.backends import get_backend
    return get_backend("numpy", wl.cat, wl.joins).oracle()


def check_home_membership(oracle, order, rows, home) -> None:
    """Every sample is in its home cover piece and in no earlier piece."""
    import numpy as np
    mat = oracle.membership_matrix(rows, order)
    home = np.asarray(home)
    n = home.shape[0]
    check(bool(mat[np.arange(n), home].all()),
          "samples outside their home piece (not in the union)")
    earlier = np.arange(len(order))[None, :] < home[:, None]
    check(not bool((mat & earlier).any()),
          "samples also in an earlier cover piece (non-canonical home)")


def lowered_loop(eng, n: int):
    """The device loop of a ``sample(n)`` call, lowered with that call's
    arguments (its compile is the one the call made: a persistent-cache
    hit)."""
    import jax.numpy as jnp
    C = 1 << max(10, (n - 1).bit_length())
    loop = eng._loop_for(C)
    if hasattr(loop, "_prog"):          # sharded: the shard_map'd program
        st = eng._dev_state
        shr = {k: st[k] for k in ("bank", "bank_head", "bank_count")}
        rep = {k: st[k] for k in loop._rep_keys}
        args = (shr, rep, eng._out_buffer(C), jnp.int32(n), eng._probs_base,
                eng._catalog_args())
        return loop._prog.lower(*args)
    return loop.lower(eng._dev_state, eng._out_buffer(C), jnp.int32(n),
                      eng._probs_base, eng._catalog_args())


def build(jax, clock, *, shards: int = 0, **kw):
    """Build one SF1 union sampler; report set-up and compile seconds."""
    from repro.launch.serve import build_sampler
    t0 = time.perf_counter()
    wl, sampler = build_sampler("UQ1", SF1_SCALE, seed=kw["seed"],
                                round_batch=ROUND_BATCH, shards=shards,
                                n_joins=5, overlap=0.2)
    eng = sampler._engine
    check(eng is not None, "no fused device engine was built")
    eng._ensure_device_inputs()
    jax.block_until_ready(jax.tree.leaves(eng._catalog_args()))
    setup_s = time.perf_counter() - t0
    c0, t0 = clock.seconds, time.perf_counter()
    sampler.sample(ROUND_BATCH)         # compiles the loop for the batch
    first_call_s = time.perf_counter() - t0
    return wl, sampler, eng, setup_s, clock.seconds - c0, first_call_s


def main_phase(jax, clock, seed: int) -> None:
    from repro.serve import SampleService

    wl, sampler, eng, setup_s, compile_s, first_call_s = build(
        jax, clock, seed=seed)
    say(f"main: UQ1 5 joins scale={SF1_SCALE} "
        f"(lineitem base rows {wl.db['lineitem'].nrows})")
    say(f"main: setup_s {setup_s}")
    say(f"main: compile_s {compile_s}  (first sample({ROUND_BATCH}) call "
        f"{first_call_s} s)")
    text = lowered_loop(eng, ROUND_BATCH).compile().as_text()
    check("tpu_custom_call" in text,
          "compiled loop holds no tpu_custom_call (Pallas probes missing)")

    served = []
    with SampleService(sampler, batch=ROUND_BATCH, prefetch=2) as svc:
        t0 = time.perf_counter()
        served.append(svc.request(REQUEST_SAMPLES))
        first_request_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        for _ in range(REQUESTS - 1):
            served.append(svc.request(REQUEST_SAMPLES))
        steady_s = time.perf_counter() - t1
    for ss in served:
        check(len(ss) == REQUEST_SAMPLES, f"request served {len(ss)} rows")
    st = sampler.stats
    rounds = st.candidate_draws // int(sum(eng.piece_batches))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    say(f"main: first_request_s {first_request_s}")
    say(f"main: steady_samples_per_s {(REQUESTS - 1) * REQUEST_SAMPLES / steady_s}"
        " (one unrepeated smoke run)")
    say(f"main: psi {st.psi()} rounds {rounds} peak_bytes_in_use {peak}")

    check_no_fallbacks(sampler)
    import numpy as np
    rows = {a: np.concatenate([ss.rows[a] for ss in served])
            for a in served[0].attrs}
    home = np.concatenate([ss.home for ss in served])
    check_home_membership(reference_oracle(wl), eng.order, rows, home)
    say(f"main: {home.shape[0]} served samples are union members; home "
        f"counts {np.bincount(home, minlength=len(eng.order)).tolist()}")


def uniformity(jax, workload: str, seed: int, **kw) -> float:
    """Chi-square p-value of served device samples against exact
    enumeration of the union (``tests/test_device_rounds.py``'s test).
    Exact uniformity needs exact piece sizes, so the cover comes from the
    exact warm-up here rather than the histogram bounds serving uses."""
    import numpy as np
    from scipy import stats as sps
    from repro.core.framework import estimate_union, warmup
    from repro.core.joins import full_join_matrix
    from repro.core.union_sampler import SetUnionSampler
    from repro.data.workloads import WORKLOADS
    from repro.serve import SampleService

    wl = WORKLOADS[workload](scale=1.0, seed=seed, **kw)
    cover = estimate_union(warmup(wl.cat, wl.joins, method="exact")
                           .oracle).cover
    sampler = SetUnionSampler(wl.cat, wl.joins, cover, seed=seed,
                              backend="jax", round_batch=ROUND_BATCH)
    attrs = list(sampler.attrs)
    union = np.unique(np.concatenate(
        [full_join_matrix(wl.cat, j, attrs) for j in wl.joins]), axis=0)
    U = union.shape[0]
    n = SMALL_SAMPLES_PER_TUPLE * U
    with SampleService(sampler, batch=ROUND_BATCH, prefetch=2) as svc:
        ss = svc.request(n)
    check(len(ss) == n, f"{workload}: served {len(ss)} of {n}")
    check_no_fallbacks(sampler)
    mat = np.stack([ss.rows[a] for a in attrs], axis=1)
    view = lambda m: np.ascontiguousarray(m).view(  # noqa: E731
        [("", m.dtype)] * m.shape[1]).ravel()
    uv, sv = view(union), view(mat.astype(union.dtype))
    idx = np.searchsorted(uv, sv)
    idx = np.minimum(idx, U - 1)
    check(bool((uv[idx] == sv).all()), f"{workload}: samples outside the union")
    counts = np.bincount(idx, minlength=U)
    exp = n / U
    chi2 = float(((counts - exp) ** 2 / exp).sum())
    p = float(1 - sps.chi2.cdf(chi2, df=U - 1))
    say(f"small: {workload} {kw} |U|={U} n={n} chi2={chi2} p={p}")
    check(p > P_MIN, f"{workload}: uniformity rejected (p={p})")
    return p


def four_chip_phase(jax, clock, seed: int) -> None:
    import numpy as np
    from repro.core.union_sampler import SetUnionSampler
    from scipy import stats as sps

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")
    wl, sharded, eng, setup_s, compile_s, first_call_s = build(
        jax, clock, seed=seed, shards=4)
    say(f"four: sharded setup_s {setup_s} compile_s {compile_s} "
        f"first_call_s {first_call_s}")
    for name, mem in eng.scat.members.items():
        for r in mem.rels:
            devs = {sh.device for sh in r.fp1.addressable_shards}
            check(len(devs) == 4,
                  f"{name}: membership shards on {len(devs)} devices")
    # the engine emits all_gathers + one reduce_scatter per round; XLA:TPU
    # may lower that reduce-scatter as an all-reduce
    lowered = lowered_loop(eng, ROUND_BATCH)
    hlo = lowered.as_text()
    check("stablehlo.all_gather" in hlo and "stablehlo.reduce_scatter" in hlo,
          "sharded loop lacks the per-round all_gather/reduce_scatter")
    text = lowered.compile().as_text()
    kinds = sorted({k for k in ("all-gather", "reduce-scatter", "all-reduce")
                    if k in text})
    say(f"four: compiled loop collectives {kinds}")
    check("all-gather" in text and ("reduce-scatter" in text
                                    or "all-reduce" in text),
          "compiled sharded loop lacks the fingerprint exchange")
    check_no_fallbacks(sharded)

    t0 = time.perf_counter()
    plain = SetUnionSampler(wl.cat, wl.joins, sharded.cover, seed=seed + 1,
                            backend="jax", round_batch=ROUND_BATCH)
    c0 = clock.seconds
    plain.sample(ROUND_BATCH)
    say(f"four: unsharded setup+first call {time.perf_counter() - t0} s, "
        f"compile_s {clock.seconds - c0}")
    check_no_fallbacks(plain)

    calls = 16                          # at the compiled batch size
    oracle = reference_oracle(wl)
    homes = []
    for label, s in (("sharded", sharded), ("unsharded", plain)):
        t0 = time.perf_counter()
        got = [s.sample(ROUND_BATCH) for _ in range(calls)]
        dt = time.perf_counter() - t0
        rows = {a: np.concatenate([g.rows[a] for g in got])
                for a in got[0].attrs}
        home = np.concatenate([g.home for g in got])
        check_home_membership(oracle, s._engine.order, rows, home)
        homes.append(np.bincount(home, minlength=len(wl.joins)))
        say(f"four: {label} {calls * ROUND_BATCH} samples in {dt} s (one "
            f"unrepeated smoke run), home counts {homes[-1].tolist()}, "
            f"psi {s.stats.psi()}")
    table = np.stack(homes)
    table = table[:, table.sum(axis=0) > 0]
    p = float(sps.chi2_contingency(table)[1])
    say(f"four: home shares sharded vs unsharded chi2 p={p}")
    check(p > P_MIN, f"home shares differ between engines (p={p})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on four chips and its "
                         "unsharded comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.jax_cache import use_persistent_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing: {e}",
              file=sys.stderr)
        return 2
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 2
    say(f"cache_dir {use_persistent_cache()}")
    say(f"device {dev.device_kind} x{len(jax.devices())}")
    clock = CompileClock()
    try:
        if args.four_chips:
            four_chip_phase(jax, clock, args.seed)
        else:
            main_phase(jax, clock, args.seed)
            uniformity(jax, "UQ1", args.seed, n_joins=2)
            uniformity(jax, "UQ4", args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    say(f"total compile_s {clock.seconds}")
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
