"""Layer 2: jaxpr structural audit of the fused round programs.

The AST lint (layer 1) reasons about *source*; this layer reasons about
the *traced programs*.  It builds real ``JaxUnionSampler`` /
``ShardedUnionSampler`` engines on small workloads, traces their fused
device loop and host-twin round program with abstract inputs (no
execution, no XLA compile beyond ``lower``), and checks structural
invariants that source-level lint cannot see:

* **RNG parity** — the device loop and its host twin must draw from the
  same family of RNG primitives.  A threefry primitive on one side only
  means the two paths would consume randomness differently and the
  host/device equivalence tests are comparing different streams.
* **Collective discipline** — the unsharded engine's programs must
  contain *zero* collectives; the world=1 sharded device loop must
  contain exactly the host round program's collective sequence plus the
  single trailing banking ``all_gather`` (the "one tiny exchange" the
  sharded round body documents).
* **Donated-buffer aliasing** — the device loop is jitted with
  ``donate_argnums`` on the carry; the lowered program must actually
  alias those inputs to outputs (``tf.aliasing_output`` /
  ``jax.buffer_donor`` in the StableHLO), otherwise every round copies
  the bank.
* **Loop fusion** — the device program must contain a ``while``
  primitive (the rounds are fused on device, not unrolled by the host).

Everything returns :class:`~repro.analysis.findings.Finding` objects so
the gate script can merge them with the AST layer's output.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Sequence, Tuple

from .findings import Finding

# exchange / mesh primitives (jax.lax collectives, by primitive name)
COLLECTIVE_PRIMITIVES = frozenset({
    "all_gather", "all_to_all", "psum", "psum_scatter", "reduce_scatter",
    "ppermute", "pmax", "pmin", "pgather", "axis_index", "pdot",
})

# substrings identifying RNG primitives (threefry2x32 on CPU paths,
# random_bits / random_seed / random_wrap under new-style keys)
_RNG_MARKERS = ("threefry", "random", "rng")

# StableHLO markers for donated/aliased buffers across jax versions
_DONATION_TOKENS = ("tf.aliasing_output", "jax.buffer_donor")


# -- primitive inventory ------------------------------------------------------

def _sub_jaxprs(val: Any) -> Iterable[Any]:
    """Duck-typed walk into eqn params that hold nested jaxprs.

    ``pjit`` carries a ClosedJaxpr, ``while``/``cond``/``scan`` carry
    (lists of) ClosedJaxprs; shard_map wraps another jaxpr again.  We
    recognise them structurally so this keeps working across jax
    versions: anything with ``.eqns`` is a Jaxpr, anything with
    ``.jaxpr`` is a ClosedJaxpr.
    """
    if hasattr(val, "eqns"):
        yield val
    elif hasattr(val, "jaxpr"):
        yield from _sub_jaxprs(val.jaxpr)
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _sub_jaxprs(item)
    elif isinstance(val, dict):
        for item in val.values():
            yield from _sub_jaxprs(item)


def collect_primitives(jaxpr: Any) -> List[str]:
    """Depth-first primitive names of ``jaxpr`` including all sub-jaxprs.

    Depth-first at the equation site preserves program order for the
    collective-sequence check (a ``while`` body's collectives appear
    once, where the loop sits).
    """
    if hasattr(jaxpr, "jaxpr"):            # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    names: List[str] = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                names.extend(collect_primitives(sub))
    return names


def rng_kinds(prims: Sequence[str]) -> frozenset:
    return frozenset(p for p in prims
                     if any(m in p for m in _RNG_MARKERS))


def collective_sequence(prims: Sequence[str]) -> List[str]:
    return [p for p in prims if p in COLLECTIVE_PRIMITIVES]


def _donated(lowered_text: str) -> bool:
    return any(tok in lowered_text for tok in _DONATION_TOKENS)


def _finding(label: str, message: str, detail: str) -> Finding:
    return Finding(rule="jaxpr-audit", path=f"<audit:{label}>", line=0,
                   scope=label, message=message, detail=detail)


# -- engine builders ----------------------------------------------------------

def build_engine(workload: str = "uq1", plan: str = "static",
                 world: int = 0, round_batch: int = 256):
    """Build the real engine a tier-1 run would use, on a small workload.

    ``world=0`` returns an unsharded ``JaxUnionSampler``; ``world>=1``
    builds the mesh path (``ShardedUnionSampler``) with that many
    shards.
    """
    from repro.core.framework import estimate_union, warmup
    from repro.core.union_sampler import SetUnionSampler
    from repro.data import workloads

    if workload == "uq1":
        wl = workloads.uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    elif workload == "uq4":
        wl = workloads.uq4(scale=0.04, seed=0)
    else:
        raise ValueError(f"unknown audit workload {workload!r}")
    cover = estimate_union(warmup(wl.cat, wl.joins, method="exact")
                           .oracle).cover
    kwargs: Dict[str, Any] = {}
    if world:
        from repro.core.sharding import make_sampler_mesh
        kwargs["mesh"] = make_sampler_mesh(world=world)
    sampler = SetUnionSampler(wl.cat, wl.joins, cover, seed=11,
                              backend="jax", round_batch=round_batch,
                              fused_rounds="device", plan=plan, **kwargs)
    return sampler._engine


# -- audits -------------------------------------------------------------------

def _device_trace_args(eng, C: int) -> Tuple:
    import jax.numpy as jnp

    eng._ensure_device_inputs()
    return (eng._init_state(), eng._out_buffer(C), jnp.int32(8),
            eng._probs_base, eng._catalog_args())


def _host_twin_args(eng) -> Tuple:
    import jax
    import jax.numpy as jnp

    nj = len(eng.order)
    args = (eng._probs_base, jnp.zeros(nj, dtype=bool),
            jnp.zeros(nj, jnp.int32), jnp.int32(4), jax.random.PRNGKey(0))
    if eng.plan == "adaptive":
        args = args + (jnp.asarray(eng._ema_seed),
                       jnp.zeros(nj, jnp.int32))
    return args


def audit_unsharded(eng, label: str, C: int = 1024
                    ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Structural checks on one ``JaxUnionSampler``'s traced programs."""
    import jax

    dev_args = _device_trace_args(eng, C)
    loop = eng._loop_for(C)
    dev_prims = collect_primitives(jax.make_jaxpr(loop)(*dev_args))
    host_prims = collect_primitives(jax.make_jaxpr(
        lambda *a: eng._round_impl(*a[:-1], cat=a[-1]))(
            *_host_twin_args(eng), eng._catalog_args()))

    findings: List[Finding] = []
    dev_rng, host_rng = rng_kinds(dev_prims), rng_kinds(host_prims)
    if dev_rng != host_rng:
        findings.append(_finding(
            label, "RNG primitive families differ between the device loop "
            "and its host twin",
            f"device={sorted(dev_rng)} host={sorted(host_rng)}"))
    if not dev_rng:
        findings.append(_finding(
            label, "device loop draws no RNG primitives", "rng:none"))
    for side, prims in (("device", dev_prims), ("host", host_prims)):
        cols = collective_sequence(prims)
        if cols:
            findings.append(_finding(
                label, f"unsharded {side} program contains collectives",
                f"{side}:{cols}"))
    if "while" not in dev_prims:
        findings.append(_finding(
            label, "device program has no fused while loop — rounds would "
            "be host-unrolled", "no-while"))
    if not _donated(loop.lower(*dev_args).as_text()):
        findings.append(_finding(
            label, "device loop carry is not donated — every call copies "
            "the bank buffers", "no-donation"))
    report = {
        "label": label, "kind": "unsharded", "plan": eng.plan,
        "device_primitives": len(dev_prims),
        "host_primitives": len(host_prims),
        "rng": sorted(dev_rng), "collectives": [],
        "donated": True, "findings": len(findings),
    }
    return findings, report


def audit_sharded(eng, label: str, C: int = 1024
                  ) -> Tuple[List[Finding], Dict[str, Any]]:
    """World=1 mesh invariants on one ``ShardedUnionSampler``.

    The device loop must run the host round program's collective
    sequence plus exactly one trailing banking ``all_gather`` per round
    body — the single exchange the shard-major water filling needs.
    """
    import jax
    import jax.numpy as jnp

    eng._ensure_device_inputs()
    run = eng._loop_for(C)
    prog = getattr(run, "_prog", None)
    findings: List[Finding] = []
    if prog is None:
        return [_finding(label, "sharded loop does not expose its jitted "
                         "program (run._prog)", "no-prog")], {
            "label": label, "kind": "sharded", "findings": 1}
    state = eng._init_state()
    shr = {k: state[k] for k in ("bank", "bank_head", "bank_count")}
    rep = {k: state[k] for k in run._rep_keys}
    dev_args = (shr, rep, eng._out_buffer(C), jnp.int32(8),
                eng._probs_base, eng._catalog_args())
    dev_prims = collect_primitives(jax.make_jaxpr(prog)(*dev_args))
    # mesh round program: (probs, dead, carry, extra, key, st[, ema, gcount])
    twin = _host_twin_args(eng)
    host_args = twin[:5] + (eng._catalog_args(),) + twin[5:]
    host_prims = collect_primitives(
        jax.make_jaxpr(eng._round_prog)(*host_args))

    dev_cols = collective_sequence(dev_prims)
    host_cols = collective_sequence(host_prims)
    if dev_cols != host_cols + ["all_gather"]:
        findings.append(_finding(
            label, "sharded device loop collective sequence is not the "
            "host round sequence plus one banking all_gather",
            f"device={dev_cols} host={host_cols}"))
    dev_rng, host_rng = rng_kinds(dev_prims), rng_kinds(host_prims)
    if dev_rng != host_rng:
        findings.append(_finding(
            label, "RNG primitive families differ between the sharded "
            "device loop and the mesh round program",
            f"device={sorted(dev_rng)} host={sorted(host_rng)}"))
    if "while" not in dev_prims:
        findings.append(_finding(
            label, "sharded device program has no fused while loop",
            "no-while"))
    if not _donated(prog.lower(*dev_args).as_text()):
        findings.append(_finding(
            label, "sharded loop carry (bank shards + output) is not "
            "donated", "no-donation"))
    report = {
        "label": label, "kind": "sharded", "plan": eng.plan,
        "device_primitives": len(dev_prims),
        "host_primitives": len(host_prims),
        "rng": sorted(dev_rng), "collectives": dev_cols,
        "donated": True, "findings": len(findings),
    }
    return findings, report


# default audit matrix: both plan regimes on the acyclic 2-join union,
# the cyclic union, and the world=1 mesh path
DEFAULT_AUDITS: Tuple[Tuple[str, Dict[str, Any]], ...] = (
    ("uq1-static", dict(workload="uq1", plan="static")),
    ("uq1-adaptive", dict(workload="uq1", plan="adaptive")),
    ("uq4-static", dict(workload="uq4", plan="static")),
    ("uq1-sharded-w1", dict(workload="uq1", plan="static", world=1)),
)


def run_jaxpr_audit(audits: Sequence[Tuple[str, Dict[str, Any]]] = None
                    ) -> Tuple[List[Finding], List[Dict[str, Any]]]:
    """Run the audit matrix; returns (findings, per-audit reports)."""
    findings: List[Finding] = []
    reports: List[Dict[str, Any]] = []
    for label, spec in (audits if audits is not None else DEFAULT_AUDITS):
        eng = build_engine(**spec)
        if spec.get("world"):
            f, r = audit_sharded(eng, label)
        else:
            f, r = audit_unsharded(eng, label)
        findings.extend(f)
        reports.append(r)
    return findings, reports
