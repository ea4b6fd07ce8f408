"""Persistent device-resident round loop: parity and banking.

Acceptance bar of the perf tentpole: moving the whole multi-round
Algorithm-1 loop into one jitted ``lax.while_loop`` (device-resident
shortfall carry, FIFO ring-buffer surplus banks, on-device stats) must not
change a single emitted sample relative to the host-driven round loop it
replaces.  Pinned here:

* device loop vs host loop — bit-equal rows/home/fingerprint *and* identical
  ``SamplerStats`` across multiple calls whose surplus banks carry over;
* FIFO-bank equivalence with a tiny ring capacity (wrap-around exercised);
* a 1-device sharded pin: the in-loop fingerprint exchange (collectives
  inside the device loop) matches the between-round exchange of the host
  mode, and both match the unsharded engine.

The served stream's uniformity is the ``served`` column of the engine ×
union matrix (``test_conformance_mesh_served.py``).
"""

import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core.backends import get_backend
from repro.core.backends.jax_backend import JaxUnionSampler
from repro.core.framework import estimate_union, warmup
from repro.core.union_sampler import SetUnionSampler
from repro.data.workloads import uq1


def _cover(wl):
    return estimate_union(warmup(wl.cat, wl.joins, method="exact").oracle).cover


def _assert_same_samples(a, b):
    assert a.attrs == b.attrs
    for attr in a.attrs:
        np.testing.assert_array_equal(a.rows[attr], b.rows[attr])
    np.testing.assert_array_equal(a.home, b.home)
    np.testing.assert_array_equal(a.fingerprint, b.fingerprint)


# ---------------------------------------------------------------------------
# device loop == host loop, bit for bit
# ---------------------------------------------------------------------------


def test_device_loop_matches_host_loop_bitwise():
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    cover = _cover(wl)
    dev = SetUnionSampler(wl.cat, wl.joins, cover, seed=11, backend="jax",
                          round_batch=512, fused_rounds="device")
    host = SetUnionSampler(wl.cat, wl.joins, cover, seed=11, backend="jax",
                           round_batch=512, fused_rounds="host")
    # successive odd-sized calls: the second and third reuse banked surplus
    # and carried shortfall from the first, so the whole carry state — not
    # just one round — must agree
    for n in (700, 1500, 333):
        _assert_same_samples(dev.sample(n), host.sample(n))
        assert dev.stats.as_dict() == host.stats.as_dict()


def test_fifo_bank_ring_wrap_equivalence():
    """A tiny ring capacity forces head wrap-around and push clipping; the
    device ring buffer must still replay the host twin's FIFO exactly."""
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    cover = _cover(wl)

    def engine(mode):
        backend = get_backend("jax", wl.cat, wl.joins, seed=2)
        return JaxUnionSampler(backend, cover, seed=7, round_batch=512,
                               surplus_cap=64, fused_rounds=mode)

    dev, host = engine("device"), engine("host")
    for n in (333, 87, 512, 1025, 64):
        _assert_same_samples(dev.sample(n), host.sample(n))
    assert dev.stats.as_dict() == host.stats.as_dict()


# ---------------------------------------------------------------------------
# FIFO ring edges: cap-boundary wrap + the W=min(rb, 256) drain clamp at
# rb < 256, = 256, and > 256 (property-tested over request sequences)
# ---------------------------------------------------------------------------

# engine pairs are module-cached: each property example continues the same
# carry state, and the dev/host twins advance in lockstep so every prefix of
# the request stream is itself a parity check
_RING_PAIRS = {}


def _ring_pair(rb, cap):
    if (rb, cap) not in _RING_PAIRS:
        wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
        cover = _cover(wl)

        def engine(mode):
            backend = get_backend("jax", wl.cat, wl.joins, seed=3)
            return JaxUnionSampler(backend, cover, seed=17, round_batch=rb,
                                   surplus_cap=cap, fused_rounds=mode)

        _RING_PAIRS[(rb, cap)] = (engine("device"), engine("host"))
    return _RING_PAIRS[(rb, cap)]


def test_drain_window_clamp_across_round_batches():
    """W = min(rb, 256) on both sides of the clamp, including rb < 256."""
    for rb, want in ((128, 128), (256, 256), (512, 256), (1024, 256)):
        dev, _ = _ring_pair(rb, 48) if rb in (128, 512) else _ring_pair(rb, 64)
        assert dev._drain_w == want == min(rb, 256)


@settings(max_examples=4, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=400),
                min_size=1, max_size=3))
def test_ring_bank_cap_wrap_property(ns):
    """Tiny non-multiple caps force head wrap + push clipping at every
    drain-clamp regime; the device ring must replay the host FIFO exactly."""
    for rb, cap in ((128, 48), (256, 64), (512, 48)):
        dev, host = _ring_pair(rb, cap)
        for n in ns:
            _assert_same_samples(dev.sample(n), host.sample(n))
        assert dev.stats.as_dict() == host.stats.as_dict()


# ---------------------------------------------------------------------------
# sharded (world=1): in-loop exchange == between-round exchange
# ---------------------------------------------------------------------------


def test_psum_counters_matches_host_merge():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.sharding import (SHARD_AXIS, make_sampler_mesh,
                                     psum_counters)
    from repro.core.union_sampler import SamplerStats
    mesh = make_sampler_mesh(world=1)
    vec = jnp.array([3, 7, 1, 0, 2], jnp.int32)
    merged = jax.jit(jax.shard_map(
        lambda v: psum_counters(v, SHARD_AXIS), mesh=mesh,
        in_specs=P(), out_specs=P()))(vec)
    host = SamplerStats(iterations=3, candidate_draws=7, cover_rejects=1,
                        residual_rejects=0, dropped_slots=2)
    assert merged.tolist() == [host.iterations, host.candidate_draws,
                               host.cover_rejects, host.residual_rejects,
                               host.dropped_slots]


def test_sharded_world1_inloop_exchange_matches_between_rounds():
    from repro.core.sharding import make_sampler_mesh
    wl = uq1(scale=0.02, overlap=0.4, seed=0, n_joins=2)
    cover = _cover(wl)

    def engine(mode, mesh):
        return SetUnionSampler(wl.cat, wl.joins, cover, seed=9,
                               backend="jax", round_batch=512, mesh=mesh,
                               fused_rounds=mode)

    in_loop = engine("device", make_sampler_mesh(world=1))
    between = engine("host", make_sampler_mesh(world=1))
    plain = engine("device", None)
    for n in (900, 411):
        a, b, c = in_loop.sample(n), between.sample(n), plain.sample(n)
        _assert_same_samples(a, b)
        _assert_same_samples(a, c)
        assert in_loop.stats.as_dict() == between.stats.as_dict()
        assert in_loop.stats.as_dict() == plain.stats.as_dict()
