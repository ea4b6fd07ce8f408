"""Compile the sampler's Pallas kernels and its device loop for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses: block shapes off the (8, 128) tiling, too much fast memory, a
primitive with no Mosaic lowering.  These tests compile for a v5e that is
described, not attached, at the widths the sampler runs at TPC-H SF1
(6,000,000 and 3,600,000 sorted keys, 4,096 queries per probe: two fence
levels) and at the smallest layout the chip takes (one fence chunk: one
level).  Nothing runs, so they check compilation
only; results are checked in interpret mode by ``test_kernels.py``.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and test workers import every
test file.  All cases stay in this one file for the same reason.
"""

import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.searchsorted import (FENCE_CHUNK, KEY_BLOCK, QUERY_TILE,
                                        _member_probe_i32, _searchsorted_i32,
                                        probe_levels)
from repro.kernels.walk import _hop_i32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        cc.reset_cache()


def _kernel_args(sharding, n_keys: int, n_queries: int, with_u: bool):
    """Shapes of ``_searchsorted_i32`` / ``_hop_i32``'s arguments: query
    tiles (and uniforms), then ``PreparedKeys.arrays()``."""
    n_blocks = -(-n_keys // KEY_BLOCK)
    n_chunks = -(-n_blocks // FENCE_CHUNK)
    n_top = -(-n_chunks // FENCE_CHUNK)
    qt = -(-n_queries // QUERY_TILE)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    tiles = [sds((qt, 1, QUERY_TILE)), sds((qt, 1, QUERY_TILE))]
    if with_u:
        tiles.append(sds((qt, 1, QUERY_TILE), jnp.float32))
    args = tiles + [sds((n_top, FENCE_CHUNK))] * 2 \
        + [sds((n_chunks, FENCE_CHUNK))] * 2 + [sds((n_blocks, KEY_BLOCK))] * 2
    return args, probe_levels(n_chunks)


def _custom_calls(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')


# (keys, queries): SF1 lineitem width, the UQ1 variant's lineitem (two
# fence levels) and one fence chunk of keys (one level: supplier, nation)
WIDTHS = [(6_000_000, 4096), (3_600_000, 4096),
          (FENCE_CHUNK * KEY_BLOCK, QUERY_TILE)]


@pytest.mark.parametrize("n_keys,n_queries", WIDTHS)
def test_searchsorted_compiles_for_v5e(one_chip, n_keys, n_queries):
    args, levels = _kernel_args(one_chip, n_keys, n_queries, with_u=False)
    text = _searchsorted_i32.lower(*args, interpret=False).compile().as_text()
    # one fence level: the sweep and the refine, as before two levels
    # existed; two: the top sweep, the fence-row compare and the refine
    assert _custom_calls(text) == 1 + levels


@pytest.mark.parametrize("n_keys,n_queries", WIDTHS)
def test_member_probe_compiles_for_v5e(one_chip, n_keys, n_queries):
    """The membership probe: the range probe's Pallas calls under a name
    the range probe's trace reduction does not match."""
    args, levels = _kernel_args(one_chip, n_keys, n_queries, with_u=False)
    text = _member_probe_i32.lower(*args, n=n_keys,
                                   interpret=False).compile().as_text()
    kernels = re.findall(r"^\s*%([\w.-]+) = [^\n]*custom_call_target="
                         r'"tpu_custom_call"', text, re.M)
    assert len(kernels) == _custom_calls(text) == 1 + levels
    assert all(k.startswith("_member_probe_i32") for k in kernels)


@pytest.mark.parametrize("n_keys,n_queries", WIDTHS)
def test_walk_hop_compiles_for_v5e(one_chip, n_keys, n_queries):
    args, levels = _kernel_args(one_chip, n_keys, n_queries, with_u=True)
    text = _hop_i32.lower(*args, interpret=False).compile().as_text()
    assert _custom_calls(text) == 1 + levels


def _compile_loop(one_chip, monkeypatch, wl=None, round_batch=256):
    """The fused Algorithm-1 loop of a union (by default two UQ1 joins),
    catalog passed as an argument, lowered from shapes and compiled:
    (engine, HLO text)."""
    from repro.core.backends.jax_backend import JaxBackend, JaxUnionSampler
    from repro.core.framework import estimate_union, warmup
    from repro.data.workloads import uq1
    import repro.kernels.ops as kops

    # this process sees the CPU, where the probes would interpret
    monkeypatch.setattr(kops, "default_interpret", lambda: False)
    method = "exact" if wl is not None else "histogram"
    if wl is None:
        wl = uq1(scale=0.1, seed=0, n_joins=2)
    cover = estimate_union(warmup(wl.cat, wl.joins,
                                  method=method).oracle).cover
    eng = JaxUnionSampler(JaxBackend(wl.cat, wl.joins, use_pallas=True),
                          cover, round_batch=round_batch)
    eng._ensure_device_inputs()
    C = 1024
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (eng._init_state(), eng._out_buffer(C), jnp.int32(C),
         eng._probs_base, eng._catalog_args()))
    lowered = eng._build_loop(C).lower(*shapes)
    # the catalog is an argument: no data-sized constant in the program
    consts = re.findall(r'dense<"0x([0-9A-Fa-f]+)"', lowered.as_text())
    assert max(map(len, consts), default=0) < 4096
    return eng, lowered.compile().as_text()


def _check_probe_kernels(eng, text: str, levels: int):
    """Every Pallas call is a probe's: ``1 + levels`` per tree node, each
    named as the benchmark's trace reduction finds it, a tree node's in
    ``walk/*`` and a residual node's in ``residual/*``; and ``1 + levels``
    per membership index of each earlier piece a piece probes, under the
    membership probe's own name, in ``member/*``."""
    from collections import Counter
    from repro import obs
    members = [eng.backend.members[n] for n in eng.order]
    assert {p.levels for t in eng.trees for p in t._prepped} == {levels}
    assert {r.probe.levels for m in members for r in m.rels} == {levels}
    assert " while(" in text
    # the loop's phase scopes are metadata only: the probe kernels keep the
    # names the benchmark's trace reduction finds them by, and each sits in
    # the walk phase of its piece
    phases = obs.hlo_op_phases(text)
    kernels = re.findall(r"^\s*%([\w.-]+) = [^\n]*custom_call_target="
                         r'"tpu_custom_call"', text, re.M)
    kinds = Counter("residual" if c.kind == "residual" else "walk"
                    for t in eng.trees for c in t.node_cfgs)
    probes = [k for k in kernels if k.startswith("_searchsorted_i32")]
    member = [k for k in kernels if k.startswith("_member_probe_i32")]
    assert len(probes) + len(member) == len(kernels)
    assert Counter(phases[k].split("/")[0] for k in probes) == Counter(
        {kind: (1 + levels) * n for kind, n in kinds.items()})
    # piece j probes the indexes of the j pieces before it
    n_member = sum(len(m.rels) * (len(members) - 1 - q)
                   for q, m in enumerate(members))
    assert n_member and Counter(phases[k].split("/")[0] for k in member) \
        == Counter({"member": (1 + levels) * n_member})


def test_device_loop_with_pallas_probes_compiles_for_v5e(one_chip,
                                                         monkeypatch):
    """The Pallas probes, range and membership, sit inside the compiled
    while loop; at this size every index has one fence chunk, so one
    level."""
    eng, text = _compile_loop(one_chip, monkeypatch)
    _check_probe_kernels(eng, text, levels=1)


def test_device_loop_with_a_narrowed_cyclic_piece_compiles_for_v5e(
        one_chip, monkeypatch):
    """UQ4's cyclic piece walks its keys, keeps the walks its residual
    accepts and gathers their payload inside the compiled loop: each node
    is still probed once a round, the residual one in its own phase."""
    from repro.data.workloads import uq4
    eng, text = _compile_loop(one_chip, monkeypatch,
                              wl=uq4(scale=0.02, seed=0), round_batch=1024)
    narrowed = [n for n, t, s, b in zip(eng.order, eng.trees,
                                        eng.survivor_widths,
                                        eng.piece_batches) if s < b]
    assert narrowed and all(t.has_residual for t in eng.trees
                            if t.name in narrowed)
    _check_probe_kernels(eng, text, levels=1)


@pytest.fixture
def two_levels_everywhere(monkeypatch):
    """Every index on the two-level fence search, so that a small union
    takes the path of SF1's orders and lineitem.  The levels are chosen
    while tracing, so traces cached under the real threshold are dropped
    on the way in and the ones made here on the way out."""
    import repro.kernels.searchsorted as ss
    monkeypatch.setattr(ss, "TWO_LEVEL_MIN_CHUNKS", 1)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_device_loop_with_two_level_probes_compiles_for_v5e(
        one_chip, monkeypatch, two_levels_everywhere):
    """The same loop with every probe searching its fences in two levels."""
    eng, text = _compile_loop(one_chip, monkeypatch)
    _check_probe_kernels(eng, text, levels=2)
