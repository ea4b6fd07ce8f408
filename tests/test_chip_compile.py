"""Compile the sampler's Pallas kernels and its device loop for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses: block shapes off the (8, 128) tiling, too much fast memory, a
primitive with no Mosaic lowering.  These tests compile for a v5e that is
described, not attached, at the widths the sampler runs at TPC-H SF1
(6,000,000 sorted keys, 4,096 queries per probe) and at the smallest layout
the chip takes (one fence chunk).  Nothing runs, so they check compilation
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
                                        _searchsorted_i32)
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
    n_blocks = -(-n_keys // KEY_BLOCK)
    n_chunks = -(-n_blocks // FENCE_CHUNK)
    qt = -(-n_queries // QUERY_TILE)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    tiles = [sds((qt, 1, QUERY_TILE)), sds((qt, 1, QUERY_TILE))]
    if with_u:
        tiles.append(sds((qt, 1, QUERY_TILE), jnp.float32))
    args = tiles + [sds((n_chunks, FENCE_CHUNK)), sds((n_chunks, FENCE_CHUNK)),
                    sds((n_blocks, KEY_BLOCK)), sds((n_blocks, KEY_BLOCK))]
    return args, dict(n_chunks=n_chunks, n_fences=n_blocks, interpret=False)


# (keys, queries): SF1 lineitem width, and one fence chunk of keys
WIDTHS = [(6_000_000, 4096), (FENCE_CHUNK * KEY_BLOCK, QUERY_TILE)]


@pytest.mark.parametrize("n_keys,n_queries", WIDTHS)
def test_searchsorted_compiles_for_v5e(one_chip, n_keys, n_queries):
    args, static = _kernel_args(one_chip, n_keys, n_queries, with_u=False)
    compiled = _searchsorted_i32.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_keys,n_queries", WIDTHS)
def test_walk_hop_compiles_for_v5e(one_chip, n_keys, n_queries):
    args, static = _kernel_args(one_chip, n_keys, n_queries, with_u=True)
    compiled = _hop_i32.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_device_loop_with_pallas_probes_compiles_for_v5e(one_chip,
                                                         monkeypatch):
    """The fused Algorithm-1 loop, catalog passed as an argument, lowered
    from shapes: the Pallas probes sit inside the compiled while loop."""
    from repro.core.backends.jax_backend import JaxBackend, JaxUnionSampler
    from repro.core.framework import estimate_union, warmup
    from repro.data.workloads import uq1
    import repro.kernels.ops as kops

    # this process sees the CPU, where the probes would interpret
    monkeypatch.setattr(kops, "default_interpret", lambda: False)
    wl = uq1(scale=0.1, seed=0, n_joins=2)
    cover = estimate_union(warmup(wl.cat, wl.joins,
                                  method="histogram").oracle).cover
    eng = JaxUnionSampler(JaxBackend(wl.cat, wl.joins, use_pallas=True),
                          cover, round_batch=256)
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
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert " while(" in text
    # the loop's phase scopes are metadata only: the probe kernels keep the
    # names the benchmark's trace reduction finds them by, and each sits in
    # the walk phase of its piece
    from repro import obs
    phases = obs.hlo_op_phases(text)
    kernels = re.findall(r"^\s*%([\w.-]+) = [^\n]*custom_call_target="
                         r'"tpu_custom_call"', text, re.M)
    assert kernels and all(k.startswith("_searchsorted_i32") for k in kernels)
    assert {phases[k].split("/")[0] for k in kernels} == {"walk"}
