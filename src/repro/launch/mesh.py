"""Production mesh construction.

Single pod: (16, 16) = ("data", "model") — 256 chips (one TPU v5e pod).
Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips.  The "pod"
axis composes with "data" for DP+FSDP so TP/EP ("model") traffic stays on
intra-pod ICI; cross-pod traffic is only gradient reduce-scatter (+ the
optional int8-compressed variant in train/grad_compress.py).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis of the Auto type."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def ambient_mesh():
    """The mesh set by ``jax.set_mesh``, or ``None`` when there isn't one."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or not am.axis_names:
        return None
    return am


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Mesh axes used for DP/FSDP (includes 'pod' when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axes(mesh) -> tuple:
    return ("model",) if "model" in mesh.axis_names else ()
