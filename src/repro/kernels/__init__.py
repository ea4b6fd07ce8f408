"""Pallas TPU kernels for the sampler's hot spots.

searchsorted — two-phase tiled sorted probe (fence sweep + refine)
walk         — fused wander-join hop (refine + ranged uniform pick)
ops          — public jit'd wrappers (interpret=True on the CPU only)
ref          — numpy oracles
"""

from . import ops, ref

__all__ = ["ops", "ref"]
