"""Pallas TPU kernels for the sampler's hot spots (+ serving attention).

searchsorted — two-phase tiled sorted probe (fence sweep + refine)
walk         — fused wander-join hop (refine + ranged uniform pick)
attention    — flash-decoding GQA w/ softcap + sliding window (model-side)
ops          — public jit'd wrappers (interpret=True on the CPU only)
ref          — pure jnp/numpy oracles
"""

from . import ops, ref

__all__ = ["ops", "ref"]
