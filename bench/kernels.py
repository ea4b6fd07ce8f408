"""The kernels the benchmark reads from a trace, and the least work each
must do: the yardstick for their roofline shares.

``_searchsorted_i32`` is the program's jitted range probe
(``kernels/searchsorted.py``); XLA names its two Pallas calls (fence sweep,
refine) after it, and they are its ``tpu_custom_call`` ops.  Every tree
node of a draw probes once, so a piece's probes are its candidate draws
times its tree's non-root nodes.
"""

from __future__ import annotations

from typing import Sequence

SEARCHSORTED = "_searchsorted_i32"

# least traffic of one probe: its int32 query key in, two int32 positions
# out; fence, tile and padding bytes are the implementation's, not the work's
PROBE_BYTES = 4 + 2 * 4


def is_probe_kernel(op: str) -> bool:
    """A Pallas call of the range probe, by its HLO op text."""
    return (op.lstrip("%").startswith(SEARCHSORTED)
            and 'custom_call_target="tpu_custom_call"' in op)


def probes(piece_draws: Sequence[int], hops: Sequence[int]) -> int:
    return int(sum(int(d) * int(h) for d, h in zip(piece_draws, hops)))


def probe_bytes(piece_draws: Sequence[int], hops: Sequence[int]) -> int:
    return PROBE_BYTES * probes(piece_draws, hops)
