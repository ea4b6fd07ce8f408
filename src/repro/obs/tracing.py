"""Bounded ring-buffer event log — the φ-trajectory tracer — and the
profiler spans and device-loop phases of the program.

ONLINE-UNION's whole pitch is refining cheap initial parameter estimates on
the fly; :class:`TraceRing` makes that refinement observable.  The sampler
appends one event dict per notable transition (init, φ-refresh, backtrack)
and the ring keeps the last ``capacity`` of them with a monotone sequence
number, so a long-running service holds bounded memory while the bench CLIs
and tests can dump the recent trajectory.

Events are plain dicts (JSON-friendly); the ring stamps ``seq`` and ``kind``
and never mutates caller payloads.  Appends are thread-safe (the serve tier
may refine φ from a producer thread while a scraper drains the ring).
"""

from __future__ import annotations

import re
import threading
from typing import Dict, List, Optional

from .metrics import trace_annotations_enabled

__all__ = ["LOOP_PHASES", "PIECE_PHASES", "SPANS", "TraceRing", "UNSCOPED",
           "hlo_op_phases", "op_phases", "phase_of", "publish_op_phases",
           "span"]


class TraceRing:
    """Fixed-capacity event log with monotone sequence numbers."""

    def __init__(self, capacity: int = 256):
        if capacity <= 0:
            raise ValueError("TraceRing capacity must be positive")
        self.capacity = int(capacity)
        self._buf: List[Optional[Dict]] = [None] * self.capacity
        self._seq = 0                       # total events ever appended
        self._lock = threading.Lock()

    def append(self, kind: str, **fields) -> Dict:
        """Record one event; returns the stored dict (with ``seq`` set)."""
        ev = {"seq": None, "kind": str(kind), **fields}
        with self._lock:
            ev["seq"] = self._seq
            self._buf[self._seq % self.capacity] = ev
            self._seq += 1
        return ev

    def __len__(self) -> int:
        with self._lock:
            return min(self._seq, self.capacity)

    @property
    def total(self) -> int:
        """Events ever appended (≥ ``len`` once the ring has wrapped)."""
        with self._lock:
            return self._seq

    def events(self, kind: Optional[str] = None) -> List[Dict]:
        """Buffered events, oldest first; optionally filtered by kind."""
        with self._lock:
            n = min(self._seq, self.capacity)
            start = self._seq - n
            out = [dict(self._buf[i % self.capacity])
                   for i in range(start, self._seq)]
        if kind is not None:
            out = [e for e in out if e["kind"] == kind]
        return out

    def last(self, kind: Optional[str] = None) -> Optional[Dict]:
        evs = self.events(kind)
        return evs[-1] if evs else None

    def clear(self) -> None:
        with self._lock:
            self._buf = [None] * self.capacity
            # seq keeps counting: consumers can detect drops across clears


# ---------------------------------------------------------------------------
# Profiler spans and device-loop phases
# ---------------------------------------------------------------------------

#: Every host span the program opens under ``REPRO_OBS_TRACE=1``.  They are
#: ``jax.profiler.TraceAnnotation``s, so they land on the profiler's host
#: plane on the same clock as the device events; a trace reduction loads
#: them by these names.
SPANS = (
    "repro/sample_dispatch",      # device-loop dispatch (call=<k>)
    "repro/engine/drain",         # _PendingSample.result (call=<k>) ...
    "repro/engine/drain_wait",    # ... blocked until the loop's scalars land
    "repro/engine/assemble",      # ... fetch, widen, shuffle, split
    "repro/engine/fingerprint",   # ... fingerprint128, inside assemble
    "repro/serve/request",        # SampleService.request (batches=<ids>) ...
    "repro/serve/lock_wait",      # ... waiting for the request lock
    "repro/serve/queue_wait",     # ... blocked on the prefetch queue
    "repro/serve/assemble",       # ... concatenation into the answer
    "repro/serve/put_wait",       # producer blocked on a full queue
)

#: Phases of one device-loop round, as ``jax.named_scope``s in the loop body.
#: The per-piece phases carry the piece's join name as a second component
#: (``walk/<join>``); the others stand alone.  ``residual/<join>`` (a cyclic
#: piece's §8.2 residual probes and ``Π d/M`` test) opens inside
#: ``walk/<join>`` and takes its ops from it.
PIECE_PHASES = ("walk", "residual", "filter", "member", "compact")
LOOP_PHASES = ("select",) + PIECE_PHASES + ("emit", "carry")
UNSCOPED = "unscoped"
# a phase that refines the phase of the scope around it
_SUB_PHASES = {"residual": "walk"}


class _NoSpan:
    """What :func:`span` returns while trace annotations are off."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **_args) -> None:
        pass


_NO_SPAN = _NoSpan()


def span(name: str, **args):
    """A profiler span named ``name`` (one of :data:`SPANS`) with ``args``
    as its metadata, or a no-op while ``REPRO_OBS_TRACE`` is off.  Either
    way the result is a context manager with ``set_metadata(**args)``."""
    if not trace_annotations_enabled():
        return _NO_SPAN
    if name not in SPANS:
        raise ValueError(f"{name!r} is not in repro.obs.SPANS")
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, **args)


def phase_of(op_name: str) -> str:
    """The loop phase of an HLO op from its ``op_name`` metadata (the JAX
    name stack, e.g. ``jit(loop_fn)/while/body/algo1_fused_round/walk/J1/
    gather``): ``"walk/J1"``, ``"emit"``, ..., or :data:`UNSCOPED`.  The
    outermost phase scope wins, except that ``residual/<join>`` inside
    ``walk/<join>`` refines it; a phase is never the last component (that
    is the primitive)."""
    parts = op_name.split("/")
    phase = None
    for i, part in enumerate(parts[:-1]):
        if part in PIECE_PHASES and i + 2 < len(parts):
            here = f"{part}/{parts[i + 1]}"
        elif part in LOOP_PHASES and part not in PIECE_PHASES:
            here = part
        else:
            continue
        if phase is None:
            phase = here
        elif phase.split("/")[0] == _SUB_PHASES.get(part):
            return here
    return UNSCOPED if phase is None else phase


_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$')
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def hlo_op_phases(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> loop phase for every instruction of a compiled
    HLO module's text (``compiled.as_text()``).  Instruction names are the
    op names a profiler trace gives the module's device ops."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if m is None:
            continue
        meta = _OP_NAME.search(m.group(2))
        out[m.group(1)] = phase_of(meta.group(1)) if meta else UNSCOPED
    return out


_op_phases: Dict[str, Dict[str, str]] = {}
_op_phases_lock = threading.Lock()
_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")


def publish_op_phases(hlo_text: str) -> None:
    """Record the op -> phase map (:func:`hlo_op_phases`) of a compiled
    program under its module name, replacing an earlier one.  Engines do
    this under ``REPRO_OBS_TRACE=1`` so that a reduction of this process's
    profiler trace can split the program's device time by phase: the trace
    names each op but carries no name stack."""
    m = _HLO_MODULE.match(hlo_text)
    if m is None:
        raise ValueError("not the text of an HLO module")
    phases = hlo_op_phases(hlo_text)
    with _op_phases_lock:
        _op_phases[m.group(1)] = phases


def op_phases(module: str) -> Dict[str, str]:
    """The op -> phase map last published for the program ``module`` (the
    name a trace gives its runs, e.g. ``jit_loop_fn``); empty if none."""
    with _op_phases_lock:
        return dict(_op_phases.get(module, {}))
