"""What the benchmark reads of the program's own instrumentation in this
process: the run's host-side histograms, and the device loop's time split
by phase.

A profiler trace names each device op but carries no name stack, so the
phase of an op comes from the program: under ``REPRO_OBS_TRACE=1`` (every
``--trace 1`` run) the engine publishes, per compiled loop, which phase
each of its ops belongs to (``repro.obs.op_phases``).  A program that
publishes nothing, or lacks the histogram, leaves the reading empty.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

LOOP = "jit_loop_fn"


def histogram_mean_ms(name: str) -> Optional[float]:
    """Mean of the program's histogram ``name`` over every observation of
    the run (set-up's warm-up calls included), in ms."""
    from repro import obs
    h = obs.get_registry().get(name)
    if h is None:
        return None
    series = h.snapshot().get(())
    if not series or not series["count"]:
        return None
    return 1e3 * series["sum"] / series["count"]


def loop_phases() -> Dict[str, str]:
    """Op name -> phase of the loop program the engine last published."""
    from repro import obs
    published = getattr(obs, "op_phases", None)
    return published(LOOP) if published is not None else {}


def phase_seconds(op_s: Dict[str, float], phases: Dict[str, str]
                  ) -> Dict[str, float]:
    """Self seconds of the loop's ops in ``op_s`` summed by phase
    (``walk/<join>`` and so on; ``unscoped`` for ops outside every phase).
    Ops of other programs are left out."""
    out: Dict[str, float] = {}
    for op, s in op_s.items():
        phase = phases.get(op)
        if phase is not None:
            out[phase] = out.get(phase, 0.0) + s
    return out


def ms_per_round(ctx, kinds: Sequence[str]) -> Optional[float]:
    """Device ms per round of the loop's phases whose first component is in
    ``kinds``: their share of the loop ops' self time in the traced window,
    times the loop's device time per round (``device_ms_per_round.bulk``'s
    reading), so the phases and ``unscoped`` add up to it."""
    red, w = ctx.get("reduced"), ctx["window"]
    if red is None or not red.module_runs.get(LOOP) or not w["rounds"]:
        return None
    phases = loop_phases()
    by_phase = phase_seconds(red.op_s, phases)
    total = sum(by_phase.values())
    if total <= 0:
        return None
    part = sum(s for p, s in by_phase.items() if p.split("/")[0] in kinds)
    rounds = red.module_runs[LOOP] * w["rounds"] / w["drain_count"]
    return 1e3 * red.module_s[LOOP] / rounds * part / total
