"""Device loop: device time of the sampling loop program per Algorithm-1
round, in the traced window.  Loop runs inside the window are counted in
the trace; rounds per run are the program's exact counters over the whole
window (one drain per run)."""

LOOP = "jit_loop_fn"


def read(ctx):
    red, w = ctx.get("reduced"), ctx["window"]
    if red is None or not red.module_runs.get(LOOP) or not w["rounds"]:
        return None
    rounds = red.module_runs[LOOP] * w["rounds"] / w["drain_count"]
    return 1e3 * red.module_s[LOOP] / rounds
