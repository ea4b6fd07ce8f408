"""Engine host path: mean time one ``_PendingSample.result`` blocks until
the loop's scalar outputs are on the host (the wait on the device), from
the program's ``repro_engine_drain_wait_seconds`` histogram over the run."""

from bench import phases


def read(ctx):
    return phases.histogram_mean_ms("repro_engine_drain_wait_seconds")
