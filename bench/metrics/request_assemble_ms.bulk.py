"""Serve tier: mean time one ``SampleService.request`` spends concatenating
its answer from the prefetched batches, from the program's
``repro_serve_assemble_seconds`` histogram over the run."""

from bench import phases


def read(ctx):
    return phases.histogram_mean_ms("repro_serve_assemble_seconds")
