"""Engine host path: mean host assembly time of one
``_PendingSample.result`` after the device wait (fetch, widening, output
shuffle, column split, ``fingerprint128``), from the program's
``repro_engine_assemble_seconds`` histogram over the run."""

from bench import phases


def read(ctx):
    return phases.histogram_mean_ms("repro_engine_assemble_seconds")
