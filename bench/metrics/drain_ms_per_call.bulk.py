"""Engine host path: mean host time of one ``_PendingSample.result`` (fetch,
output shuffle, ``fingerprint128``), from the program's
``repro_engine_drain_seconds`` histogram (sum over count) over the window."""


def read(ctx):
    w = ctx["window"]
    return 1e3 * w["drain_sum"] / w["drain_count"] if w["drain_count"] else None
