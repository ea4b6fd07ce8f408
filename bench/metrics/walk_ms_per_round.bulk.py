"""Device loop: device time per round of the EW tree walks (``walk/<join>``
scopes: root pick, range probes with their Pallas kernels, residual
steps), in the traced window."""

from bench import phases


def read(ctx):
    return phases.ms_per_round(ctx, ["walk"])
