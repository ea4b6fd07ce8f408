"""Device loop: device time per round of compaction and emission
(``compact/<join>`` rank scatters and the ``emit`` bank and output
scatters), in the traced window."""

from bench import phases


def read(ctx):
    return phases.ms_per_round(ctx, ["compact", "emit"])
