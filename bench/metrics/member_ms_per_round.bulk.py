"""Device loop: device time per round of the cover-membership probes
against earlier pieces (``member/<join>`` scopes:
``DeviceJoinMembership.contains``), in the traced window."""

from bench import phases


def read(ctx):
    return phases.ms_per_round(ctx, ["member"])
