"""Device: share of the traced window in which no program ran on the chip
(1 - the union of program run intervals over the window)."""


def read(ctx):
    red = ctx.get("reduced")
    return None if red is None else 100.0 * red.idle_share
