"""Every sample delivered to clients in the window, over all of the
window's time (from its opening to the last answer)."""

from bench import load


def read(ctx):
    return load.samples_per_s(ctx["requests"])
