"""Device loop: candidate draws per emitted sample (ψ) over the window,
from the program's exact ``SamplerStats`` counters."""


def read(ctx):
    w = ctx["window"]
    return w["draws"] / w["emitted"] if w["emitted"] else None
