"""Device loop: device time per round of the §8.2 residual steps of cyclic
pieces (``residual/<join>`` scopes: the residual range probes with their
Pallas kernels, the uniform pick among the matches, the ``Π d/M`` test), in
the traced window.  A program that publishes no residual phase has nothing
to read."""

from bench import phases


def read(ctx):
    if not any(p.startswith("residual/")
               for p in phases.loop_phases().values()):
        return None
    return phases.ms_per_round(ctx, ["residual"])
