"""Device loop: the share of the program's candidate draws that a cyclic
piece's residual edge turned away (a probe with no match, or the ``Π d/M``
test), over the run, from the program's registry counters
``repro_engine_piece_residual_kills_total`` and
``repro_engine_piece_draws_total`` (summed over pieces).  A program without
the first counter has nothing to read."""


def _total(reg, name):
    counter = reg.get(name)
    return None if counter is None else sum(counter.snapshot().values())


def read(ctx):
    from repro import obs
    reg = obs.get_registry()
    kills = _total(reg, "repro_engine_piece_residual_kills_total")
    draws = _total(reg, "repro_engine_piece_draws_total")
    if kills is None or not draws:
        return None
    return kills / draws
