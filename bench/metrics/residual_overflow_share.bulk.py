"""Device loop: the share of a cyclic piece's residual survivors (skeleton
walks the residual edges accepted: draws less residual kills) that the loop
dropped as spent draws because the round's survivor width was full, over
the run, from the program's registry counters
``repro_engine_piece_residual_overflow_total``,
``repro_engine_piece_draws_total`` and
``repro_engine_piece_residual_kills_total`` (summed over pieces).  A
program without the first counter has nothing to read."""


def _total(reg, name):
    counter = reg.get(name)
    return None if counter is None else sum(counter.snapshot().values())


def read(ctx):
    from repro import obs
    reg = obs.get_registry()
    overflow = _total(reg, "repro_engine_piece_residual_overflow_total")
    draws = _total(reg, "repro_engine_piece_draws_total")
    kills = _total(reg, "repro_engine_piece_residual_kills_total")
    if overflow is None or draws is None or kills is None:
        return None
    survivors = draws - kills
    return overflow / survivors if survivors > 0 else None
