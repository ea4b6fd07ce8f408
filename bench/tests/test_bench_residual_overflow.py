"""The reader of ``residual_overflow_share.bulk`` on a hand-built registry
(CPU): the share of a cyclic piece's residual survivors that the device
loop dropped because its survivor width was full, and nothing where the
program publishes no overflow counter."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def test_residual_overflow_share_reads_the_registry():
    from repro import obs
    read = harness.load_metric(ROOT, "residual_overflow_share.bulk").read
    reg = obs.MetricsRegistry()
    prev = obs.set_registry(reg)
    try:
        draws = reg.counter("repro_engine_piece_draws_total", "", ("join",))
        kills = reg.counter("repro_engine_piece_residual_kills_total", "",
                            ("join",))
        draws.labels(join="A").inc(300)
        draws.labels(join="B").inc(100)
        kills.labels(join="A").inc(270)
        assert read({}) is None               # a program without the counter
        overflow = reg.counter("repro_engine_piece_residual_overflow_total",
                               "", ("join",))
        assert read({}) == 0.0
        overflow.labels(join="B").inc(13)
        assert read({}) == pytest.approx(13 / 130)
    finally:
        obs.set_registry(prev)
