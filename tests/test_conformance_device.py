"""The engine × union matrix on the device engines: the fused loop on the
static and the adaptive plan, and the record engine (``_conformance``)."""

import pytest

from _conformance import cells, check_members, check_uniform

ENGINES = ("jax", "jax-adaptive", "jax-record")


@pytest.mark.parametrize("engine,union", cells(ENGINES, "member"))
def test_rows_are_members_of_their_home_piece(engine, union):
    check_members(engine, union)


@pytest.mark.parametrize("engine,union", cells(ENGINES, "uniform"))
def test_stream_is_uniform(engine, union):
    check_uniform(engine, union)
