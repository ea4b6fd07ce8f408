"""The engine × union matrix on the sharded device loop (a one-device mesh)
and on the served stream (``SampleService`` over the jax engine, requests
of mixed sizes) (``_conformance``)."""

import pytest

from _conformance import cells, check_members, check_uniform

ENGINES = ("mesh1", "served")


@pytest.mark.parametrize("engine,union", cells(ENGINES, "member"))
def test_rows_are_members_of_their_home_piece(engine, union):
    check_members(engine, union)


@pytest.mark.parametrize("engine,union", cells(ENGINES, "uniform"))
def test_stream_is_uniform(engine, union):
    check_uniform(engine, union)
