"""The engine × union matrix on the host engines: the numpy reference in
probe and record mode (``_conformance``)."""

import pytest

from _conformance import cells, check_members, check_uniform

ENGINES = ("numpy", "numpy-record")


@pytest.mark.parametrize("engine,union", cells(ENGINES, "member"))
def test_rows_are_members_of_their_home_piece(engine, union):
    check_members(engine, union)


@pytest.mark.parametrize("engine,union", cells(ENGINES, "uniform"))
def test_stream_is_uniform(engine, union):
    check_uniform(engine, union)
