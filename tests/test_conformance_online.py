"""ONLINE-UNION in the engine × union matrix, on numpy and on jax: the
membership test only (``_conformance``)."""

import pytest

from _conformance import cells, check_members

ENGINES = ("online-numpy", "online-jax")


@pytest.mark.parametrize("engine,union", cells(ENGINES, "member"))
def test_rows_are_members_of_their_home_piece(engine, union):
    check_members(engine, union)
