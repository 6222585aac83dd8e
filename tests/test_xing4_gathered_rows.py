"""Xing4's architecture through the program's models, on the CPU: a held share's
rows gathered against walked, and against the uncut layer, at rank 0 of four
(``tests/xing4_cases.py`` has the body: each case traces the layer three times
from no cache, so the five are a file a rank).
"""
import pytest

from xing4_cases import (  # noqa: F401 - fixtures
    fresh_traces, gathered_rows_give_what_walked_rows_give, interpret,
)


@pytest.mark.parametrize("rank, routing", [
    (0, "expected-share"),
])
def test_gathered_rows_give_what_walked_rows_give(rank, routing, fresh_traces, monkeypatch):
    gathered_rows_give_what_walked_rows_give(rank, routing, monkeypatch)
