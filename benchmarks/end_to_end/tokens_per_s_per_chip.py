"""Tokens of the steps completed in the window over the host-clock time from
the first of them starting to the last one's loss arriving, over the cell's
chips (benchmarks/lib/spans.py)."""
from benchmarks.lib.spans import tokens_per_s_per_chip as read  # noqa: F401
