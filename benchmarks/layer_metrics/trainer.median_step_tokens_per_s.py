"""Tokens of one step over the median turn of the loop in the window, over
chips: the rate of the steady step. Beside tokens_per_s_per_chip, which counts
every step, it says whether a change sits in every step or in a few stalled
ones; trainer.stall_share is the distance between the two."""
from benchmarks.lib.spans import median_step_tokens_per_s_per_chip as read  # noqa: F401
