"""Share of the window's time that the loop's turns took beyond their
median: what a few stalled steps (the machine's, or a change's) cost
tokens_per_s_per_chip against trainer.median_step_tokens_per_s."""
from benchmarks.lib.spans import percentile, turn_times


def read(run):
    turns = turn_times(run)
    if not turns:
        return None
    return 100.0 * (1 - len(turns) * percentile(turns, 50) / sum(turns))
