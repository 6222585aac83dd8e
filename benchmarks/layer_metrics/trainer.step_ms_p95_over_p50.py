"""Tail of the loop's turn time over its median: p95 where at least ten
steps lie beyond it, else the highest percentile that has ten. Which one is
said on an earlier line of the output. A window of under twenty steps has no
percentile above its median with ten beyond it: nothing to read."""
from benchmarks.lib.spans import percentile, tail_percentile, turn_times


def read(run):
    times = turn_times(run)
    if len(times) < 20:
        return None
    q = tail_percentile(len(times))
    run["notes"].append(
        f"trainer.step_ms_p95_over_p50: p{q:g} over p50 of {len(times)} steps"
    )
    return percentile(times, q) / percentile(times, 50)
