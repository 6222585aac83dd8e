"""Median over the window of loss arrived -> next step dispatched:
train.report, the next host batch and its device_put. The device waits
through all of it."""
from benchmarks.lib.spans import percentile, step_gaps_ms


def read(run):
    gaps = step_gaps_ms(run)
    return percentile(gaps, 50) if gaps else None
