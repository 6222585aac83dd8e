"""Over the window's turns longer than the median turn, the sum of min(the
turn's excess, the excess of its loop-thread CPU time over a steady turn's): stall
time in which the loop's thread was running host code. An earlier line lists
every turn with an excess over the larger of 5 ms and 5% of the median."""
from benchmarks.lib import train_events


def read(run):
    return train_events.read(run, "trainer.stall_on_cpu_ms")
