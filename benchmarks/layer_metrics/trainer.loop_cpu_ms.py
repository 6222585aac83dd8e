"""The loop thread's CPU time in a steady turn of the window (one REPORT of the
trainer's own record to the next): the mean, over the turns that are not
stalled, of the difference of the two USAGE readings of that thread's CPU clock
taken as the turn's two reports were handed over. The per-step host work,
measured on the thread that does it; near the whole turn, the runtime
busy-waits on that thread. A mean and not a median: the chip's host ticks its
CPU clocks at 10 ms, so one turn reads 0 or 10 (the median is on an earlier
line)."""
from benchmarks.lib import train_events


def read(run):
    return train_events.read(run, "trainer.loop_cpu_ms")
