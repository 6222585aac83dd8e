"""Mean over the window's turns of the collector's time in a turn, from the
GC_PAUSE events of the trainer's own record (a gc.callbacks hook; any thread's
collection holds the interpreter). A mean: most turns have none."""
from benchmarks.lib import train_events


def read(run):
    return train_events.read(run, "trainer.gc_pause_ms")
