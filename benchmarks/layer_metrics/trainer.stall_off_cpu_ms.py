"""The rest of the excess of the window's turns longer than the median turn,
beyond trainer.stall_on_cpu_ms: the loop's thread was waiting (for the device,
the runtime, a lock, the interpreter, or to be scheduled). The two sum to
trainer.stall_share x the window plus what the turns under the median give
back, which an earlier line says."""
from benchmarks.lib import train_events


def read(run):
    return train_events.read(run, "trainer.stall_off_cpu_ms")
