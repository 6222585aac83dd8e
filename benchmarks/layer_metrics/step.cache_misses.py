"""Executables of set-up that the persistent cache did not hold and that were
compiled and written there: the program's ray_tpu.compile.cache_miss events
before set-up's report. 0 in a warm run; above 0 says this side of a pair ran
cold, which setup_s alone cannot tell from a slower program."""
from benchmarks.lib import setup_events


def read(run):
    return setup_events.read(run, "step.cache_misses")
