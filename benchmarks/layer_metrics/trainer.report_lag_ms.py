"""Median over the traced steps of ray_tpu.train.report starting to the
ray_tpu.train.next_result that carries that report ending (first in, first
out). The last step's lag over the first's is said on an earlier line."""
from benchmarks.lib import program_trace


def read(run):
    return program_trace.report_lag_ms(run)
