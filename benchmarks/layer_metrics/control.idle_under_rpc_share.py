"""Share of the between-programs idle time during which a thread other than
the loop's is inside ray_tpu.worker.* or ray_tpu.train.next_result and not
inside ray_tpu.train.result_wait. An earlier line gives the same idle time by
program span, longest first, beside the bench.* phase it fell in."""
from benchmarks.lib import program_trace


def read(run):
    return program_trace.idle_under_rpc(run)
