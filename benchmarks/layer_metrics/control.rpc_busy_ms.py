"""Median over the window's turns of the time threads other than the loop's
spent inside ray_tpu.worker.* / ray_tpu.train.next_result and not inside
ray_tpu.train.result_wait, from the spans' events in the flight recorder: the
untraced window's twin of control.idle_under_rpc_share."""
from benchmarks.lib import train_events


def read(run):
    return train_events.read(run, "control.rpc_busy_ms")
