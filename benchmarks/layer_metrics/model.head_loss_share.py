"""Device time of the head and the loss (the full-logit path's /lm_head/
module; the loss functions' scope, which JAX renders (loss) directly under a
transform and /loss/ inside another scope: the chunked head's float32 matmuls
with their replay, the soft-max arithmetic, the gather of the gold logit;
forward, backward and replay) over device busy time, device 0. In
pretrain-mtp-4k it overlaps model.mtp_share by the module's pass of the head,
jvp(mtp)/loss/, as that metric overlaps model.mla_share, model.moe_* and
model.hc_share. Nothing to read in a program that names neither."""
from benchmarks.lib import step_table, trace as tracing

MARKS = (f"/{step_table.LM_HEAD}/", f"({step_table.LOSS})", f"/{step_table.LOSS}/")


def in_head_or_loss(event):
    return any(mark in event.path for mark in MARKS)


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    events = trace.devices[device]
    if not any(in_head_or_loss(e) for e in events):
        return None
    share = tracing.share_of_busy(events, window, in_head_or_loss)
    return None if share is None else 100.0 * share
