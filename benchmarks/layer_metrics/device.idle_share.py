"""1 - union of busy intervals over the traced steady window, on device 0
(the worst device of a multi-chip cell is said on an earlier line)."""
from benchmarks.lib import trace as tracing


def read(run):
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    idle = {
        d: 100.0 * (1 - tracing.busy_seconds(ev, window) / (window[1] - window[0]))
        for d, ev in trace.devices.items()
    }
    if len(idle) > 1:
        worst = max(idle, key=idle.get)
        run["notes"].append(
            f"device.idle_share: worst device {worst} at {idle[worst]:.3f}%"
        )
    return idle[device]
