"""Device time of operations of the forward pass (metadata path under jvp(
and under neither transpose( nor a remat replay) over device busy time."""
from benchmarks.lib import program_trace


def read(run):
    shares = program_trace.pass_shares(run)
    return None if shares is None else shares.get("forward", 0.0)
