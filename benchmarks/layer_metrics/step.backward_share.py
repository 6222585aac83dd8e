"""Device time of operations of the backward pass (metadata path under
transpose( and not a remat replay) over device busy time. A fusion whose body
holds both backward and optimizer instructions counts here; an earlier line
says how much of this share such fusions are."""
from benchmarks.lib import program_trace


def read(run):
    shares = program_trace.pass_shares(run)
    return None if shares is None else shares.get("backward", 0.0)
