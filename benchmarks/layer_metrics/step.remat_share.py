"""Device time of operations under rematted_computation, the forward run
again for the backward, over device busy time."""
from benchmarks.lib import program_trace


def read(run):
    shares = program_trace.pass_shares(run)
    return None if shares is None else shares.get("replay", 0.0)
