"""Device time of operations under /moe/experts/ (the three expert matmuls
and the activation; forward, backward and replay) over device busy time.
Nothing to read in a program whose MoE layer carries no such scope."""
from benchmarks.lib import program_trace


def read(run):
    shares = program_trace.moe_shares(run)
    return None if shares is None else shares["experts"]
