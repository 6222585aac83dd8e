"""Device time of operations under /moe/router/, /moe/dispatch/ and
/moe/combine/, the collectives GSPMD put there included, over device busy
time. Nothing to read in a program whose MoE layer carries no such scope."""
from benchmarks.lib import program_trace


def read(run):
    shares = program_trace.moe_shares(run)
    return None if shares is None else shares["dispatch"]
