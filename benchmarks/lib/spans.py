"""Arithmetic on the benchmark's own host spans: the window's step records
(``t_start``, ``t_dispatch``, ``t_done`` in seconds from the window's start,
and ``loss``). The profiler never runs inside the window."""
from __future__ import annotations


def turn_times(run: dict) -> list:
    """Seconds of each turn of the loop in the window: from one loss
    arriving to the next (for the first step, from the window's start)."""
    done = [0.0] + [s["t_done"] for s in run["steps"]]
    return [b - a for a, b in zip(done, done[1:])]


def tokens_per_s_per_chip(run: dict):
    """Tokens of the steps completed in the window, over the host-clock time
    from the first of them starting to the last one's loss arriving, over
    chips: ``attempted`` x tokens a step over the seconds they took. Every
    step counts, so a cost that falls on a few steps (a save, an ingest
    stall, a collection, work put off to every Nth step) shows here."""
    steps = run["steps"]
    if not steps:
        return None
    traffic = run["cell"]["traffic"]
    seconds = steps[-1]["t_done"] - steps[0]["t_start"]
    return len(steps) * traffic["batch"] * traffic["seq"] / seconds / run["cell"]["chips"]


def median_step_tokens_per_s_per_chip(run: dict):
    """Tokens of one step over the median turn of the loop, over chips: the
    rate of the steady step, which a few stalled steps do not move. It says
    whether a change in ``tokens_per_s_per_chip`` is in every step or in a
    few (the chip machines themselves stall a step by 0.1 s or more about
    once in 400: PERF.md, Findings, PR 22)."""
    if not run["steps"]:
        return None
    traffic = run["cell"]["traffic"]
    turn = percentile(turn_times(run), 50)
    return traffic["batch"] * traffic["seq"] / turn / run["cell"]["chips"]


def step_gaps_ms(run: dict) -> list:
    """Loss arrived -> next step dispatched: report, next batch, device_put."""
    steps = run["steps"]
    return [1e3 * (b["t_dispatch"] - a["t_done"]) for a, b in zip(steps, steps[1:])]


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty list, q in [0, 100]."""
    ordered = sorted(values)
    at = (len(ordered) - 1) * q / 100.0
    lo = int(at)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (at - lo)


def tail_percentile(n: int, beyond: int = 10) -> float:
    """The highest percentile, at most 95, that has ``beyond`` samples
    beyond it among ``n``."""
    return min(95.0, max(50.0, 100.0 * (1 - beyond / n))) if n else 50.0
