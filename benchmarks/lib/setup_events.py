"""Set-up as the program's own record holds it, read from the trainer's file.

While a train session is held the program leaves, in the flight recorder's
``train`` category, one span for every trace, lowering, backend compile and
persistent-cache read JAX reports (``ray_tpu/util/tracing.py``
``watch_compiles``, ``SETUP_SPANS``), the cache's hits and misses as spans of
no length, and ``parallel.shard_params`` as a span. Set-up here is everything
up to the set-up's ``REPORT`` (ordinal 0) on the monotonic clock, on every
thread. A jit called while another is traced sends an interval inside its
caller's, so a stage is the union of its intervals, never their sum.

The five readers share one reduction a run (``metrics``), which also says
once, on an earlier line, set-up by function: every executable of set-up in
the order they were made, with the seconds of its trace, its lowering and its
backend span and whether the persistent cache held it (the small ones
together); the ten inner traces that took longest by function, with their
counts; the tally of the durations too short for an event; what of ``setup_s``
no span covers; and any compile that ended after set-up's report, with the
ordinal of the turn it fell in: the step that recompiled. Where the file is
absent, or holds none of these spans (a parent commit under these benchmark
files), every reader returns None.
"""
from __future__ import annotations

import collections
import json
import os

from . import trace as tracing
from . import train_events

PREFIX = "ray_tpu.compile."
TRACE, LOWER, BACKEND = PREFIX + "trace", PREFIX + "lower", PREFIX + "backend"
CACHE_HIT, CACHE_MISS = PREFIX + "cache_hit", PREFIX + "cache_miss"
SHORT = PREFIX + "short"
SHARD_PARAMS = "ray_tpu.parallel.shard_params"
NAMES = ("step.trace_s", "step.lower_s", "step.executables_s",
         "step.cache_misses", "mesh.shard_params_s")
#: An executable whose trace, lowering and backend span come to less is
#: said with the other small ones: the eager operations' programs.
SMALL_S = 0.1


def load(run: dict):
    """Set-up's spans with what they say of themselves, ``[name, thread, lo,
    hi, attrs]`` by end (``train_events.load`` keeps the interval alone), or
    None without the file. JSON types only; read once a run."""
    if "setup_events" not in run:
        path = os.path.join(run["out_dir"], train_events.FILE)
        spans = None
        if os.path.exists(path):
            spans = []
            with open(path) as f:
                for text in f:
                    line = json.loads(text)
                    name = line.get("event", "")
                    if line.get("category") == "train" and (
                            name.startswith(PREFIX) or name == SHARD_PARAMS):
                        attrs = dict(line["attrs"])
                        spans.append([name, line["entity"], attrs.pop("m_start"),
                                      line["monotonic"], attrs])
            spans.sort(key=lambda s: s[3])
        run["setup_events"] = spans
    return run["setup_events"]


def intervals(spans, name: str) -> list:
    return [(lo, hi) for n, _, lo, hi, _ in spans if n == name]


def outermost(spans) -> list:
    """Of one stage's spans, those that lie inside no other of their thread."""
    out, cover = [], {}
    for span in sorted(spans, key=lambda s: (s[1], s[2], -s[3])):
        if span[3] > cover.get(span[1], float("-inf")):
            out.append(span)
            cover[span[1]] = span[3]
    return out


def executables(spans) -> list:
    """One row an executable, in the order they were made: the backend span,
    the lowering of the same name that ended last before it on its thread,
    the outermost trace of that function that ended last before that, and
    the cache's hit or miss inside the backend span."""
    free = {LOWER: [s for s in spans if s[0] == LOWER],
            TRACE: outermost([s for s in spans if s[0] == TRACE])}

    def claim(stage, thread, fun_name, before):
        found = [s for s in free[stage] if s[1] == thread and s[3] <= before
                 and s[4].get("fun_name") == fun_name]
        if not found:
            return None
        free[stage].remove(found[-1])
        return found[-1]

    rows = []
    for name, thread, lo, hi, attrs in spans:
        if name != BACKEND:
            continue
        jitted = attrs.get("fun_name") or ""
        function = jitted[4:-1] if jitted.startswith("jit(") else jitted
        lowered = claim(LOWER, thread, jitted, lo)
        traced = claim(TRACE, thread, function, lo if lowered is None else lowered[2])
        cache = [n for n, t, _, at, _ in spans
                 if t == thread and lo <= at <= hi and n in (CACHE_HIT, CACHE_MISS)]
        rows.append({
            "fun_name": function, "at": lo if traced is None else traced[2],
            "trace_s": 0.0 if traced is None else traced[3] - traced[2],
            "lower_s": 0.0 if lowered is None else lowered[3] - lowered[2],
            "backend_s": hi - lo,
            "cache": "hit" if CACHE_HIT in cache else "miss" if CACHE_MISS in cache
            else "not asked",
        })
    # A trace that made no executable of its own (eval_shape, a lowering
    # kept for later) is a row too.
    for name, thread, lo, hi, attrs in free[TRACE]:
        rows.append({"fun_name": attrs.get("fun_name"), "at": lo, "trace_s": hi - lo,
                     "lower_s": 0.0, "backend_s": 0.0, "cache": "no executable"})
    return sorted(rows, key=lambda r: r["at"])


def inner_traces(spans, n: int = 10) -> list:
    """``[fun_name, count, seconds]`` of the traces inside another trace, the
    ``n`` that took longest. A function traced inside an inner one is in
    both: lines to read, not to add up."""
    traces = [s for s in spans if s[0] == TRACE]
    outer = {id(s) for s in outermost(traces)}
    by_name = {}
    for span in traces:
        if id(span) not in outer:
            entry = by_name.setdefault(span[4].get("fun_name"), [0, 0.0])
            entry[0] += 1
            entry[1] += span[3] - span[2]
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:n]
    return [[name, count, round(seconds, 3)] for name, (count, seconds) in ranked]


def metrics(run: dict):
    """{metric name: value} of set-up, or None; the note said once."""
    if "setup_metrics" in run:
        return run["setup_metrics"]
    run["setup_metrics"] = None
    spans, record = load(run), train_events.load(run)
    if not spans or not record["reports"] or record["reports"][0]["ordinal"] != 0:
        return None
    ready = record["reports"][0]["m"]  # set-up's report
    setup = [s for s in spans if s[3] <= ready and s[0] != SHORT]
    placing = intervals(setup, SHARD_PARAMS)
    values = {
        "step.trace_s": tracing.measure(intervals(setup, TRACE)),
        "step.lower_s": tracing.measure(intervals(setup, LOWER)),
        "step.executables_s": tracing.measure(intervals(setup, BACKEND)),
        "step.cache_misses": len(intervals(setup, CACHE_MISS)),
        "mesh.shard_params_s": sum(hi - lo for lo, hi in placing) if placing else None,
    }
    run["setup_metrics"] = values
    run["notes"].append("set-up by function: " + json.dumps(
        by_function(run, record, spans, setup, values)))
    return values


def by_function(run: dict, record: dict, spans, setup, values) -> dict:
    """What the note says, JSON types only."""
    said, small = [], []
    for r in executables(setup):
        if r["trace_s"] + r["lower_s"] + r["backend_s"] < SMALL_S:
            small.append(r)
        else:
            said.append([r["fun_name"], round(r["trace_s"], 3), round(r["lower_s"], 3),
                         round(r["backend_s"], 3), r["cache"]])
    counts = collections.Counter(name.removeprefix(PREFIX) for name, *_ in setup)
    covered = tracing.measure(
        [i for name in (TRACE, LOWER, BACKEND, SHARD_PARAMS) for i in intervals(setup, name)]
    )
    out = {
        # [function, trace s, lower s, backend s, the cache] in the order made
        "executables": said,
        "small": {"count": len(small),
                  "trace_s": round(sum(r["trace_s"] for r in small), 3),
                  "lower_s": round(sum(r["lower_s"] for r in small), 3),
                  "backend_s": round(sum(r["backend_s"] for r in small), 3),
                  "hits": sum(1 for r in small if r["cache"] == "hit"),
                  "misses": sum(1 for r in small if r["cache"] == "miss")},
        "inner_traces": inner_traces(setup),  # [function, count, s]
        "events": dict(counts), "dropped": record["header"].get("dropped"),
        # {stage: [count, s]} under the program's floor, of the whole session
        "short": next((s[4] for s in spans if s[0] == SHORT), None),
        "stages_s": {k.rsplit(".", 1)[1]: round(v, 3) for k, v in values.items()
                     if v is not None and k != "step.cache_misses"},
        "covered_s": round(covered, 3),
    }
    setup_s = run["setup"]["t_ready"] - run["t_command"]
    worker_ready_s = run["setup"]["t_loop"] - run["t_command"]
    out["setup_s"] = round(setup_s, 3)
    out["worker_ready_s"] = round(worker_ready_s, 3)
    # the backend's start, the programs' runs, the corpus, the optimizer
    out["no_span_s"] = round(setup_s - worker_ready_s - covered, 3)
    reports = record["reports"]
    late = []
    for name, _, lo, hi, attrs in spans:
        if hi > reports[0]["m"] and name in (TRACE, LOWER, BACKEND):
            turn = next((r["ordinal"] for r in reports if r["m"] >= hi), None)
            late.append([name.removeprefix(PREFIX), attrs.get("fun_name"),
                         round(1e3 * (hi - lo), 3), turn])
    # [stage, function, ms, ordinal of the turn; null after the last report]
    out["compiled_after_setup"] = {"count": len(late), "first": late[:10]}
    return out


def read(run: dict, name: str):
    values = metrics(run)
    return None if values is None else values[name]
