"""The trainer's own record of the measured window, read from its file.

``JaxTrainer.fit`` leaves ``<Result.path>/train_events_rank<k>.jsonl``
(``ray_tpu/train/trainer.py write_train_events``): what the flight
recorder's ``train`` category holds of the run. A turn here is the time from
one ``REPORT`` to the next, on the monotonic clock, for the window's steps as
``lib/spans.py turn_times`` chooses them: report 0 is the set-up's, report k
the window's step k - 1, and the traced steps and the final record come after.
Every number is of the window ``tokens_per_s_per_chip`` is computed from, never
of the traced steps; only the clock offset reads those.

The five readers share one reduction a run (``metrics``), which also says, on
earlier lines: the window's summary beside the benchmark's own median turn;
every turn whose excess over the median is more than the larger of 5 ms and
5% of it, with what the record holds of that moment; how
``control.worker_ready_s`` splits; and in a traced run the offset between the
recorder's monotonic clock and the device trace's. Where the file is absent
(a parent commit under these benchmark files) every reader returns None.
"""
from __future__ import annotations

import glob
import json
import os
import statistics

from . import trace as tracing
from .spans import percentile, turn_times

FILE = os.path.join("results", "train_events_rank0.jsonl")
REPORT_SPAN = "ray_tpu.train.report"
RPC_SPANS = ("ray_tpu.worker.", "ray_tpu.train.next_result")
RPC_WAIT = "ray_tpu.train.result_wait"
NAMES = ("trainer.loop_cpu_ms", "trainer.gc_pause_ms", "control.rpc_busy_ms",
         "trainer.stall_on_cpu_ms", "trainer.stall_off_cpu_ms")


def load(run: dict):
    """The file's lines by kind, JSON types only (``--keep`` dumps the run
    record), or None without the file. Read once a run."""
    if "train_events" not in run:
        run["train_events"] = _load(os.path.join(run["out_dir"], FILE))
    return run["train_events"]


def _load(path: str):
    if not os.path.exists(path):
        return None
    out = {"header": {}, "reports": [], "usage": [], "gc": [], "overdue": [],
           "spans": [], "lifecycle": []}
    with open(path) as f:
        for text in f:
            line = json.loads(text)
            if "header" in line:
                out["header"] = line["header"]
                continue
            attrs, name = line.get("attrs") or {}, line["event"]
            if line["category"] != "train":
                out["lifecycle"].append(
                    [line["timestamp"], line["category"], name, line.get("name", "")]
                )
            elif name == "REPORT":
                out["reports"].append(
                    {**attrs, "t": line["timestamp"], "m": line["monotonic"],
                     "thread": line["entity"]}
                )
            elif name == "USAGE":
                out["usage"].append({**attrs, "m": line["monotonic"]})
            elif name == "GC_PAUSE":
                out["gc"].append({**attrs, "m": line["monotonic"],
                                  "thread": line["entity"]})
            elif name == "OVERDUE":
                out["overdue"].append({**attrs, "m": line["monotonic"]})
            else:
                out["spans"].append([name, line["entity"], attrs["m_start"],
                                     line["monotonic"], line["timestamp"]])
    for key in ("reports", "usage"):
        out[key].sort(key=lambda r: r["ordinal"])
    return out


def window_turns(run: dict, record: dict):
    """One dict a turn of the window, or None where the record does not hold
    every report of it with its usage reading (the file's header then says
    how many events were dropped). A turn's counters are the difference of
    the two readings taken as its two reports were handed over."""
    n, reports, usage = len(run["steps"]), record["reports"], record["usage"]
    held = list(range(n + 1))
    if not n or [r["ordinal"] for r in reports[:n + 1]] != held \
            or [u["ordinal"] for u in usage[:n + 1]] != held \
            or any(u["thread_cpu_ns"] is None for u in usage[:n + 1]):
        return None
    turns = []
    for a, b, was, now in zip(reports, reports[1:n + 1], usage, usage[1:n + 1]):
        turns.append({
            "ordinal": b["ordinal"], "lo": a["m"], "hi": b["m"],
            "seconds": b["m"] - a["m"],
            # How long after its report each of the two readings was taken:
            # a late one (the interpreter was held) shifts CPU between turns.
            "read_lag_s": now["m"] - b["m"], "read_lag_before_s": was["m"] - a["m"],
            "cpu_s": (now["thread_cpu_ns"] - was["thread_cpu_ns"]) * 1e-9,
            "process_cpu_s": now["process_cpu_s"] - was["process_cpu_s"],
            "nivcsw": now["nivcsw"] - was["nivcsw"],
            "majflt": now["majflt"] - was["majflt"],
            "minflt": now["minflt"] - was["minflt"],
        })
    return turns


def rpc_by_name(record: dict, loop_thread: str) -> dict:
    """span name -> [(lo, hi)]: when a thread other than the loop's was inside
    that control-plane span and not inside its wait for the next report."""
    threads = {}
    for name, thread, lo, hi, _ in record["spans"]:
        if thread != loop_thread:
            threads.setdefault(thread, []).append((name, lo, hi))
    out = {}
    for thread, spans in threads.items():
        waits = [(lo, hi) for name, lo, hi in spans if name == RPC_WAIT]
        for name, lo, hi in spans:
            if name.startswith(RPC_SPANS):
                out.setdefault((name, thread), []).extend(
                    tracing.subtract([(lo, hi)], waits)
                )
    return out


def in_turn(intervals, turn) -> float:
    return tracing.measure(tracing.clip(intervals, turn["lo"], turn["hi"]))


def metrics(run: dict):
    """{metric name: value} of the window, or None; notes said once."""
    if "train_metrics" in run:
        return run["train_metrics"]
    run["train_metrics"] = None
    record = load(run)
    if record is None:
        return None
    note = run["notes"].append
    turns = window_turns(run, record)
    if turns is None:
        note(f"train events: the record does not hold the window's "
             f"{len(run['steps'])} turns (reports {len(record['reports'])}, "
             f"usage readings {len(record['usage'])}, "
             f"dropped {record['header'].get('dropped')})")
        return None
    loop_thread = record["reports"][0]["thread"]
    by_name = rpc_by_name(record, loop_thread)
    rpc = tracing.union(i for spans in by_name.values() for i in spans)
    pauses = [(g["m"] - g["seconds"], g["m"]) for g in record["gc"]]
    median_turn = statistics.median(t["seconds"] for t in turns)
    floor = max(0.005, 0.05 * median_turn)  # a turn further over is stalled
    # The loop thread's CPU in a steady turn: the mean over the turns that are
    # not stalled. (A median would do where the CPU clock is fine; where it
    # ticks at 10 ms, as on the chip's host, a turn reads 0 or 10 and only a
    # sum over many turns is a measurement.)
    steady = [t["cpu_s"] for t in turns if t["seconds"] - median_turn <= floor]
    steady_cpu = sum(steady) / len(steady)
    on = off = under = 0.0
    for t in turns:
        t["excess"] = t["seconds"] - median_turn
        t["cpu_excess"] = max(0.0, t["cpu_s"] - steady_cpu)
        t["gc_s"] = in_turn(pauses, t)
        t["rpc_s"] = in_turn(rpc, t)
        if t["excess"] > 0:
            t_on = min(t["excess"], t["cpu_excess"])
            on, off = on + t_on, off + t["excess"] - t_on
        else:
            under -= t["excess"]
    values = {
        "trainer.loop_cpu_ms": 1e3 * steady_cpu,
        "trainer.gc_pause_ms": 1e3 * sum(t["gc_s"] for t in turns) / len(turns),
        "control.rpc_busy_ms": 1e3 * statistics.median(t["rpc_s"] for t in turns),
        "trainer.stall_on_cpu_ms": 1e3 * on,
        "trainer.stall_off_cpu_ms": 1e3 * off,
    }
    run["train_metrics"] = values

    theirs = turn_times(run)
    their_median = percentile(theirs, 50)
    note(
        f"train events: {len(turns)} turns of the window, median turn "
        f"{1e3 * median_turn:.4f} ms (the benchmark's {1e3 * their_median:.4f}, "
        f"{100 * (median_turn / their_median - 1):+.4f}%); a turn's medians: loop "
        f"thread CPU {1e3 * statistics.median(t['cpu_s'] for t in turns):.4f} ms "
        f"(mean over the {len(steady)} steady turns {1e3 * steady_cpu:.4f}), process CPU "
        f"{1e3 * statistics.median(t['process_cpu_s'] for t in turns):.4f} ms (mean "
        f"{1e3 * sum(t['process_cpu_s'] for t in turns) / len(turns):.4f}), "
        f"involuntary switches {statistics.median(t['nivcsw'] for t in turns):g}, "
        f"minor faults {statistics.median(t['minflt'] for t in turns):g}, read "
        f"{1e3 * statistics.median(t['read_lag_s'] for t in turns):.4f} ms after "
        f"the report; window "
        f"totals: switches {sum(t['nivcsw'] for t in turns)}, major faults "
        f"{sum(t['majflt'] for t in turns)}, minor faults "
        f"{sum(t['minflt'] for t in turns)}, collector "
        f"{1e3 * sum(t['gc_s'] for t in turns):.3f} ms in "
        f"{sum(1 for lo, hi in pauses if hi > turns[0]['lo'] and lo < turns[-1]['hi'])}"
        f" collections; overdue samples {len(record['overdue'])}; events dropped "
        f"{record['header'].get('dropped')}"
    )
    note(
        f"train events: stall on CPU {1e3 * on:.3f} + off CPU {1e3 * off:.3f} = "
        f"{1e3 * (on + off):.3f} ms over the turns above the median; the turns "
        f"under it give back {1e3 * under:.3f}, leaving {1e3 * (on + off - under):.3f} "
        f"ms against trainer.stall_share x the window's "
        f"{1e3 * (sum(theirs) - len(theirs) * their_median):.3f} ms"
    )
    for t in turns:
        if t["excess"] > floor:
            note("train events: stalled turn " + json.dumps(
                stalled_turn(t, record, by_name)
            ))
    for text in (worker_ready_split(run, record), clock_offset(run, record)):
        if text:
            note(text)
    return values


def stalled_turn(t: dict, record: dict, by_name: dict) -> dict:
    """What the record holds of one turn, for its line."""
    spans = [[name, thread, round(1e3 * in_turn(where, t), 3)]
             for (name, thread), where in sorted(by_name.items())]
    return {
        "ordinal": t["ordinal"], "turn_ms": round(1e3 * t["seconds"], 3),
        "excess_ms": round(1e3 * t["excess"], 3),
        "loop_cpu_excess_ms": round(1e3 * t["cpu_excess"], 3),
        "process_cpu_ms": round(1e3 * t["process_cpu_s"], 3),
        "read_lag_ms": [round(1e3 * t["read_lag_before_s"], 3),
                        round(1e3 * t["read_lag_s"], 3)],
        "gc_ms": round(1e3 * t["gc_s"], 3),
        "gc": [[g["generation"], g["thread"], round(1e3 * g["seconds"], 3)]
               for g in record["gc"] if t["lo"] < g["m"] <= t["hi"]],
        "rpc_spans": [s for s in spans if s[2] > 0.01],
        "nivcsw": t["nivcsw"], "majflt": t["majflt"], "minflt": t["minflt"],
        # [waited s, the watchdog's own worst oversleep s, innermost frames]
        "overdue": [[round(o["waited_s"], 4), round(o.get("overslept_s", 0.0), 4),
                     o["frames"][:4]]
                    for o in record["overdue"] if o["ordinal"] == t["ordinal"]],
    }


def worker_ready_split(run: dict, record: dict):
    """control.worker_ready_s by the recorder's own stamps, seconds from the
    command's start (wall clocks of one host)."""
    if "t_command" not in run or "t_fit" not in record["header"]:
        return None
    t0 = run["t_command"]
    marks = [["fit", record["header"]["t_fit"] - t0]]
    for t, category, event, name in record["lifecycle"]:
        if category == "worker" or event in ("EXEC_START", "EXEC_END"):
            marks.append([f"{name or category} {event}".strip(), t - t0])
    ready = run["setup"]["t_loop"] - t0
    marks = sorted((m for m in marks if m[1] <= ready), key=lambda m: m[1])
    marks.append(["the loop's first line", ready])
    return "control.worker_ready_s split (s from the command's start): " + json.dumps(
        [[name, round(at, 3)] for name, at in marks]
    )


def traced_xplane(run: dict):
    paths = glob.glob(os.path.join(
        run["out_dir"], "trace", "plugins", "profile", "*", "*.xplane.pb"
    ))
    return paths[0] if paths else None


def profile_start_s(path: str):
    """The profiler session's own start stamp (the xplane's ``Task
    Environment`` plane), seconds: host lines count from it."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                return float(value) * 1e-9
    return None


def program_of(run: dict, path: str):
    """The run's program trace, shared with ``program_trace.of`` through the
    run record; a trace without a device plane (a CPU run) has one too."""
    from . import program_trace

    if "program_trace" not in run:
        with open(os.path.join(run["out_dir"], "step.hlo.txt")) as f:
            run["program_trace"] = program_trace.load_xplane(path, f.read()).to_json()
    return program_trace.ProgramTrace.from_json(run["program_trace"])


def clock_offset(run: dict, record: dict):
    """In a traced run the traced steps' ``ray_tpu.train.report`` spans are in
    the xplane and in the record: the median difference of their starts lays
    any event of the record on the device trace's clock."""
    path = traced_xplane(run)
    if path is None:
        return None
    program = program_of(run, path)
    if program.loop_thread is None:
        return None
    theirs = sorted((e.start, e.end) for e in program.threads[program.loop_thread]
                    if e.name == REPORT_SPAN)
    loop_thread = record["reports"][0]["thread"]
    mine = sorted((lo, hi, wall) for name, thread, lo, hi, wall in record["spans"]
                  if name == REPORT_SPAN and thread == loop_thread)
    first = len(run["steps"]) + 1  # report 0 is the set-up's
    mine = mine[first:first + len(theirs)]
    if not theirs or len(mine) != len(theirs):
        return (f"clock offset: {len(theirs)} report spans in the device trace, "
                f"{len(mine)} in the record after the window: not matched")
    diffs = [x[0] - m[0] for x, m in zip(theirs, mine)]
    offset = statistics.median(diffs)
    residual = percentile([abs(d - offset) for d in diffs], 95)
    text = (
        f"clock offset: device trace = recorder's monotonic {offset:+.6f} s over "
        f"{len(diffs)} matched report spans, residual p95 {1e3 * residual:.4f} ms"
    )
    start = profile_start_s(path)
    if start is not None:
        wall = statistics.median(start + x[1] - m[2] for x, m in zip(theirs, mine))
        text += (
            f"; the trace's own start stamp {start:.6f} s plus a span's end, "
            f"less the recorder's wall stamp of that end: {1e3 * wall:+.4f} ms"
        )
    return text


def read(run: dict, name: str):
    values = metrics(run)
    return None if values is None else values[name]
