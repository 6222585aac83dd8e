"""From a run record to the result line the contract fixes."""
from __future__ import annotations

import glob
import json
import os
import shutil

from . import cells, checks, trace as tracing

KEYS = ("correct", "attempted", "failed", "metrics", "device")  # + breakdown


def load_trace(run: dict):
    found = glob.glob(os.path.join(
        run["out_dir"], "trace", "plugins", "profile", "*", "*.xplane.pb"
    ))
    if not found:
        return None
    with open(os.path.join(run["out_dir"], "step.hlo.txt")) as f:
        hlo = f.read()
    return tracing.load_xplane(found[0], hlo)


def memory_peak_bytes(run: dict) -> int:
    """The peak on the fullest chip: the larger of the backend's own
    ``peak_bytes_in_use`` and what the compiled step holds while it runs.
    The backend's counter leaves a program's temporaries out (it read
    6.94 GB in a cell whose step the compiler gives 13.9 GiB, PERF.md)."""
    return max(max(run["final"]["peak_bytes_in_use"]), run["setup"]["step_bytes"])


def device_block(run: dict, trace) -> dict:
    setup = run["setup"]
    device = {
        "platform": setup["platform"], "kind": setup["device_kind"],
        "count": setup["device_count"],
        "memory_peak_bytes": memory_peak_bytes(run),
    }
    if trace is not None and trace.devices:
        window = tracing.step_window(trace)
        busy = [tracing.busy_seconds(ev, window) for ev in trace.devices.values()]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = window[1] - window[0]
    return device


def breakdown(trace) -> dict:
    window = tracing.step_window(trace)
    events = trace.devices[min(trace.devices)]
    return {
        "device_ops": tracing.top_ops(events, window),
        "idle_gaps": tracing.idle_gaps(events, window, trace.host)[:10],
    }


def result_line(run: dict) -> tuple:
    """(the result object, notes to print on earlier lines)."""
    cell, setup, final = run["cell"], run["setup"], run["final"]
    verdict = checks.decide(setup, run["steps"], final, cell)
    notes = [
        "checks: " + json.dumps(verdict),
        "reference: " + json.dumps(setup["reference"]),
        "set-up phases (s): " + json.dumps(
            {k: round(v, 2) for k, v in setup["phases"].items()}
            | {"worker_ready_s": round(setup["t_loop"] - run["t_command"], 2)}
        ),
        "compile cache: " + json.dumps(setup["compiles"])
        + f" at {setup['cache_dir']}",
        f"peak bytes: backend {max(final['peak_bytes_in_use'])}, compiled "
        f"step {setup['step_bytes']}",
        f"steps in window: {len(run['steps'])} (+{final['traced_steps']} traced "
        f"after it), reported through the "
        f"trainer: {run['reported_steps']}, moe_dispatch: "
        f"{setup['moe_dispatch']}, pallas kernels: {setup['pallas_kernels']}, "
        f"collectives: {setup['collectives']}",
    ]
    if final["error"]:
        notes.append("step failed: " + final["error"])
    trace = load_trace(run) if run["trace"] else None
    run["trace_data"] = trace
    run["notes"] = notes
    line = {
        "correct": all(verdict.values()),
        "attempted": len(run["steps"]) + final["traced_steps"] + final["failed"],
        "failed": final["failed"]
        + sum(1 for s in run["steps"] if s["loss"] != s["loss"]
              or abs(s["loss"]) == float("inf")),
        "metrics": {},
        "device": device_block(run, trace),
    }
    if run["rehearsal"]:
        line["rehearsal"] = True  # a CPU run: no metric value is printed
        return line, notes
    kind = "per_layer" if run["trace"] else "end_to_end"
    directory = "layer_metrics" if run["trace"] else "end_to_end"
    line["metrics"] = cells.read_metrics(
        cell[kind], os.path.join(cells.BENCH_DIR, directory), run
    )
    if trace is not None and trace.devices:
        line["breakdown"] = breakdown(trace)
    return line, notes


def keep(run: dict, notes: list, directory: str) -> None:
    """For looking at a run by hand: the record, the notes, and in a traced
    run the raw trace and the compiled step's text."""
    os.makedirs(directory, exist_ok=True)
    record = {k: v for k, v in run.items() if k not in ("trace_data", "cell")}
    with open(os.path.join(directory, "run.json"), "w") as f:
        json.dump({**record, "notes": notes}, f)
    for path in glob.glob(os.path.join(run["out_dir"], "step.hlo.txt")) + glob.glob(
        os.path.join(run["out_dir"], "trace", "plugins", "profile", "*", "*.xplane.pb")
    ):
        shutil.copy(path, directory)
