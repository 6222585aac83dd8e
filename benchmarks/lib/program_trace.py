"""What the program's own spans and scopes add to a device trace.

``lib/trace.py`` reduces a run's xplane to the device's operations and the
benchmark's ``bench.*`` spans. This module reads the same file, on the same
clock, for what that leaves out:

- host events whose name starts with ``ray_tpu.`` (``ray_tpu/util/tracing.py``
  names them), each with the thread (xplane line) it ran on, and which of
  those threads is the loop's (the one that carries ``bench.step``);
- the device plane's "XLA Modules" line: one event per execution of the
  jitted train step, the program's own span on the device. Where a libtpu
  writes no such line an execution is taken from the first to the last
  operation between two ``bench.dispatch`` starts;
- from the compiled text, for every ``fusion`` the ``op_name`` of the
  instructions in the computation it ``calls``: a fusion is one device event
  named for one of them, and may hold forward, backward and optimizer work.

Interval arithmetic, ``step_window`` and ``self_times`` are ``lib/trace.py``'s.
A trace of a program that has no such span or scope (a parent commit) gives
empty lists, and the readers built on this return None for it.

``python -m benchmarks.lib.program_trace record <xplane.pb> <step.hlo.txt>
<out.json.gz> <key>`` cuts a trace to two steps of its first device and keeps
them under ``key`` in a recorded file for the tests.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import statistics
import sys
from collections import defaultdict
from dataclasses import dataclass, field

from . import trace as tracing
from .trace import Event

PROGRAM_PREFIX = "ray_tpu."
MODULES_LINE = "XLA Modules"
STEP_MODULE = "jit_train_step"
# Spans under which a thread serves the control plane, and the one wait
# inside them: ray_tpu/util/tracing.py says the names.
RPC_SPANS = ("ray_tpu.worker.", "ray_tpu.train.next_result")
RPC_WAIT = "ray_tpu.train.result_wait"
REPORT, NEXT_RESULT = "ray_tpu.train.report", "ray_tpu.train.next_result"
MOE_SCOPES = {"experts": ("/moe/experts/",),
              "dispatch": ("/moe/router/", "/moe/dispatch/", "/moe/combine/")}


@dataclass
class ProgramTrace:
    threads: list = field(default_factory=list)  # per host thread: [Event] of ray_tpu.* spans
    loop_thread: int | None = None  # index into threads of the loop's, if it left a span
    programs: dict = field(default_factory=dict)  # device id -> [Event], executions of the step
    bodies: dict = field(default_factory=dict)  # fusion instruction -> [op_name] of its body

    def to_json(self) -> dict:
        def rows(events):
            return [[e.name, e.start, e.dur] for e in events]

        return {"threads": [rows(t) for t in self.threads],
                "loop_thread": self.loop_thread,
                "programs": {str(d): rows(ev) for d, ev in self.programs.items()},
                "bodies": self.bodies}

    @classmethod
    def from_json(cls, data: dict) -> "ProgramTrace":
        def events(rows):
            return [Event(*row) for row in rows]

        return cls([events(t) for t in data["threads"]], data["loop_thread"],
                   {int(d): events(r) for d, r in data["programs"].items()},
                   data["bodies"])


# ------------------------------------------------------------------ loading


def fusion_bodies(hlo_text: str) -> dict:
    """Fusion instruction name -> the ``op_name``s of the instructions of
    the computation it calls (those that carry one), from the compiled text."""
    computations, calls, current = {}, {}, None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if current is None:
            head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", stripped)
            if head:
                current = computations.setdefault(head.group(1), [])
            continue
        if stripped == "}":
            current = None
            continue
        meta = re.search(r'op_name="([^"]*)"', line)
        if meta:
            current.append(meta.group(1))
        called = re.search(r" fusion\(.*calls=%?([\w.\-]+)", line)
        name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        if called and name:
            calls[name.group(1)] = called.group(1)
    return {name: computations.get(body, []) for name, body in calls.items()}


def load_xplane(path: str, hlo_text: str = "") -> ProgramTrace:
    from jax.profiler import ProfileData

    out = ProgramTrace(bodies=fusion_bodies(hlo_text))
    for plane in ProfileData.from_file(path).planes:
        device = tracing.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device:
                if line.name == MODULES_LINE:
                    out.programs[int(device.group(1))] = sorted(
                        (Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                         for e in line.events if e.name.startswith(STEP_MODULE)),
                        key=lambda e: e.start,
                    )
                continue
            mine, loops = [], False
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    mine.append(Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
                elif e.name == "bench.step":
                    loops = True
            if mine or loops:
                if loops:
                    out.loop_thread = len(out.threads)
                out.threads.append(sorted(mine, key=lambda e: (e.start, -e.dur)))
    return out


def executions_from_dispatch(trace, device: int) -> list:
    """Without a modules line: one execution from the first to the last
    operation between two ``bench.dispatch`` starts."""
    starts = [e.start for e in trace.host if e.name == "bench.dispatch"]
    ops, out = trace.devices[device], []
    for lo, hi in zip(starts, starts[1:] + [float("inf")]):
        inside = [e for e in ops if lo <= e.start < hi]
        if inside:
            out.append(Event(STEP_MODULE, inside[0].start,
                             max(e.end for e in inside) - inside[0].start))
    return out


def of(run: dict):
    """(program trace, trace, device id, window) of a traced run, or None.
    Read from the files once a run: the readers share it through the run
    record, which holds it as ``to_json`` gives it (``--keep`` dumps the
    record as JSON)."""
    found = tracing.traced_device(run)
    if found is None:
        return None
    trace, device, window = found
    if "program_trace" not in run:
        paths = glob.glob(os.path.join(
            run["out_dir"], "trace", "plugins", "profile", "*", "*.xplane.pb"
        ))
        with open(os.path.join(run["out_dir"], "step.hlo.txt")) as f:
            run["program_trace"] = load_xplane(paths[0], f.read()).to_json()
    program = ProgramTrace.from_json(run["program_trace"])
    if device not in program.programs:
        program.programs[device] = executions_from_dispatch(trace, device)
    return program, trace, device, window


def note_once(run: dict, key: str, text: str) -> None:
    """A line for the run's notes, said once whichever reader says it."""
    said = run.setdefault("program_notes", [])
    if key not in said:
        said.append(key)
        run["notes"].append(text)


# ----------------------------------------------------- passes of the step


def pass_of_path(path: str) -> str:
    """forward, backward, replay, optimizer, or "" for a path outside all
    four: JAX marks the forward ``jvp(``, the backward ``transpose(`` and a
    remat replay ``rematted_computation``; ``make_train_step`` marks the
    update ``/optimizer/``."""
    if "rematted_computation" in path:
        return "replay"
    if "transpose(" in path:
        return "backward"
    if "jvp(" in path:
        return "forward"
    if "/optimizer/" in path:
        return "optimizer"
    return ""


def body_passes(bodies: dict) -> dict:
    """Fusion instruction -> the set of passes its body's instructions are of."""
    return {name: {pass_of_path(p) for p in ops} for name, ops in bodies.items()}


def is_mixed(event: Event, passes: dict) -> bool:
    """A fusion whose body holds both backward and optimizer instructions
    (``passes`` is ``body_passes``'s): XLA fuses the Adam update into the
    weight-gradient matmuls."""
    return {"backward", "optimizer"} <= passes.get(event.name, set())


def pass_of(event: Event, passes: dict) -> str:
    """The pass a device event counts in. An event is named for one of its
    instructions and counts where that one does (so the three passes and
    ``step.optimizer_share``, which reads the same name, add up), except
    that a mixed fusion is backward whatever it is named for. An event
    outside ``jvp(`` and ``transpose(`` is ``step.optimizer_share``'s."""
    if is_mixed(event, passes):
        return "backward"
    return pass_of_path(event.path) or "optimizer"


def pass_shares(run: dict):
    """{pass: share of busy time in %} over the traced window, and the notes
    that go with them; None without a device trace. Three readers ask: the
    run record keeps the answer."""
    if "pass_shares" not in run:
        run["pass_shares"] = _pass_shares(run)
    return run["pass_shares"]


def _pass_shares(run: dict):
    found = of(run)
    if found is None:
        return None
    program, trace, device, window = found
    passes = body_passes(program.bodies)
    events = [e for e in trace.devices[device]
              if e.end > window[0] and e.start < window[1]]
    busy = tracing.busy_seconds(events, window)
    if not busy or not any(pass_of_path(e.path) for e in events):
        return None
    seconds, unscoped = defaultdict(float), defaultdict(float)
    mixed = mixed_as_optimizer = holds_replay = 0.0
    for e, t in tracing.self_times(events):
        named = pass_of_path(e.path)
        seconds[pass_of(e, passes)] += t
        if is_mixed(e, passes):
            mixed += t
            if named in ("optimizer", ""):
                mixed_as_optimizer += t
        elif not named:
            unscoped[e.name.split(".")[0]] += t
        if named != "replay" and "replay" in passes.get(e.name, ()):
            holds_replay += t
    shares = {k: 100.0 * v / busy for k, v in seconds.items()}
    total = sum(shares.values())
    left_over = ", ".join(
        f"{k} {100 * v / busy:.2f}"
        for k, v in sorted(unscoped.items(), key=lambda kv: -kv[1])[:3]
    )
    note_once(run, "passes", (
        f"step passes, % of busy: forward {shares.get('forward', 0):.2f}, "
        f"backward {shares.get('backward', 0):.2f}, replay "
        f"{shares.get('replay', 0):.2f}, optimizer {shares.get('optimizer', 0):.2f} "
        f"(sum {total:.2f}); fusions holding backward and optimizer instructions "
        f"are {100 * mixed / busy:.2f} and count as backward "
        f"({100 * mixed / max(seconds['backward'], 1e-30):.1f}% of it; "
        f"{100 * mixed_as_optimizer / busy:.2f} of them named for the update, "
        f"which step.optimizer_share counts too); fusions named for another "
        f"pass that hold replay instructions are {100 * holds_replay / busy:.2f}; "
        f"left over, under no scope and counted with the optimizer: "
        f"{100 * sum(unscoped.values()) / busy:.2f} ({left_over})"
    ))
    return shares


def moe_shares(run: dict):
    """{"experts", "dispatch": share of busy time in %} of the MoE layer's
    scopes, forward, backward and replay; None in a model without them."""
    found = of(run)
    if found is None:
        return None
    _, trace, device, window = found
    events = trace.devices[device]
    out = {}
    for key, scopes in MOE_SCOPES.items():
        if not any(s in e.path for e in events for s in scopes):
            return None
        share = tracing.share_of_busy(
            events, window, lambda e, scopes=scopes: any(s in e.path for s in scopes)
        )
        if share is None:
            return None
        out[key] = 100.0 * share
    return out


# ------------------------------------------------------------- idle time


def intersect(intervals, cover) -> list:
    """The parts of ``intervals`` that ``cover`` covers."""
    return tracing.subtract(intervals, tracing.subtract(intervals, cover))


def idle_split(run: dict):
    """(idle between programs, idle inside a program, window): the device's
    idle intervals in the traced window, split at the executions of the
    step. Outside every execution the device waits for the host; inside one
    it waits between two of its own operations."""
    found = of(run)
    if found is None:
        return None
    program, trace, device, window = found
    idle = tracing.subtract([window], tracing.spans(trace.devices[device]))
    running = tracing.clip(tracing.spans(program.programs[device]), *window)
    if not running:
        return None
    return tracing.subtract(idle, running), intersect(idle, running), window


def note_turns(run: dict) -> None:
    """Says the traced steps' median turn: what tracing costs when it is on
    is this number on two commits."""
    steps = [e for e in run["trace_data"].host if e.name == "bench.step"]
    turns = [b.start - a.start for a, b in zip(steps, steps[1:])]
    if turns:
        note_once(run, "turns", (
            f"traced steps: {len(turns) + 1}, median turn "
            f"{1e3 * statistics.median(turns):.4f} ms (under the profiler)"
        ))


def innermost(events) -> list:
    """[(lo, hi, name)], disjoint and sorted: at each moment the innermost
    open span of one thread's nested spans."""
    out, stack = [], []  # stack of [event, covered up to]

    def close(upto):
        while stack and stack[-1][0].end <= upto:
            e, at = stack.pop()
            if e.end > at:
                out.append((at, e.end, e.name))
            if stack:
                stack[-1][1] = max(stack[-1][1], e.end)

    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        close(e.start)
        if stack and e.start > stack[-1][1]:
            out.append((stack[-1][1], e.start, stack[-1][0].name))
        if stack:
            stack[-1][1] = max(stack[-1][1], e.start)
        stack.append([e, e.start])
    close(float("inf"))
    return sorted(out)


def bench_phases(host, window) -> list:
    """[(lo, hi, name)] covering the window: the benchmark's innermost span
    at each moment, ``outside`` between two steps."""
    named = [s for s in innermost(host) if s[1] > window[0] and s[0] < window[1]]
    bare = tracing.subtract([window], [(lo, hi) for lo, hi, _ in named])
    return sorted(named + [(lo, hi, "outside") for lo, hi in bare])


def overlap(intervals, segments) -> dict:
    """name -> seconds of ``intervals`` under each named segment."""
    total = defaultdict(float)
    for lo, hi, name in segments:
        part = tracing.measure(tracing.clip(intervals, lo, hi))
        if part > 0:
            total[name] += part
    return total


def other_threads(program: ProgramTrace) -> list:
    """The span lists of every thread but the loop's."""
    return [t for i, t in enumerate(program.threads) if i != program.loop_thread]


def rpc_intervals(program: ProgramTrace) -> list:
    """When a thread other than the loop's is inside a control-plane span
    and not inside the wait for the next report."""
    out = []
    for events in other_threads(program):
        serving = [e for e in events if e.name.startswith(RPC_SPANS)]
        waiting = [e for e in events if e.name == RPC_WAIT]
        out += tracing.subtract(tracing.spans(serving), tracing.spans(waiting))
    return tracing.union(out)


def idle_under_rpc(run: dict):
    """Share (%) of the between-programs idle time with another thread in a
    control-plane span; None where the program left no such span."""
    split = idle_split(run)
    if split is None:
        return None
    between, _, window = split
    program, trace, _, _ = of(run)
    total = tracing.measure(between)
    if not any(other_threads(program)) or not total:
        return None
    under = tracing.measure(intersect(between, rpc_intervals(program)))
    # The listing: idle seconds by the innermost program span of any other
    # thread, each beside the benchmark phases it fell in.
    phases = bench_phases(trace.host, window)
    rows = []
    by_name = defaultdict(list)
    for events in other_threads(program):
        for lo, hi, name in innermost(events):
            by_name[name].append((lo, hi))
    for name, where in by_name.items():
        part = intersect(between, where)
        seconds = tracing.measure(part)
        if seconds > 0:
            rows.append([name, seconds, overlap(part, phases)])
    rows.sort(key=lambda r: -r[1])
    note_once(run, "idle_by_span", (
        f"between-programs idle {1e3 * total:.3f} ms by another thread's "
        "innermost program span (ms, and the bench phase it fell in): "
        + json.dumps([[n, round(1e3 * s, 3),
                       {k: round(1e3 * v, 3) for k, v in sorted(p.items(), key=lambda kv: -kv[1])}]
                      for n, s, p in rows])
        + "; all of it by bench phase: "
        + json.dumps({k: round(1e3 * v, 3) for k, v in sorted(
            overlap(between, phases).items(), key=lambda kv: -kv[1])})
    ))
    return 100.0 * under / total


# ------------------------------------------------------------ report lag


def drain_calls(program: ProgramTrace) -> list:
    """(wait start, wait end, call end) of every ``next_result`` call a
    thread other than the loop's served, by the time its wait ended."""
    calls = []
    for events in other_threads(program):
        waits = [e for e in events if e.name == RPC_WAIT]
        for call in (e for e in events if e.name == NEXT_RESULT):
            wait = next((w for w in waits if call.start <= w.start and w.end <= call.end),
                        Event(RPC_WAIT, call.start, 0.0))
            calls.append((wait.start, wait.end, call.end))
    return sorted(calls, key=lambda c: c[1])


def report_lags(program: ProgramTrace, window) -> list:
    """Seconds from each ``report`` starting to the ``next_result`` call that
    carries it ending, for the reports inside the window. The queue is first
    in, first out and one item a call. A call that waited through a report's
    ``put`` carries that report: the queue was empty while it waited, so
    reports put before it and still unmatched went to a call the trace does
    not hold (the one in flight when the profiler started leaves no span).
    A call whose wait began after the ``put`` takes the oldest one."""
    if program.loop_thread is None:
        return []
    pending = sorted((e for e in program.threads[program.loop_thread]
                      if e.name == REPORT), key=lambda e: e.start)
    lags = []
    for wait_start, wait_end, call_end in drain_calls(program):
        put = [r for r in pending if r.start <= wait_end]
        if not put:
            continue  # it carried something from before the trace
        waited_through = [r for r in put if r.end > wait_start]
        carried = waited_through[0] if waited_through else put[0]
        pending = pending[pending.index(carried) + 1:]
        if window[0] <= carried.start <= window[1]:
            lags.append(call_end - carried.start)
    return lags


def report_lag_ms(run: dict):
    found = of(run)
    if found is None:
        return None
    program, _, _, window = found
    lags = report_lags(program, window)
    if not lags:
        return None
    note_once(run, "report_lag", (
        f"trainer.report_lag_ms: {len(lags)} reports matched, first "
        f"{1e3 * lags[0]:.4f} ms, last {1e3 * lags[-1]:.4f} ms "
        f"(last over first {lags[-1] / max(lags[0], 1e-12):.2f})"
    ))
    return 1e3 * statistics.median(lags)


# ---------------------------------------------------------------- record


def record(xplane: str, hlo: str, out: str, key: str, steps: int = 2) -> None:
    """Keep ``steps`` bench.step spans of a trace, first device only, with
    the program's part, under ``key`` in a recorded file. The first traced
    step is left out: the ``next_result`` call in flight when the profiler
    started left no span. A program span that reaches into the cut is kept
    whole (a call waits through most of a step)."""
    with open(hlo) as f:
        text = f.read()
    trace, program = tracing.load_xplane(xplane, text), load_xplane(xplane, text)
    device = min(trace.devices)
    spans_ = [e for e in trace.host if e.name == "bench.step"]
    lo, hi = spans_[1].start, spans_[steps].end
    cut = trace.cut(lo, hi)
    cut.devices = {device: cut.devices[device]}
    cut.overlapped = {device: cut.overlapped.get(device, [])}

    def reaching_in(events):
        return [e for e in events if e.end > lo and e.start < hi]

    names = {e.name for e in cut.devices[device]}
    kept = ProgramTrace(
        [reaching_in(t) for t in program.threads], program.loop_thread,
        {device: [e for e in program.programs.get(device, [])
                  if e.start >= lo and e.end <= hi]},
        {k: v for k, v in program.bodies.items() if k in names},
    )
    recorded = {}
    if os.path.exists(out):
        with gzip.open(out, "rt") as f:
            recorded = json.load(f)
    recorded[key] = {"trace": cut.to_json(), "program": kept.to_json()}
    with gzip.open(out, "wt") as f:
        json.dump(recorded, f)


def recorded_run(path: str, key: str) -> dict:
    """A run record, as the readers take it, from a recorded file."""
    with gzip.open(path, "rt") as f:
        data = json.load(f)[key]
    return {"trace_data": tracing.Trace.from_json(data["trace"]),
            "program_trace": data["program"], "notes": []}


if __name__ == "__main__":
    if sys.argv[1] != "record":
        sys.exit(__doc__)
    record(*sys.argv[2:6])  # record <xplane.pb> <step.hlo.txt> <out.json.gz> <key>
