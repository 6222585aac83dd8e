"""From a profiler trace to numbers: the one reduction every PR uses.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a ``Trace``: per
device the operations of the "XLA Ops" line (what the core executes, one
after another) and of the "Async XLA Ops" line (copies and collectives from
their ``-start`` to their ``-done``, which run beside the core's work), and
the benchmark's own host spans (``bench.*`` TraceAnnotations), all on the
profiler's one clock, in seconds. ``Trace.to_json`` / ``from_json`` keep a trace as a small recorded
file, which the tests reduce (``benchmarks/tests/data``). Everything below
that is arithmetic on intervals.

``python -m benchmarks.lib.trace <file.xplane.pb>`` prints what a trace
holds: planes, lines, and a few events of each with their stats. Look at one
by hand before writing a reader against it (``run.py --keep DIR`` keeps a
run's raw trace and compiled text). ``... trace record <xplane> <hlo> <out>``
cuts one to two steps for the tests.
"""
from __future__ import annotations

import base64
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
HOST_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)


@dataclass
class Event:
    name: str
    start: float  # seconds on the profiler's clock
    dur: float
    path: str = ""  # the op's metadata path: jit(...)/jvp(Model)/layers_0/...

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)  # device id -> [Event]
    overlapped: dict = field(default_factory=dict)  # device id -> async [Event]
    host: list = field(default_factory=list)  # the benchmark's spans

    def to_json(self) -> dict:
        def rows(events):
            return [[e.name, e.start, e.dur, e.path] for e in events]

        def per_device(lines):
            return {str(d): rows(ev) for d, ev in lines.items()}

        return {"devices": per_device(self.devices),
                "overlapped": per_device(self.overlapped),
                "host": rows(self.host)}

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        def events(rows):
            return [Event(*row) for row in rows]

        def per_device(lines):
            return {int(d): events(r) for d, r in lines.items()}

        return cls(per_device(data["devices"]), per_device(data["overlapped"]),
                   events(data["host"]))

    def cut(self, lo: float, hi: float) -> "Trace":
        """The events that lie wholly inside [lo, hi]."""
        def inside(events):
            return [e for e in events if e.start >= lo and e.end <= hi]

        return Trace({d: inside(ev) for d, ev in self.devices.items()},
                     {d: inside(ev) for d, ev in self.overlapped.items()},
                     inside(self.host))


def kernel_name(custom_call_line: str) -> str:
    """The Pallas kernel a ``tpu_custom_call`` runs. The compiled text keeps
    the kernel only as its serialised Mosaic module, whose string table holds
    the kernel function's name before the names of the frames that built it:
    the first identifier there that ends in ``_kernel``."""
    body = re.search(r'"body":"([^"]*)"', custom_call_line)
    if not body:
        return "unknown"
    strings = base64.b64decode(body.group(1)).split(b"\x00")
    for s in strings:
        if re.fullmatch(rb"[A-Za-z_][A-Za-z0-9_]*_kernel", s):
            return s.decode()
    return "unknown"


def op_paths(hlo_text: str) -> dict:
    """HLO instruction name -> its ``op_name`` metadata, from the compiled
    step's text. The trace names device events by instruction; the path says
    which module scope (``/moe/``, ``jvp(``, ``transpose(``) the op came from,
    and for a Pallas call ends in `` kernel_name=<kernel>``."""
    paths = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        if not m:
            continue
        meta = re.search(r'op_name="([^"]*)"', line)
        path = meta.group(1) if meta else ""
        if 'custom_call_target="tpu_custom_call"' in line:
            path += f" kernel_name={kernel_name(line)}"
        paths[m.group(1)] = path
    return paths


def kernel_of(event: Event):
    """The Pallas kernel a device event ran (``op_paths`` wrote it at the
    end of the path), or None."""
    _, found, kernel = event.path.rpartition(" kernel_name=")
    return kernel if found else None


def load_xplane(path: str, hlo_text: str = "") -> Trace:
    from jax.profiler import ProfileData

    paths = op_paths(hlo_text)

    def device_events(line):
        events = []
        for e in line.events:
            # The trace names an op by its whole instruction: "%x.1 = ...".
            name = e.name.split(" = ", 1)[0].lstrip("%")
            events.append(Event(name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                paths.get(name, "")))
        return sorted(events, key=lambda e: (e.start, -e.dur))

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                trace.devices[int(device.group(1))] = device_events(line)
            elif device and line.name == ASYNC_LINE:
                trace.overlapped[int(device.group(1))] = device_events(line)
            elif not device:
                trace.host.extend(
                    Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events if e.name.startswith(HOST_PREFIX)
                )
    trace.host.sort(key=lambda e: (e.start, -e.dur))
    return trace


# ------------------------------------------------------------- intervals


def union(intervals) -> list:
    """Sorted, disjoint intervals covering the same points."""
    merged = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def measure(intervals) -> float:
    return sum(hi - lo for lo, hi in union(intervals))


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def subtract(intervals, cover) -> list:
    """The parts of ``intervals`` that ``cover`` leaves bare."""
    out, cover = [], union(cover)
    for lo, hi in union(intervals):
        at = lo
        for a, b in cover:
            if b <= at or a >= hi:
                continue
            if a > at:
                out.append((at, a))
            at = max(at, b)
        if at < hi:
            out.append((at, hi))
    return out


def spans(events) -> list:
    return [(e.start, e.end) for e in events]


def nested(events) -> list:
    """(event, self seconds, has children): an event that lies wholly inside
    an earlier one (an op of a ``while`` body, say) is its child, and a
    parent's self time leaves its children's out, so nothing is counted
    twice. Events that merely overlap are siblings."""
    out, stack = [], []  # stack of [event, self seconds, has children]
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and not (e.start >= stack[-1][0].start
                             and e.end <= stack[-1][0].end):
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= e.dur
            stack[-1][2] = True
        stack.append([e, e.dur, False])
    out.extend(tuple(s) for s in stack)
    return out


def self_times(events) -> list:
    return [(e, t) for e, t, _ in nested(events)]


# ------------------------------------------------------------ reductions


def step_window(trace: Trace) -> tuple:
    """The traced steady window: from the first whole ``bench.step`` span
    to the end of the last one."""
    steps = [e for e in trace.host if e.name == "bench.step"]
    if not steps:
        raise ValueError("the trace holds no bench.step span")
    return steps[0].start, steps[-1].end


def traced_device(run: dict):
    """(trace, device id, window) of a run's first device, or None where
    the run has no device trace to read."""
    trace = run["trace_data"]
    if trace is None or not trace.devices:
        return None
    return trace, min(trace.devices), step_window(trace)


def busy_seconds(events, window) -> float:
    return measure(clip(spans(events), *window))


def share_of_busy(events, window, pick) -> float | None:
    """Self time of the operations ``pick`` selects over the device's busy
    time in the window."""
    inside = [e for e in events if e.end > window[0] and e.start < window[1]]
    busy = busy_seconds(inside, window)
    if not busy:
        return None
    return sum(t for e, t in self_times(inside) if pick(e)) / busy


def is_collective(event: Event) -> bool:
    return bool(COLLECTIVE.match(event.name))


def collective_spans(trace: Trace, device: int) -> list:
    """When a collective is under way on a device: the synchronous ones as
    the core executes them, the asynchronous ones from start to done."""
    return spans(
        e for e in trace.devices[device] + trace.overlapped.get(device, [])
        if is_collective(e)
    )


def collective_seconds(trace: Trace, device: int, window) -> float:
    return measure(clip(collective_spans(trace, device), *window))


def exposed_collective_seconds(trace: Trace, device: int, window) -> float:
    """The part of the collectives' time in which the core executes nothing
    else: it sits in the collective itself or waits in its ``-done``."""
    inside = [e for e in trace.devices[device]
              if e.end > window[0] and e.start < window[1]]
    # A parent (a while loop) spans its children: only leaves can hide one.
    work = [e for e, _, parent in nested(inside)
            if not parent and not is_collective(e)]
    bare = subtract(collective_spans(trace, device), spans(work))
    return measure(clip(bare, *window))


def idle_gaps(events, window, host) -> list:
    """[[host span, idle seconds]], longest first: the device's idle time
    in the window, each part named by the benchmark span the host was in
    (a phase of a step, which follow one another; ``bench.step`` itself
    where none of its phases was open; ``outside`` between steps)."""
    gaps = subtract([window], spans(events))
    phases = [(e.start, e.end, e.name) for e in host if e.name != "bench.step"]
    steps = subtract(spans(e for e in host if e.name == "bench.step"),
                     [(lo, hi) for lo, hi, _ in phases])
    named = sorted(phases + [(lo, hi, "bench.step") for lo, hi in steps])
    total, first = defaultdict(float), 0
    for lo, hi in gaps:  # both lists are sorted: one walk over each
        while first < len(named) and named[first][1] <= lo:
            first += 1
        bare = hi - lo
        for a, b, name in named[first:]:
            if a >= hi:
                break
            part = min(b, hi) - max(a, lo)
            if part > 0:
                total[name] += part
                bare -= part
        total["outside"] += bare
    return sorted(([k, v] for k, v in total.items() if v > 1e-12),
                  key=lambda kv: -kv[1])


def top_ops(events, window, n: int = 10) -> list:
    """[[name, self seconds]] of the operations that took most device time
    in the window, instances of one instruction summed."""
    inside = [e for e in events if e.end > window[0] and e.start < window[1]]
    total = defaultdict(float)
    for e, t in self_times(inside):
        total[f"{e.name} {e.path}".strip()[:160]] += t
    return sorted(([k, v] for k, v in total.items()), key=lambda kv: -kv[1])[:n]


def describe(path: str, per_line: int = 4) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:per_line]:
                print("     ", e.name[:80], e.start_ns, e.duration_ns,
                      {k: str(v)[:120] for k, v in e.stats})


def record(xplane: str, hlo: str, out: str, steps: int = 2) -> None:
    """Keep the first ``steps`` bench.step spans of a trace as a small
    recorded file for the tests (gzip of ``Trace.to_json``)."""
    import gzip
    import json

    with open(hlo) as f:
        trace = load_xplane(xplane, f.read())
    spans_ = [e for e in trace.host if e.name == "bench.step"]
    with gzip.open(out, "wt") as f:
        json.dump(trace.cut(spans_[0].start, spans_[steps - 1].end).to_json(), f)


if __name__ == "__main__":
    if sys.argv[1] == "record":  # record <xplane.pb> <step.hlo.txt> <out.json.gz>
        record(*sys.argv[2:5])
    else:
        describe(sys.argv[1])
