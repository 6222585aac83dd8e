"""Per-worker training session.

Reference: train/_internal/session.py — _TrainSession :110, report()
:402. The worker's train loop calls `ray_tpu.train.report(metrics,
checkpoint=...)`; results flow through a queue the trainer drains,
epoch-synchronized across the worker group.

The session keeps the trainer's own record of every turn of the loop in
the flight recorder's ``train`` category (``_private/events.py``
``TRAIN_FIELDS`` names the values): a ``REPORT`` a ``report`` call, stamped
on the loop's thread, which makes no system call for it (one costs 6 µs on
a sandboxed host); a ``USAGE`` a report taken off the queue, where the
taking thread reads the loop thread's CPU clock and the process's CPU time
and fault counters; a ``GC_PAUSE`` a collection, from a ``gc.callbacks``
hook; and from one watchdog thread an ``OVERDUE`` with the loop thread's
innermost frames when a report is late, and again as the wait doubles.
While a session is open the host spans of ``util/tracing.py`` record there
too, and so does set-up: every trace, lowering, backend compile and
persistent-cache read of the process as JAX reports it
(``tracing.watch_compiles``, ``tracing.SETUP_SPANS``), one event each from
a millisecond up and one ``ray_tpu.compile.short`` event at the record's end
for the count and the sum of the shorter ones, and one
``ray_tpu.compile.entries`` there for the calls and the traces of each
kernel's jitted entry. The events ride the worker's ``task_done`` flushes;
``RAY_TPU_events_enabled=0`` turns all of it off with the recorder.
"""
from __future__ import annotations

import gc
import queue
import statistics
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass
from resource import RUSAGE_SELF, getrusage
from typing import Any, Dict, Optional

from .._private import events as _events
from .._private.stacks import frame_lines
from ..util import tracing

_session_lock = threading.Lock()
_session: Optional["TrainSession"] = None


@dataclass
class TrainContext:
    world_rank: int
    world_size: int
    local_rank: int
    node_rank: int
    experiment_name: str
    storage_path: Optional[str]


class TrainSession:
    #: A report is overdue once it is later than this many running median
    #: turns; the watchdog sleeps as long, so it wakes less than once a turn.
    OVERDUE_TURNS = 1.5
    #: Turns the running median is taken over, and how many it needs.
    MEDIAN_OVER, MEDIAN_NEEDS = 64, 8
    #: Frames of the loop's thread an OVERDUE event holds, innermost first.
    OVERDUE_FRAMES = 10

    def __init__(self, context: TrainContext):
        self.context = context
        self.result_queue: "queue.Queue" = queue.Queue()
        self.error: Optional[BaseException] = None
        self._recorder = _events.get_recorder()
        self._reports = 0  # report calls so far; the watchdog compares it
        self._taken = 0  # reports taken off the queue: the next USAGE's ordinal
        self._loop_clock: Optional[int] = None  # the loop thread's CPU clock
        self._loop_thread: Optional[int] = None
        self._loop_entity = ""  # its ident as the events' entity
        # Monotonic stamps of the latest reports, for the running median.
        self._stamps: deque = deque(maxlen=self.MEDIAN_OVER + 1)
        self._gc_started = 0.0
        # stage -> [count, seconds] of the compile durations under
        # tracing.COMPILE_FLOOR_S, which leave no event of their own.
        self._short_compiles: Dict[str, list] = {}
        # The kernel entries' calls and traces before this session's own.
        self._entries_before = tracing.entry_counts()
        self._closed = threading.Event()
        gc.callbacks.append(self._on_gc)
        # Never imported for the record's sake: ray_tpu.train loads jax today
        # (config.py names MeshSpec), and ray_tpu.parallel watches as it does.
        if "jax" in sys.modules:
            tracing.watch_compiles()
        tracing.record_spans_into(self._recorder, self._short_compiles)
        threading.Thread(
            target=self._watch, name="train-overdue", daemon=True
        ).start()

    def report(self, metrics: Dict[str, Any], checkpoint=None):
        rec = self._recorder
        if rec.enabled:
            # No lock, no dict and no system call here: two clocks the
            # vDSO serves, one tuple, two appends.
            thread = threading.get_ident()
            if thread != self._loop_thread:
                self._loop_thread, self._loop_entity = thread, str(thread)
                self._loop_clock = time.pthread_getcpuclockid(thread)
            m = time.monotonic()
            rec.record_at(
                time.time(), m, _events.TRAIN, self._loop_entity, "REPORT",
                (self._reports,),
            )
            self._stamps.append(m)
        self._reports += 1
        with tracing.span(tracing.TRAIN_REPORT):
            self.result_queue.put(("report", metrics, checkpoint))

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        m = time.monotonic()
        if phase == "start":
            self._gc_started = m
        else:
            self._recorder.record_at(
                time.time(), m, _events.TRAIN,
                str(threading.get_ident()), "GC_PAUSE",
                (info["generation"], m - self._gc_started),
            )

    def _watch(self) -> None:
        """Samples the loop thread's stack for a report that is overdue:
        once when it is, and again each time the wait has doubled. It
        sleeps ``OVERDUE_TURNS`` running median turns at a time and
        compares the report count: one that has not moved means the turn
        under way is at least that old. Each sample also says by how much
        the watchdog's own sleeps within that turn ran over since the sample
        before: a loop that waits while this thread wakes on time waits for
        something; both late together, the process was not being run."""
        pause, seen, sampled_at, overslept = 0.05, -1, 0.0, 0.0
        while True:
            before = time.monotonic()
            if self._closed.wait(pause):
                return
            now = time.monotonic()
            overslept = max(overslept, now - before - pause)
            stamps, count = list(self._stamps), self._reports
            if len(stamps) <= self.MEDIAN_NEEDS:
                continue
            median = statistics.median(
                b - a for a, b in zip(stamps, stamps[1:])
            )
            pause = max(self.OVERDUE_TURNS * median, 0.005)
            waited = now - stamps[-1]
            if count != seen:  # a new turn: its own sleeps start here
                seen, sampled_at, overslept = count, 0.0, 0.0
            elif waited > pause and waited >= 2 * sampled_at:
                frame = sys._current_frames().get(self._loop_thread)
                self._recorder.record_at(
                    time.time(), now, _events.TRAIN, self._loop_entity,
                    "OVERDUE",
                    (count, waited, overslept,
                     tuple(frame_lines(frame, self.OVERDUE_FRAMES))),
                )
                sampled_at, overslept = waited, 0.0

    def close_record(self) -> None:
        """Ends the session's record: the watchdog, the collector hook and
        the spans' second home."""
        if self._closed.is_set():
            return
        self._closed.set()
        gc.callbacks.remove(self._on_gc)
        tracing.record_spans_into(None)
        tallies = (
            (tracing.COMPILE_SHORT, self._short_compiles),
            (tracing.COMPILE_ENTRIES, tracing.entry_counts(self._entries_before)),
        )
        for name, tally in tallies:
            if tally:
                m = time.monotonic()
                self._recorder.record_at(
                    time.time(), m, _events.TRAIN, str(threading.get_ident()),
                    name, {"m_start": m, **tally},
                )

    def finish(self, error: Optional[BaseException] = None):
        self.error = error
        self.close_record()
        self.result_queue.put(("done", None, None))

    def next_result(self, timeout: Optional[float] = None):
        with tracing.span(tracing.TRAIN_RESULT_WAIT):
            item = self.result_queue.get(timeout=timeout)
        if item[0] != "report":
            return item
        rec = self._recorder
        if rec.enabled and self._loop_clock is not None:
            # On the taking thread, as soon as the report arrives: what the
            # loop's thread and the process had used when it reported, give
            # or take the hand-over (the event's own stamps say how long).
            usage = getrusage(RUSAGE_SELF)
            try:
                thread_cpu_ns = time.clock_gettime_ns(self._loop_clock)
            except OSError:  # the loop's thread has ended, its clock with it
                thread_cpu_ns = None
            rec.record_at(
                time.time(), time.monotonic(), _events.TRAIN,
                self._loop_entity, "USAGE",
                (self._taken, thread_cpu_ns,
                 usage.ru_utime + usage.ru_stime, usage.ru_nivcsw,
                 usage.ru_majflt, usage.ru_minflt),
            )
        self._taken += 1
        return item


def init_session(context: TrainContext) -> TrainSession:
    global _session
    with _session_lock:
        if _session is not None:
            _session.close_record()  # one session's record a process
        _session = TrainSession(context)
        return _session


def get_session() -> Optional[TrainSession]:
    return _session


def report(metrics: Dict[str, Any], *, checkpoint=None) -> None:
    """Reference: ray.train.report — every worker must call it the same
    number of times; rank-0's checkpoint is persisted."""
    s = get_session()
    if s is None:
        raise RuntimeError("report() called outside a train session")
    s.report(metrics, checkpoint)


def get_context() -> TrainContext:
    s = get_session()
    if s is None:
        raise RuntimeError("get_context() called outside a train session")
    return s.context
