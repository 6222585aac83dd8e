"""The trainer's own record of every turn: the flight recorder's ``train``
category (``train/session.py``, ``util/tracing.py``), its delivery through
the recorder's flushes, the file a rank under ``Result.path`` and the
timeline's row."""
from __future__ import annotations

import gc
import glob
import json
import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu import train as rt_train
from ray_tpu._private import event_names, events
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
from ray_tpu.train.session import TrainContext, TrainSession, init_session
from ray_tpu.train.trainer import EVENTS_FILE
from ray_tpu.util import tracing

N = 14
BURN, COLLECT, SLEEP = 5, 7, 10  # ordinals of the turns that do something
SPAM = 5_000


def burn_cpu(seconds):
    t = time.thread_time()
    while time.thread_time() - t < seconds:
        pass


def loop(config):
    for i in range(N):
        if i == SLEEP:
            time.sleep(0.3)
        elif i == BURN:
            burn_cpu(0.1)
        elif i == COLLECT:
            gc.collect()
            time.sleep(0.02)
        else:
            time.sleep(0.02)
        rt_train.report({"i": i})


def spamming_loop(config):
    from collections import deque

    ring = events.get_recorder()  # this worker's, which dies with the fit
    ring.capacity, ring._buf = 64, deque(ring._buf, maxlen=64)
    rt_train.report({"i": 0})
    for _ in range(SPAM):  # far more than the ring holds between two flushes
        with tracing.span(tracing.TRAIN_REPORT):
            pass
    rt_train.report({"i": 1})


def read_file(storage, rank=0):
    with open(os.path.join(storage, EVENTS_FILE.format(rank=rank))) as f:
        lines = [json.loads(text) for text in f]
    return lines[0]["header"], lines[1:]


@pytest.fixture(scope="module")
def fit(tmp_path_factory):
    """One CPU fit whose loop burns, collects and sleeps in known turns:
    (file header, file lines, the head's train events, the timeline)."""
    from ray_tpu._private import state
    from ray_tpu.util.state import list_cluster_events

    storage = str(tmp_path_factory.mktemp("fit"))
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        result = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="events", storage_path=storage),
        ).fit()
        assert result.error is None and result.path == storage
        listed = list_cluster_events(category="train", limit=100_000)
        timeline = state.timeline()
        header, lines = read_file(storage)
        yield {"header": header, "lines": lines, "listed": listed,
               "timeline": timeline, "storage": storage}
    finally:
        ray_tpu.shutdown()


def named(fit, event):
    return [e for e in fit["lines"]
            if e["category"] == "train" and e["event"] == event]


def turn(fit, ordinal):
    """(seconds, loop thread CPU seconds) of the turn that ends with the
    report of that ordinal: the two REPORTs' stamps, and the difference of
    the two USAGE readings taken as they were handed over."""
    reports = {e["attrs"]["ordinal"]: e for e in named(fit, "REPORT")}
    usage = {e["attrs"]["ordinal"]: e["attrs"] for e in named(fit, "USAGE")}
    return (reports[ordinal]["monotonic"] - reports[ordinal - 1]["monotonic"],
            (usage[ordinal]["thread_cpu_ns"]
             - usage[ordinal - 1]["thread_cpu_ns"]) * 1e-9)


def test_fit_leaves_one_file_a_rank_with_every_report(fit):
    assert glob.glob(os.path.join(fit["storage"], "train_events_rank*.jsonl")) \
        == [os.path.join(fit["storage"], EVENTS_FILE.format(rank=0))]
    reports = named(fit, "REPORT")
    assert [e["attrs"]["ordinal"] for e in reports] == list(range(N))
    stamps = [e["monotonic"] for e in reports]
    assert stamps == sorted(stamps) and len(set(stamps)) == N
    assert len({e["entity"] for e in reports}) == 1  # one loop thread
    assert fit["header"]["rank"] == 0 and fit["header"]["dropped"] == 0
    for e in reports:  # no user metric is copied
        assert set(e["attrs"]) == set(events.TRAIN_FIELDS["REPORT"])
    # One usage reading a report, taken by the thread it was handed to.
    usage = named(fit, "USAGE")
    assert [e["attrs"]["ordinal"] for e in usage] == list(range(N))
    assert {e["entity"] for e in usage} == {reports[0]["entity"]}
    for e, report in zip(usage, reports):
        assert set(e["attrs"]) == set(events.TRAIN_FIELDS["USAGE"])
        assert 0 < e["monotonic"] - report["monotonic"] < 0.25


def test_a_turn_that_burns_cpu_shows_in_its_cpu_difference(fit):
    seconds, cpu = turn(fit, BURN)
    # The reading before it is taken by another thread as the burn begins,
    # which holds the interpreter: a few switch intervals of the burn may be
    # read into the turn before. Nothing is lost between two readings.
    before = turn(fit, BURN - 1)[1]
    assert cpu >= 0.05 and cpu + before >= 0.09 and seconds >= cpu
    # (The last report may be taken after the loop's thread, and its CPU
    # clock, are gone: that reading holds no thread time.)
    quiet = [turn(fit, k)[1] for k in range(1, N - 1)
             if k not in (BURN - 1, BURN, COLLECT - 1, COLLECT)]
    assert max(quiet) < 0.02


def test_a_turn_that_sleeps_is_off_cpu_with_an_overdue_sample(fit):
    seconds, cpu = turn(fit, SLEEP)
    assert seconds >= 0.3 and cpu < 0.02
    samples = [e["attrs"] for e in named(fit, "OVERDUE")
               if e["attrs"]["ordinal"] == SLEEP]
    # Once when the report is overdue and again as the wait doubles: a turn
    # of 0.3 s among turns of 0.02 s is sampled two to five times.
    assert 2 <= len(samples) <= 5
    waits = [a["waited_s"] for a in samples]
    assert all(b >= 2 * a for a, b in zip(waits, waits[1:])) and waits[-1] < 0.31
    for a in samples:
        assert 1 <= len(a["frames"]) <= TrainSession.OVERDUE_FRAMES
        assert a["frames"][0].startswith("loop (test_train_events.py:")
        # The watchdog itself woke on time: the loop was waiting, and the
        # process was being run.
        assert 0 <= a["overslept_s"] < 0.1
    loop_thread = named(fit, "REPORT")[0]["entity"]
    assert {e["entity"] for e in named(fit, "OVERDUE")} == {loop_thread}
    # No other turn was late enough to be sampled.
    assert {e["attrs"]["ordinal"] for e in named(fit, "OVERDUE")} == {SLEEP}


def test_a_forced_collection_is_recorded_with_its_generation(fit):
    reports = {e["attrs"]["ordinal"]: e["monotonic"] for e in named(fit, "REPORT")}
    full = [e for e in named(fit, "GC_PAUSE") if e["attrs"]["generation"] == 2
            and reports[COLLECT - 1] < e["monotonic"] < reports[COLLECT]]
    assert len(full) == 1
    assert 0 < full[0]["attrs"]["seconds"] < 1
    assert full[0]["entity"] == named(fit, "REPORT")[0]["entity"]


def test_spans_reach_the_file_from_the_threads_that_ran_them(fit):
    loop_thread = named(fit, "REPORT")[0]["entity"]
    by_name = {name: named(fit, name) for name in tracing.HOST_SPANS}
    assert all(by_name.values()), {k: len(v) for k, v in by_name.items()}
    assert len(by_name[tracing.TRAIN_REPORT]) == N
    assert {e["entity"] for e in by_name[tracing.TRAIN_REPORT]} == {loop_thread}
    for name in (tracing.TRAIN_NEXT_RESULT, tracing.TRAIN_RESULT_WAIT,
                 tracing.WORKER_EXEC, tracing.WORKER_REPLY):
        assert loop_thread not in {e["entity"] for e in by_name[name]}
    for spans in by_name.values():
        for e in spans:  # both stamps, on the monotonic clock
            assert 0 <= e["monotonic"] - e["attrs"]["m_start"] < 5
    # ray_tpu.worker.exec comes from the task's EXEC_SPAN, once a task.
    tasks = [e["task"] for e in by_name[tracing.WORKER_EXEC]]
    assert len(tasks) == len(set(tasks)) >= N
    # The wait lies inside its call, on the call's thread.
    call = by_name[tracing.TRAIN_NEXT_RESULT][3]
    wait = [w for w in by_name[tracing.TRAIN_RESULT_WAIT]
            if w["entity"] == call["entity"]
            and call["attrs"]["m_start"] <= w["attrs"]["m_start"]
            and w["monotonic"] <= call["monotonic"]]
    assert len(wait) == 1


def test_the_file_holds_what_the_recorder_lists(fit):
    def key(e):
        return (e["event"], e["entity"], e["monotonic"])

    listed = {key(e) for e in fit["listed"]}
    filed = {key(e) for e in fit["lines"] if e["category"] == "train"
             and e["event"] != tracing.WORKER_EXEC}
    assert listed and listed == filed
    assert {e["source"] for e in fit["listed"]} == {fit["header"]["source"]}


def test_the_file_holds_the_workers_own_lifecycle(fit):
    lifecycle = [e["event"] for e in fit["lines"] if e["category"] == "worker"]
    for name in ("SPAWN_REQUESTED", "FORKED", "BOOT", "REGISTERED"):
        assert name in lifecycle
    early = {(e["name"], e["event"]) for e in fit["lines"]
             if e["category"] == "task"}
    assert ("TrainWorker.__init__", "SUBMITTED") in early
    assert ("TrainWorker.__init__", "EXEC_END") in early
    assert ("run", "EXEC_START") in early
    first_report = named(fit, "REPORT")[0]["timestamp"]
    assert fit["header"]["t_fit"] < first_report < fit["header"]["t_written"]


def test_timeline_renders_the_train_row(fit):
    rows = [r for r in fit["timeline"] if r.get("cat") == "train"]
    assert {r["pid"] for r in rows} == {"train " + fit["header"]["source"]}
    turns = [r for r in rows if r["tid"] == "turns" and r["name"].startswith("turn ")]
    assert [r["name"] for r in turns] == [f"turn {k}" for k in range(1, N)]
    slept = turns[SLEEP - 1]
    assert slept["ph"] == "X" and slept["dur"] >= 0.3e6
    assert slept["args"]["loop_cpu_ms"] < 20
    assert turns[BURN - 1]["args"]["loop_cpu_ms"] >= 90
    # Collector pauses and overdue samples lie on the turns' row, in their turn.
    pauses = [r for r in rows if r["tid"] == "turns" and r["name"] == "gc gen2"]
    assert any(turns[COLLECT - 1]["ts"] <= p["ts"]
               and p["ts"] + p["dur"] <= turns[COLLECT - 1]["ts"] + turns[COLLECT - 1]["dur"] + 1
               for p in pauses)
    overdue = [r for r in rows if r["name"] == "OVERDUE"]
    assert overdue and all(r["ph"] == "i" for r in overdue)
    assert all(slept["ts"] < r["ts"] < slept["ts"] + slept["dur"] for r in overdue)
    # The RPC spans on the threads that ran them.
    threads = {r["tid"] for r in rows if r["name"] == tracing.TRAIN_NEXT_RESULT}
    assert threads and all(t.startswith("thread ") for t in threads)
    assert f"thread {turns[0]['args']['thread']}" not in threads


def test_events_cli_lists_the_train_category(fit, monkeypatch, capsys):
    from ray_tpu.scripts import cli

    monkeypatch.setattr(cli, "_connect", lambda: None)
    cli.main(["events", "--category", "train", "--limit", "1000"])
    table = capsys.readouterr().out
    for name in ("REPORT", "USAGE", "GC_PAUSE", "OVERDUE", tracing.TRAIN_REPORT):
        assert name in table
    cli.main(["events", "--category", "train", "--limit", "1000", "--json"])
    rows = json.loads(capsys.readouterr().out)
    assert sum(1 for r in rows if r["event"] == "REPORT") == N


def test_a_ring_overflow_is_counted_in_the_file(fit, tmp_path):
    storage = str(tmp_path)
    result = JaxTrainer(
        spamming_loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="overflow", storage_path=storage),
    ).fit()
    assert result.error is None
    header, lines = read_file(storage)
    # A flush carries at most 64 away, and there is one a task or so.
    assert header["dropped"] >= SPAM // 2
    kept = [e for e in lines if e["event"] == tracing.TRAIN_REPORT]
    assert SPAM + 2 <= len(kept) + header["dropped"] <= SPAM + 50
    assert header["source"] != fit["header"]["source"]


# ------------------------------------------------ in one process, no cluster


class TrainRing(events.FlightRecorder):
    """Keeps the train category alone. The head that ``fit`` started lives
    until the module ends, and its threads record into whatever recorder is
    in place: an ``SHM_SWEEP`` once the last trainer's worker is reclaimed."""

    def record(self, category, entity, event, attrs=None):
        if category == events.TRAIN:
            super().record(category, entity, event, attrs)


@pytest.fixture
def ring(monkeypatch):
    """A recorder of its own in the place of the process's."""
    recorder = TrainRing(capacity=4096, enabled=True)
    monkeypatch.setattr(events, "_recorder", recorder)
    yield recorder
    tracing.record_spans_into(None)


def context():
    return TrainContext(0, 1, 0, 0, "unit", None)


def test_every_new_name_is_in_the_registry():
    registered = event_names.EVENTS_BY_CATEGORY["train"]
    assert "train" in event_names.CATEGORIES and events.TRAIN == "train"
    assert "TRAIN" in event_names.CATEGORY_CONSTS
    assert set(events.TRAIN_FIELDS) == {"REPORT", "USAGE", "GC_PAUSE", "OVERDUE"}
    assert set(events.TRAIN_FIELDS) <= registered
    # Every host span but the one the task category's EXEC_SPAN holds.
    assert registered - set(events.TRAIN_FIELDS) \
        == set(tracing.HOST_SPANS) - {tracing.WORKER_EXEC}
    assert all(event_names.is_registered(n) for n in registered)


def test_a_span_outside_a_profiler_session_lands_in_the_ring(ring):
    import jax  # noqa: F401 - the span is an annotation too where jax is loaded

    with tracing.span(tracing.WORKER_RECV):
        pass
    assert len(ring) == 0  # no train session is held: nothing is recorded
    session = TrainSession(context())
    try:
        before = time.monotonic()
        with tracing.span(tracing.WORKER_RECV):
            time.sleep(0.01)
        with tracing.span(tracing.WORKER_EXEC):  # EXEC_SPAN's, not the ring's
            pass
        after = time.monotonic()
    finally:
        session.close_record()
    items, dropped = ring.drain()
    assert dropped == 0 and len(items) == 1
    t_wall, t_mono, category, entity, event, m_start = items[0]
    assert (category, event) == (events.TRAIN, tracing.WORKER_RECV)
    assert entity == str(threading.get_ident())
    assert before <= m_start <= m_start + 0.01 <= t_mono <= after
    assert abs(t_wall - time.time()) < 5
    expanded = events._expand(items[0], "here")
    assert expanded[0]["attrs"] == {"m_start": m_start}
    assert expanded[0]["monotonic"] == t_mono


def test_a_span_inside_a_profiler_session_is_in_both_records(ring, tmp_path):
    import jax
    from jax.profiler import ProfileData

    session = TrainSession(context())
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            session.report({"loss": 1.0})
        finally:
            jax.profiler.stop_trace()
    finally:
        session.close_record()
    spans = [i for i in ring.drain()[0] if i[4] == tracing.TRAIN_REPORT]
    assert len(spans) == 1
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    traced = [e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name == tracing.TRAIN_REPORT]
    assert len(traced) == 1
    ring_ms = 1e3 * (spans[0][1] - spans[0][5])
    assert abs(traced[0].duration_ns * 1e-6 - ring_ms) < 1.0


def test_usage_is_read_by_the_taking_thread_and_no_user_metric_is_copied(ring):
    session = TrainSession(context())
    taken = []

    def loop():
        session.report({"secret": 1})
        burn_cpu(0.05)
        session.report({"secret": 2})
        while len(taken) < 2:  # its CPU clock lives as long as the thread
            time.sleep(0.001)

    thread = threading.Thread(target=loop)
    thread.start()
    try:
        for _ in range(2):
            taken.append(session.next_result(timeout=5))
        thread.join()
    finally:
        session.close_record()
    expanded = [events._expand(i, "here")[0] for i in ring.drain()[0]]
    reports = [e for e in expanded if e["event"] == "REPORT"]
    usage = [e for e in expanded if e["event"] == "USAGE"]
    assert [r["attrs"] for r in reports] == [{"ordinal": 0}, {"ordinal": 1}]
    assert [u["attrs"]["ordinal"] for u in usage] == [0, 1]
    assert {u["entity"] for u in usage} == {str(thread.ident)}
    a, b = (u["attrs"] for u in usage)
    # The first reading is taken as the burn begins and may hold some of it.
    assert (b["thread_cpu_ns"] - a["thread_cpu_ns"]) * 1e-9 >= 0.02
    assert b["process_cpu_s"] - a["process_cpu_s"] >= 0.02
    for field in ("nivcsw", "majflt", "minflt"):
        assert b[field] >= a[field] >= 0
    assert "secret" not in json.dumps(expanded)
    assert reports[0]["monotonic"] < reports[1]["monotonic"]


def test_a_report_taken_after_its_thread_ended_is_still_delivered(ring):
    session = TrainSession(context())
    thread = threading.Thread(target=lambda: session.report({"i": 0}))
    thread.start()
    thread.join()
    try:
        assert session.next_result(timeout=1)[0] == "report"
    finally:
        session.close_record()
    usage = [events._expand(i, "here")[0] for i in ring.drain()[0]
             if i[4] == "USAGE"]
    # The thread's CPU clock goes with the thread: None once it is gone.
    assert len(usage) == 1 and (usage[0]["attrs"]["thread_cpu_ns"] or 0) >= 0
    assert usage[0]["attrs"]["process_cpu_s"] > 0


def test_finish_removes_the_hook_the_watchdog_and_the_spans(ring):
    def watchdogs():
        return [t for t in threading.enumerate() if t.name == "train-overdue"]

    deadline = time.time() + 5  # an earlier test's watchdog may still be ending
    while watchdogs() and time.time() < deadline:
        time.sleep(0.01)
    hooks, before = len(gc.callbacks), len(watchdogs())
    session = init_session(context())
    assert len(gc.callbacks) == hooks + 1 and len(watchdogs()) == before + 1
    gc.collect()
    assert [i[5][0] for i in ring.drain()[0] if i[4] == "GC_PAUSE"] == [2]
    session.finish()
    deadline = time.time() + 5
    while len(watchdogs()) > before and time.time() < deadline:
        time.sleep(0.01)
    assert len(gc.callbacks) == hooks and len(watchdogs()) == before
    gc.collect()
    with tracing.span(tracing.TRAIN_REPORT):
        pass
    assert len(ring) == 0
    assert session.next_result(timeout=1) == ("done", None, None)


def test_a_new_session_ends_the_record_of_the_one_before(ring):
    hooks = len(gc.callbacks)
    first = init_session(context())
    second = init_session(context())
    try:
        assert first._closed.is_set() and not second._closed.is_set()
        assert len(gc.callbacks) == hooks + 1
    finally:
        second.close_record()


def test_a_disabled_recorder_records_nothing_of_a_session(ring):
    ring.enabled = False
    session = TrainSession(context())
    try:
        session.report({"i": 0})
        gc.collect()
        with tracing.span(tracing.WORKER_REPLY):
            pass
    finally:
        session.close_record()
    assert len(ring) == 0 and len(session._stamps) == 0
    assert session.next_result(timeout=1)[0] == "report"


def test_the_watchdog_wakes_less_than_once_a_turn(ring, monkeypatch):
    wakes = []
    real_wait = threading.Event.wait

    def counted(self, timeout=None):
        if threading.current_thread().name == "train-overdue":
            wakes.append(timeout)
        return real_wait(self, timeout)

    session = TrainSession(context())
    try:
        for _ in range(12):  # the running median needs eight turns
            time.sleep(0.02)
            session.report({})
        monkeypatch.setattr(threading.Event, "wait", counted)
        for _ in range(20):
            time.sleep(0.02)
            session.report({})
        monkeypatch.undo()
    finally:
        session.close_record()
    assert 1 <= len(wakes) < 20
    assert min(wakes) >= 0.02 * TrainSession.OVERDUE_TURNS * 0.9
    assert not [i for i in ring.drain()[0] if i[4] == "OVERDUE"]


def test_exec_span_carries_its_thread_and_monotonic_interval():
    item = (10.0, 0.0, events.TASK, "t" * 32, "EXEC_SPAN", {
        "t_fork": 9.0, "t_start": 9.1, "t_end": 9.9, "t_seal": 10.0,
        "worker": "w", "thread": 7, "m_start": 100.1, "m_end": 100.9,
    })
    by_event = {e["event"]: e for e in events._expand(item, "worker-w")}
    assert by_event["EXEC_END"]["attrs"] == {
        "worker": "w", "thread": 7, "m_start": 100.1, "m_end": 100.9,
    }
    assert by_event["EXEC_START"]["attrs"] == {"worker": "w"}


def test_record_at_is_held_to_the_registry_by_raylint():
    from tools.raylint.engine import lint_source

    bad = (
        "def f(rec, t, m):\n"
        "    rec.record_at(t, m, 'train', 'x', 'NOT_A_TRAIN_EVENT', None)\n"
    )
    found = lint_source(bad, only=["event-taxonomy"])
    assert len(found) == 1 and "NOT_A_TRAIN_EVENT" in found[0].message
    good = bad.replace("NOT_A_TRAIN_EVENT", "GC_PAUSE")
    assert not lint_source(good, only=["event-taxonomy"])
