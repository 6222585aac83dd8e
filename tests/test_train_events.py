"""The trainer's own record of every turn: the flight recorder's ``train``
category (``train/session.py``, ``util/tracing.py``), its delivery through
the recorder's flushes, the file a rank under ``Result.path`` and the
timeline's row."""
from __future__ import annotations

import gc
import glob
import json
import os
import sys
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


# ----------------------------------------------------- set-up on the record

STAGES = ("trace", "lower", "backend", "cache_read", "cache_hit", "cache_miss")
JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}


def count_compiles(into):
    """A listener of the test's own on JAX's hooks: stage -> count."""
    import jax

    def on_event(event, *_, **__):
        if event in JAX_EVENTS:
            into[JAX_EVENTS[event]] = into.get(JAX_EVENTS[event], 0) + 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_event)


def use_cache(directory):
    import jax

    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def compiling_loop(config):
    """Jits one function at one shape before set-up's report and at another
    in the turn after it. The sleeps run while it is traced, so its trace
    spans are well over the floor; ``inner`` is a jit called from a jit and
    ``scaled`` a kernel's kind of entry, inlined as ``outer`` is traced."""
    at_start = {"jax_loaded": "jax" in sys.modules, "watching": tracing._watching}
    import jax
    import jax.numpy as jnp

    if config["root"] not in sys.path:
        sys.path.insert(0, config["root"])
    from benchmarks.lib.checks import CompileCounter
    from ray_tpu.ops.attention import kernel_entry

    use_cache(config["cache"])
    own, theirs = {}, CompileCounter().install()
    count_compiles(own)

    @jax.jit
    def inner(x):
        time.sleep(0.005)
        return jnp.sin(x)

    @kernel_entry("by")
    def scaled(x, by):
        return x * by

    @jax.jit
    def outer(x):
        time.sleep(0.01)
        for _ in range(64):
            x = scaled(inner(x), 1.0001)
        return x.sum()

    outer(jnp.ones((4,)))
    rt_train.report({"own": dict(own), "theirs": theirs.snapshot(), **at_start})
    outer(jnp.ones((8,)))
    rt_train.report({"own": dict(own), "theirs": theirs.snapshot()})


@pytest.fixture(scope="module")
def compiled(fit, tmp_path_factory):
    """Two fits of ``compiling_loop`` over one persistent cache, a new worker
    process each: the first finds it empty, the second full."""
    from ray_tpu._private import state
    from ray_tpu.util.state import list_cluster_events

    cache = str(tmp_path_factory.mktemp("jax_cache"))
    runs = []
    for name in ("cold", "warm"):
        storage = str(tmp_path_factory.mktemp(name))
        result = JaxTrainer(
            compiling_loop,
            train_loop_config={"cache": cache, "root": os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name=name, storage_path=storage),
        ).fit()
        assert result.error is None
        header, lines = read_file(storage)
        runs.append({"header": header, "lines": lines, "storage": storage,
                     "history": result.metrics_history})
    return {"cold": runs[0], "warm": runs[1],
            "listed": list_cluster_events(category="train", limit=100_000),
            "timeline": state.timeline()}


def stage_spans(run, stage, fun_name=None):
    return [e for e in named(run, f"ray_tpu.compile.{stage}")
            if fun_name is None or e["attrs"].get("fun_name") == fun_name]


def test_a_loop_that_jits_leaves_its_trace_lower_and_backend_spans(compiled):
    for run in (compiled["cold"], compiled["warm"]):
        reports = named(run, "REPORT")
        loop_thread = reports[0]["entity"]
        for stage, fun_name in (("trace", "outer"), ("lower", "jit(outer)"),
                                ("backend", "jit(outer)")):
            spans = stage_spans(run, stage, fun_name)
            # One shape in set-up, the other in the turn after it.
            assert [e["monotonic"] < reports[0]["monotonic"] for e in spans] \
                == [True, False], (stage, spans)
            assert spans[1]["monotonic"] < reports[1]["monotonic"]
            assert {e["entity"] for e in spans} == {loop_thread}
            for e in spans:
                assert set(e["attrs"]) == {"m_start", "fun_name"}
                assert 0 < e["monotonic"] - e["attrs"]["m_start"] < 30
        # The sleeps are inside the traces: 64 calls of inner, one trace.
        for e in stage_spans(run, "trace", "outer"):
            assert e["monotonic"] - e["attrs"]["m_start"] >= 0.015
        assert run["header"]["dropped"] == 0


def test_a_cold_cache_is_a_miss_an_executable_and_a_warm_one_a_hit_and_a_read(compiled):
    cold, warm = compiled["cold"], compiled["warm"]
    assert not named(cold, tracing.COMPILE_CACHE_HIT)
    assert not named(warm, tracing.COMPILE_CACHE_MISS)
    assert not named(cold, tracing.COMPILE_CACHE_READ)
    for run, point in ((cold, tracing.COMPILE_CACHE_MISS),
                       (warm, tracing.COMPILE_CACHE_HIT)):
        points = named(run, point)
        # Every executable of the run asked the cache: each point lies inside
        # one backend span of its thread, outer's two among them.
        backends = stage_spans(run, "backend")
        assert len(points) == run["history"][-1]["own"]["backend"] >= 2
        for p in points:
            assert p["attrs"] == {"m_start": p["monotonic"]}
            assert sum(1 for b in backends if b["entity"] == p["entity"]
                       and b["attrs"]["m_start"] <= p["monotonic"] <= b["monotonic"]) <= 1
        for b in stage_spans(run, "backend", "jit(outer)"):
            assert sum(1 for p in points
                       if b["attrs"]["m_start"] <= p["monotonic"] <= b["monotonic"]) == 1
    # A read for every hit, of a millisecond or more or in the short tally.
    reads = named(warm, tracing.COMPILE_CACHE_READ)
    short = named(warm, tracing.COMPILE_SHORT)[0]["attrs"]
    assert len(reads) + short.get("cache_read", [0])[0] \
        == len(named(warm, tracing.COMPILE_CACHE_HIT))
    for e in reads:
        assert "fun_name" not in e["attrs"]
        assert tracing.COMPILE_FLOOR_S <= e["monotonic"] - e["attrs"]["m_start"] < 30


def test_the_spans_counts_agree_with_a_listener_of_the_tests_own(compiled):
    for run in (compiled["cold"], compiled["warm"]):
        own, theirs = run["history"][-1]["own"], run["history"][-1]["theirs"]
        shorts = named(run, tracing.COMPILE_SHORT)
        assert len(shorts) == 1  # once, as the record ends
        short = shorts[0]["attrs"]
        assert shorts[0]["monotonic"] == short["m_start"] \
            >= named(run, "REPORT")[-1]["monotonic"]
        assert set(short) - {"m_start"} <= set(STAGES[:4])
        for stage in STAGES:
            recorded = len(stage_spans(run, stage))
            count, seconds = short.get(stage, [0, 0.0])
            assert recorded + count == own.get(stage, 0), stage
            # A short one is under the floor; one with an event is not.
            assert 0 <= seconds <= count * tracing.COMPILE_FLOOR_S
            if stage in STAGES[:4]:
                assert all(e["monotonic"] - e["attrs"]["m_start"]
                           >= tracing.COMPILE_FLOOR_S * 0.999
                           for e in stage_spans(run, stage))
        # A model's inner jnp traces are the short ones.
        assert short["trace"][0] >= 64
        # The benchmark's counter stays the benchmark's, and agrees.
        assert theirs["backend_compiles"] == own["backend"]
        assert theirs["hits"] == own.get("cache_hit", 0) \
            == len(named(run, tracing.COMPILE_CACHE_HIT))
        assert theirs["requests"] == own["backend"]


def test_the_record_ends_with_the_entries_calls_and_traces(compiled):
    for run in (compiled["cold"], compiled["warm"]):
        said = named(run, tracing.COMPILE_ENTRIES)
        assert len(said) == 1  # once, as the record ends
        attrs = dict(said[0]["attrs"])
        assert attrs.pop("m_start") == said[0]["monotonic"] \
            >= named(run, "REPORT")[-1]["monotonic"]
        # 64 calls a shape of ``outer``, one trace a shape, cache or none.
        (entry, counts), = attrs.items()
        assert entry.endswith(".scaled") and counts == [128, 2]


def test_an_inner_jits_interval_lies_inside_its_callers(compiled):
    for run in (compiled["cold"], compiled["warm"]):
        outers = stage_spans(run, "trace", "outer")
        inners = stage_spans(run, "trace", "inner")
        assert len(outers) == len(inners) == 2  # traced once a shape
        for outer, inner in zip(outers, inners):
            assert outer["attrs"]["m_start"] < inner["attrs"]["m_start"]
            assert inner["monotonic"] < outer["monotonic"]
            assert inner["monotonic"] - inner["attrs"]["m_start"] >= 0.005


def test_timeline_lays_the_compile_spans_on_the_compiling_threads_row(compiled):
    source = compiled["cold"]["header"]["source"]
    loop_thread = named(compiled["cold"], "REPORT")[0]["entity"]
    rows = [r for r in compiled["timeline"]
            if r.get("cat") == "train" and r["pid"] == "train " + source
            and r["name"].startswith("ray_tpu.compile.")]
    assert {r["tid"] for r in rows} == {f"thread {loop_thread}"}
    traced = [r for r in rows if r["name"] == tracing.COMPILE_TRACE
              and r["args"] == {"fun_name": "outer"}]
    assert len(traced) == 2 and all(r["ph"] == "X" for r in traced)
    assert all(r["dur"] >= 0.015e6 for r in traced)
    backend = [r for r in rows if r["name"] == tracing.COMPILE_BACKEND
               and r["args"] == {"fun_name": "jit(outer)"}]
    misses = [r for r in rows if r["name"] == tracing.COMPILE_CACHE_MISS]
    assert len(backend) == 2 and all(r["dur"] == 0 and r["args"] == {} for r in misses)
    for b in backend:  # the miss lies in its executable's slice
        assert sum(1 for r in misses if b["ts"] - 1 <= r["ts"] <= b["ts"] + b["dur"] + 1) == 1
    short = [r for r in rows if r["name"] == tracing.COMPILE_SHORT]
    assert len(short) == 1 and short[0]["args"]["trace"][0] >= 64
    # The host spans' slices say nothing more than they did.
    assert all(r["args"] == {} for r in compiled["timeline"]
               if r.get("cat") == "train" and r["name"] in tracing.HOST_SPANS)


def test_events_cli_lists_the_compile_spans(compiled, monkeypatch, capsys):
    from ray_tpu.scripts import cli

    monkeypatch.setattr(cli, "_connect", lambda: None)
    cli.main(["events", "--category", "train", "--limit", "100000"])
    table = capsys.readouterr().out
    for name in tracing.SETUP_SPANS:
        if name != tracing.SHARD_PARAMS:  # the loop places nothing
            assert name in table
    assert '"fun_name": "jit(outer)"' in table
    cli.main(["events", "--category", "train", "--limit", "100000", "--json"])
    rows = json.loads(capsys.readouterr().out)
    warm = compiled["warm"]["header"]["source"]
    assert sum(1 for r in rows if r["source"] == warm
               and r["event"] == tracing.COMPILE_CACHE_HIT) \
        == len(named(compiled["warm"], tracing.COMPILE_CACHE_HIT))
    traced = [r for r in rows if r["source"] == warm
              and r["event"] == tracing.COMPILE_TRACE
              and r["attrs"]["fun_name"] == "outer"]
    assert len(traced) == 2 and all("m_start" in r["attrs"] for r in traced)


def test_jax_is_loaded_when_a_workers_loop_starts(compiled):
    """``ray_tpu.train`` imports ``ray_tpu.parallel`` (``train/config.py``
    names ``MeshSpec``), which imports jax and registers the listeners: a
    worker's session finds jax loaded (it never imports it itself,
    ``test_a_session_that_starts_without_jax_imports_and_registers_nothing``),
    and set-up is seen from its first compile, the eager ones before the
    loop's first jit among them."""
    for run in (compiled["cold"], compiled["warm"]):
        assert run["history"][0]["jax_loaded"] and run["history"][0]["watching"]
        first = min(stage_spans(run, "backend"), key=lambda e: e["monotonic"])
        assert first["monotonic"] < stage_spans(run, "backend", "jit(outer)")[0]["monotonic"]


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
    # Every host span but the one the task category's EXEC_SPAN holds, and
    # set-up's spans.
    assert registered - set(events.TRAIN_FIELDS) \
        == (set(tracing.HOST_SPANS) - {tracing.WORKER_EXEC}) | set(tracing.SETUP_SPANS)
    assert len(tracing.SETUP_SPANS) == 9
    assert all(n.startswith(("ray_tpu.compile.", "ray_tpu.parallel."))
               for n in tracing.SETUP_SPANS)
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


def compile_events(ring):
    """The ring's compile events, expanded as the head expands them."""
    return [e for e in (events._expand(i, "here")[0] for i in ring.drain()[0])
            if e["event"].startswith("ray_tpu.compile.")]


def jit_something_new(scale):
    """A function no test has jitted: its trace holds a sleep of 5 ms."""
    import jax
    import jax.numpy as jnp

    def fresh(x):
        time.sleep(0.005)
        return jnp.cos(x) * scale

    fresh.__name__ = f"fresh_{scale}"
    return float(jax.jit(fresh)(jnp.ones((3,)))[0])


def test_durations_under_the_floor_reach_the_record_as_a_count_and_a_sum(ring, monkeypatch):
    import jax

    seen = []

    def own(event, duration, **_):
        if event in JAX_EVENTS:
            seen.append((JAX_EVENTS[event], duration))

    monkeypatch.setattr(tracing, "COMPILE_FLOOR_S", 60.0)
    session = TrainSession(context())
    jax.monitoring.register_event_duration_secs_listener(own)
    try:
        jit_something_new(2)
    finally:
        jax.monitoring.unregister_event_duration_listener(own)
        session.close_record()
        session.close_record()  # said once
    said = compile_events(ring)
    assert [e["event"] for e in said] == [tracing.COMPILE_SHORT]
    short = said[0]["attrs"]
    assert short.pop("m_start") == said[0]["monotonic"]
    assert said[0]["entity"] == str(threading.get_ident())
    assert set(short) == {"trace", "lower", "backend"} == {s for s, _ in seen}
    for stage, (count, seconds) in short.items():
        assert count == sum(1 for s, _ in seen if s == stage) >= 1
        assert seconds == pytest.approx(sum(d for s, d in seen if s == stage))
    assert short["trace"][1] >= 0.005


def test_two_sessions_in_one_process_register_one_listener(ring):
    from jax._src import monitoring

    first = init_session(context())
    jit_something_new(3)
    second = init_session(context())  # ends the first's record
    try:
        jit_something_new(4)
    finally:
        second.close_record()
    assert monitoring.get_event_duration_listeners().count(tracing._on_duration) == 1
    assert monitoring.get_event_listeners().count(tracing._on_point) == 1
    said = compile_events(ring)
    for scale in (3, 4):  # each compile once, whichever session held it
        assert [e["attrs"]["fun_name"] for e in said
                if e["event"] == tracing.COMPILE_TRACE
                and e["attrs"]["fun_name"].startswith("fresh_")].count(f"fresh_{scale}") == 1
    # Each session's tally of short ones is its own, said as it ends.
    assert [e["event"] for e in said].count(tracing.COMPILE_SHORT) == 2
    assert first._short_compiles is not second._short_compiles


def test_a_session_says_its_kernel_entries_calls_and_traces_as_its_record_ends(ring):
    """``ops/attention.py`` ``kernel_entry`` counts for the whole process; a
    session's event holds what was called while it was held."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import kernel_entry

    @kernel_entry("scale")
    def scaled(x, scale):
        return x * scale

    x = jnp.ones((3,))
    jax.make_jaxpr(lambda x: scaled(x, 2.0))(x)  # before the session: not its own
    session = TrainSession(context())
    try:
        jax.make_jaxpr(lambda x: scaled(scaled(scaled(x, 2.0), 2.0), 3.0))(x)
    finally:
        session.close_record()
        session.close_record()  # said once
    said = [e for e in compile_events(ring) if e["event"] == tracing.COMPILE_ENTRIES]
    assert len(said) == 1 and said[0]["entity"] == str(threading.get_ident())
    attrs = dict(said[0]["attrs"])
    assert attrs.pop("m_start") == said[0]["monotonic"]
    # Three calls, one trace: 2.0 was traced before, 3.0 is a static of its own.
    assert attrs == {"test_train_events.scaled": [3, 1]}
    assert tracing.entry_counts()["test_train_events.scaled"] == [4, 2]


def test_no_session_or_a_disabled_recorder_records_nothing_that_compiles(ring):
    tracing.watch_compiles()
    jit_something_new(5)  # no session is held
    assert len(ring) == 0
    ring.enabled = False
    session = TrainSession(context())
    try:
        jit_something_new(6)
    finally:
        session.close_record()
    assert len(ring) == 0 and session._short_compiles == {}


def test_a_session_that_starts_without_jax_imports_and_registers_nothing(ring, monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    monkeypatch.setattr(tracing, "_watching", False)
    session = TrainSession(context())
    try:
        assert "jax" not in sys.modules and tracing._watching is False
    finally:
        session.close_record()


def test_shard_params_leaves_its_span(ring):
    import numpy as np
    from ray_tpu.parallel import MeshSpec, shard_params

    mesh = MeshSpec().build()
    tree = {"layer": {"w": np.ones((8, 8), np.float32)}, "b": np.zeros((8,), np.float32)}
    session = TrainSession(context())
    try:
        before = time.monotonic()
        placed = shard_params(tree, mesh)
        after = time.monotonic()
    finally:
        session.close_record()
    assert placed["layer"]["w"].shape == (8, 8)
    spans = [i for i in ring.drain()[0] if i[4] == tracing.SHARD_PARAMS]
    assert len(spans) == 1
    t_wall, t_mono, category, entity, event, m_start = spans[0]
    assert category == events.TRAIN and entity == str(threading.get_ident())
    assert before <= m_start < t_mono <= after
    assert events._expand(spans[0], "here")[0]["attrs"] == {"m_start": m_start}
    shard_params(tree, mesh)  # no session: the placing is not recorded
    assert not [i for i in ring.drain()[0] if i[4] == tracing.SHARD_PARAMS]


def test_the_benchmarks_readers_take_a_file_that_holds_the_new_events(tmp_path):
    """``benchmarks/lib/train_events.py`` reads ``attrs["m_start"]`` of every
    ``train`` event it does not know: set-up's events are laid out so, and
    its five metrics of a recorded window are what they were without them."""
    from benchmarks.lib import train_events
    from benchmarks.tests import test_train_events_readers as recorded

    def metrics(directory):
        return dict(train_events.metrics(recorded.a_run(directory)))

    plain, held = tmp_path / "plain", tmp_path / "held"
    recorded.write_record(str(plain))
    recorded.write_record(str(held))
    path = os.path.join(str(held), train_events.FILE)
    with open(path) as f:
        lines = [json.loads(text) for text in f]

    def event(name, entity, mono, attrs):
        return {"category": "train", "event": name, "entity": entity,
                "timestamp": 1000.0 + mono, "monotonic": mono,
                "attrs": attrs, "source": "worker-x"}

    loop, m = recorded.LOOP, 50.0  # the recorded set-up's report is at 50.0
    added = [
        event(tracing.COMPILE_TRACE, loop, m - 3.0, {"m_start": m - 4.0, "fun_name": "train_step"}),
        event(tracing.COMPILE_LOWER, loop, m - 2.0, {"m_start": m - 3.0, "fun_name": "jit(train_step)"}),
        event(tracing.COMPILE_CACHE_MISS, loop, m - 1.5, {"m_start": m - 1.5}),
        event(tracing.COMPILE_BACKEND, loop, m - 1.0, {"m_start": m - 2.0, "fun_name": "jit(train_step)"}),
        event(tracing.SHARD_PARAMS, loop, m - 4.5, {"m_start": m - 5.0}),
        # A recompile inside the window's third turn, on the loop's thread
        # and on another.
        event(tracing.COMPILE_BACKEND, loop, m + 0.025, {"m_start": m + 0.021, "fun_name": "jit(f)"}),
        event(tracing.COMPILE_TRACE, recorded.POOL, m + 0.025, {"m_start": m + 0.021, "fun_name": "f"}),
        event(tracing.COMPILE_SHORT, loop, m + 1.0, {"m_start": m + 1.0, "trace": [7, 0.001]}),
    ]
    with open(path, "w") as f:
        f.writelines(json.dumps(e) + "\n" for e in lines[:1] + added + lines[1:])
    record = train_events._load(path)
    assert len(record["spans"]) == len(train_events._load(
        os.path.join(str(plain), train_events.FILE))["spans"]) + len(added)
    assert [tracing.COMPILE_TRACE, loop, m - 4.0, m - 3.0, 1000.0 + m - 3.0] in record["spans"]
    assert metrics(held) == metrics(plain)
    assert set(metrics(held)) == set(train_events.NAMES)


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
