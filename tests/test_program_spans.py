"""Program spans and scopes (``ray_tpu/util/tracing.py``).

In-graph scopes are checked on the compiled text of tiny models (CPU): the
``op_name`` of every instruction is what a device trace shows on the chip.
Host spans are checked in the xplane a CPU ``jax.profiler`` session writes:
the same file, lines and clock the benchmark's readers take from the chip.

This file holds the host spans and the lists themselves; the in-graph scopes
are in ``tests/test_program_paths_*.py``, a file a family of models, over
``tests/program_paths.py`` (the reading, ``EXEMPT``, ``SHOWN_BY`` and the cases
every family's compiled step passes).
"""
import contextlib
import glob
import os
import re
import subprocess
import sys
import threading

import jax
import pytest

import ray_tpu
from ray_tpu.util import tracing

from program_paths import SHOWN_BY


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- host spans


def host_lines(trace_dir: str) -> list:
    """One list per thread that left program spans in the session's xplane:
    [(name, start ns, end ns)] in start order."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    ))
    assert len(found) == 1, found
    lines = []
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            spans = sorted(
                ((e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for e in line.events if e.name.startswith("ray_tpu.")),
                key=lambda s: s[1],
            )
            if spans:
                lines.append(spans)
    return lines


@contextlib.contextmanager
def profiler_session(trace_dir: str):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def session_lines(tmp_path_factory):
    """A TrainWorker whose loop reports three times from its own thread
    while this thread drains ``next_result``, under a profiler session."""
    from ray_tpu.train.trainer import TrainWorker

    trace_dir = str(tmp_path_factory.mktemp("session_trace"))
    gate = threading.Semaphore(0)

    def loop(config):
        for i in range(3):
            assert gate.acquire(timeout=30)  # the drain waits first: a real wait
            ray_tpu.train.report({"step": i})

    worker = TrainWorker(0, 1, "spans", None)
    results = []
    with profiler_session(trace_dir):
        worker.run(loop, {})
        for _ in range(4):  # three reports and "done"
            gate.release()
            results.append(worker.next_result())
    worker._thread.join(timeout=30)
    assert not worker._thread.is_alive()
    assert [r[0] for r in results] == ["report"] * 3 + ["done"]
    return host_lines(trace_dir)


def test_report_and_next_result_are_spans_on_two_threads_in_order(session_lines):
    by_name = {}
    for i, line in enumerate(session_lines):
        for name, start, end in line:
            by_name.setdefault(name, []).append((i, start, end))
    reports = by_name[tracing.TRAIN_REPORT]
    drains = by_name[tracing.TRAIN_NEXT_RESULT]
    waits = by_name[tracing.TRAIN_RESULT_WAIT]
    assert len(reports) == 3 and len(drains) == 4 and len(waits) == 4
    loop_line, = {i for i, _, _ in reports}
    drain_line, = {i for i, _, _ in drains}
    assert loop_line != drain_line
    assert {i for i, _, _ in waits} == {drain_line}
    for (_, w0, w1), (_, d0, d1) in zip(waits, drains):
        assert d0 <= w0 <= w1 <= d1  # the wait lies inside its call
    for (_, r0, _), (_, d0, d1) in zip(reports, drains):
        assert d0 <= d1 and r0 <= d1  # first in, first out: report i ends call i
    for (_, _, before), (_, start, _) in zip(drains, drains[1:]):
        assert before <= start


@pytest.fixture(scope="module")
def actor_lines(tmp_path_factory):
    """An actor that opens a profiler session in its own process, serves a
    call under it and closes it: the worker's spans are in its xplane."""
    trace_dir = str(tmp_path_factory.mktemp("actor_trace"))

    @ray_tpu.remote
    class Traced:
        def start(self, trace_dir):
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            return True

        def work(self, x):
            return x + 1

        def stop(self):
            import jax

            jax.profiler.stop_trace()
            return True

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        actor = Traced.remote()
        assert ray_tpu.get(actor.start.remote(trace_dir), timeout=120)
        assert ray_tpu.get([actor.work.remote(i) for i in range(3)]) == [1, 2, 3]
        assert ray_tpu.get(actor.stop.remote(), timeout=120)
    finally:
        ray_tpu.shutdown()
    return host_lines(trace_dir)


def test_actor_call_leaves_exec_reply_and_recv(actor_lines):
    names = [name for line in actor_lines for name, _, _ in line]
    for name in (tracing.WORKER_EXEC, tracing.WORKER_REPLY, tracing.WORKER_RECV):
        assert names.count(name) >= 3, (name, names)
    # a call's reply follows its execution on the thread that ran it
    for line in actor_lines:
        execs = [s for s in line if s[0] == tracing.WORKER_EXEC]
        replies = [s for s in line if s[0] == tracing.WORKER_REPLY]
        for (_, _, done), (_, start, _) in zip(execs, replies[-len(execs):]):
            assert done <= start


def test_names_emitted_are_exactly_the_list(session_lines, actor_lines):
    spans = {name for lines in (session_lines, actor_lines)
             for line in lines for name, _, _ in line}
    assert spans == set(tracing.HOST_SPANS)
    assert all(name.startswith("ray_tpu.") for name in tracing.HOST_SPANS)
    # and every scope, mixer and body name is one that some family's compiled
    # step is held to show, in that family's own file
    listed = [name for names in SHOWN_BY.values() for name in names]
    assert sorted(listed) == sorted(
        set(tracing.SCOPES) | set(tracing.MIXERS) | set(tracing.BODY))


def test_source_names_no_span_or_scope_outside_the_list():
    """Every span and scope in ray_tpu/ is opened through util/tracing.py
    with one of its constants."""
    constants = {k for k, v in vars(tracing).items()
                 if k.isupper() and isinstance(v, str)}
    assert {getattr(tracing, k) for k in constants} == (
        set(tracing.HOST_SPANS) | set(tracing.SETUP_SPANS) | set(tracing.SCOPES)
        | set(tracing.MIXERS) | set(tracing.BODY)
    )
    literal = re.compile(r'name=f?"(?:%s)' % "|".join(tracing.MIXERS + tracing.BODY))
    calls = 0
    for path in glob.glob(os.path.join(REPO, "ray_tpu", "**", "*.py"), recursive=True):
        with open(path) as f:
            source = f.read()
        if path.endswith(os.path.join("util", "tracing.py")):
            continue
        assert not re.search(r"named_scope|TraceAnnotation|jax\.profiler", source), path
        if os.sep + "models" + os.sep in path:  # a module is named from the lists
            assert not literal.search(source), (path, literal.search(source))
        for arg in re.findall(r"\b_?tracing\.(?:span|scope)\(([^)]*)\)", source):
            calls += 1
            assert re.fullmatch(r"_?tracing\.([A-Z_]+)", arg), (path, arg)
            assert arg.split(".")[1] in constants, (path, arg)
    assert calls >= 20


def test_the_key_value_span_store_is_gone():
    for name in ("inject", "record_span", "get_trace", "new_context", "enabled"):
        assert not hasattr(tracing, name), name
    with open(tracing.__file__) as f:
        source = f.read()
    assert "RAY_TPU_TRACE" not in source and "kv_put" not in source


def test_import_leaves_jax_out_and_span_is_a_noop_without_it():
    code = (
        "import sys, contextlib\n"
        "import ray_tpu.util.tracing as t\n"
        "import ray_tpu\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "cm = t.span(t.TRAIN_REPORT)\n"
        "assert isinstance(cm, contextlib.nullcontext), cm\n"
        "with cm:\n"
        "    pass\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True,
        timeout=120, env={**os.environ, "PYTHONPATH": REPO},
    )
    assert done.returncode == 0 and done.stdout.strip() == "ok", done.stderr
