"""The five readers of the trainer's own record, on a small recorded file:
hand-built turns with one stall of each kind."""
from __future__ import annotations

import json
import os

import pytest

from benchmarks.lib import cells, train_events

LOOP, POOL, READER = "11", "22", "33"
TURN, CPU = 0.010, 0.002  # a quiet turn and the loop thread's CPU in it
# ordinal of the turn -> (seconds, loop thread CPU seconds)
STALLS = {4: (0.030, 0.022), 8: (0.040, 0.002), 10: (0.012, 0.002)}
N = 12


def write_record(out_dir, drop=None):
    """N turns of 10 ms: turn 4 stalls on the CPU, turn 8 off it (with an
    overdue sample, two involuntary switches and a major fault), turn 10
    holds a 2 ms collection; every turn another thread serves the control
    plane for 0.3 ms outside its wait."""
    lines = [{"header": {"rank": 0, "source": "worker-x", "dropped": 0,
                         "t_fit": 1000.5}}]
    m, cpu_ns, switches, majflt = 50.0, 10**9, 5, 0

    def event(name, entity, mono, attrs):
        lines.append({"category": "train", "event": name, "entity": entity,
                      "timestamp": 1000.0 + mono, "monotonic": mono,
                      "attrs": attrs, "source": "worker-x"})

    def report(ordinal):
        event("REPORT", LOOP, m, {"ordinal": ordinal})
        event("ray_tpu.train.report", LOOP, m + 2e-5, {"m_start": m + 1e-5})
        # Read by the thread that takes the report, 0.1 ms later.
        event("USAGE", LOOP, m + 1e-4, {
            "ordinal": ordinal, "thread_cpu_ns": cpu_ns,
            "process_cpu_s": cpu_ns * 3e-9, "nivcsw": switches,
            "majflt": majflt, "minflt": 100 * ordinal})

    report(0)
    for k in range(1, N + 3):  # the window's N, one traced step, the final
        seconds, cpu = STALLS.get(k, (TURN, CPU))
        # The call that carries report k-1 ends 0.2 ms into the turn; the
        # next one waits from 0.3 ms in until this turn's report.
        event("ray_tpu.worker.exec", POOL, m + 2e-4, {"m_start": m - 0.5 * TURN})
        event("ray_tpu.train.next_result", POOL, m + 1.5e-4, {"m_start": m - 0.5 * TURN})
        event("ray_tpu.train.result_wait", POOL, m + 1e-4, {"m_start": m - 0.5 * TURN})
        event("ray_tpu.worker.reply", POOL, m + 2.5e-4, {"m_start": m + 2e-4})
        event("ray_tpu.worker.recv", READER, m + 3e-4, {"m_start": m + 2.5e-4})
        if k == 8:
            event("OVERDUE", LOOP, m + 0.02, {
                "ordinal": 8, "waited_s": 0.02, "overslept_s": 0.0005,
                "frames": ["wait_loss (loop.py:7)", "loop (loop.py:3)"]})
            switches, majflt = switches + 2, majflt + 1
        if k == 10:
            event("GC_PAUSE", READER, m + 0.006, {"generation": 2, "seconds": 0.002})
        m, cpu_ns = m + seconds, cpu_ns + int(cpu * 1e9)
        report(k)
    lines.append({"category": "worker", "event": "REGISTERED", "entity": "w",
                  "timestamp": 1001.0, "monotonic": 0.0, "attrs": {}})
    if drop is not None:
        lines = [e for e in lines if not (e.get("event") == drop[0]
                                          and e["attrs"]["ordinal"] == drop[1])]
    path = os.path.join(out_dir, train_events.FILE)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.writelines(json.dumps(e) + "\n" for e in lines)


def a_run(out_dir):
    """The record run.py hands the readers: the window's steps as the
    benchmark's own clock saw them (the same turns)."""
    done, steps = 0.0, []
    for k in range(1, N + 1):
        done += STALLS.get(k, (TURN, CPU))[0]
        steps.append({"t_start": done - 0.009, "t_dispatch": done - 0.008,
                      "t_done": done, "loss": 1.0})
    return {"out_dir": str(out_dir), "steps": steps, "notes": [],
            "t_command": 1000.0, "setup": {"t_loop": 1002.0}}


def read(name, run):
    directory = os.path.join(cells.BENCH_DIR, "layer_metrics")
    return cells.load_reader(directory, name).read(run)


@pytest.fixture
def run(tmp_path):
    write_record(str(tmp_path))
    return a_run(tmp_path)


def test_loop_cpu_is_the_median_turns(run):
    assert read("trainer.loop_cpu_ms", run) == pytest.approx(2.0)


def test_gc_pause_is_the_mean_over_the_windows_turns(run):
    assert read("trainer.gc_pause_ms", run) == pytest.approx(2.0 / N)


def test_rpc_busy_leaves_the_wait_out(run):
    # exec and next_result end 0.2 and 0.15 ms into the turn (their wait
    # ended at 0.1), reply runs to 0.25, another thread's recv to 0.3.
    assert read("control.rpc_busy_ms", run) == pytest.approx(0.2, abs=1e-6)


def test_stall_is_split_by_the_loop_threads_cpu(run):
    on = read("trainer.stall_on_cpu_ms", run)
    off = read("trainer.stall_off_cpu_ms", run)
    # Turn 4: 20 ms over the median, all of it on the CPU. Turn 8: 30 ms,
    # none of it. Turn 10: 2 ms, the collector's on another thread: off.
    assert on == pytest.approx(20.0) and off == pytest.approx(32.0)
    turns = [STALLS.get(k, (TURN, CPU))[0] for k in range(1, N + 1)]
    share = read("trainer.stall_share", run)  # the outside clock's
    assert on + off == pytest.approx(10.0 * share * sum(turns))


def test_every_reader_is_declared_for_every_cell_and_moves_the_rate():
    bench = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in train_events.NAMES}
    for name in train_events.NAMES:
        m = declared[name]
        assert "workloads" not in m and m["moves"] == "tokens_per_s_per_chip"
        assert m["unit"] == "ms" and m["better"] == "lower"
        assert m["layer"] in layers
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(train_events.NAMES)


def test_the_stalled_turns_are_said_with_what_the_record_holds(run):
    read("trainer.loop_cpu_ms", run)
    read("trainer.gc_pause_ms", run)  # the notes are said once
    said = [json.loads(n.split("stalled turn ", 1)[1])
            for n in run["notes"] if "stalled turn" in n]
    assert [t["ordinal"] for t in said] == [4, 8]  # 2 ms is under the floor
    on_cpu, off_cpu = said
    assert on_cpu["excess_ms"] == pytest.approx(20.0)
    assert on_cpu["loop_cpu_excess_ms"] == pytest.approx(20.0)
    assert on_cpu["overdue"] == [] and on_cpu["nivcsw"] == 0
    assert off_cpu["excess_ms"] == pytest.approx(30.0)
    assert off_cpu["loop_cpu_excess_ms"] == 0.0
    assert (off_cpu["nivcsw"], off_cpu["majflt"], off_cpu["minflt"]) == (2, 1, 100)
    assert off_cpu["overdue"] == [
        [0.02, 0.0005, ["wait_loss (loop.py:7)", "loop (loop.py:3)"]]]
    spans = {(name, thread): ms for name, thread, ms in off_cpu["rpc_spans"]}
    assert spans[("ray_tpu.worker.reply", POOL)] == pytest.approx(0.05)
    assert spans[("ray_tpu.worker.recv", READER)] == pytest.approx(0.05)
    summary = [n for n in run["notes"] if "turns of the window" in n]
    assert len(summary) == 1 and "median turn 10.0000 ms (the benchmark's 10.0000" in summary[0]
    assert "overdue samples 1; events dropped 0" in summary[0]
    assert "read 0.1000 ms after the report" in summary[0]
    against = [n for n in run["notes"] if "trainer.stall_share x the window" in n]
    assert len(against) == 1 and "leaving 52.000 ms against" in against[0]
    split = [n for n in run["notes"] if "worker_ready_s split" in n]
    assert json.loads(split[0].split(": ", 1)[1]) == [
        ["fit", 0.5], ["worker REGISTERED", 1.0], ["the loop's first line", 2.0]]
    assert not [n for n in run["notes"] if "clock offset" in n]  # untraced


def test_without_the_file_every_reader_returns_none(tmp_path):
    run = a_run(tmp_path)  # the parent's program under these benchmark files
    for name in train_events.NAMES:
        assert read(name, run) is None
    assert run["notes"] == []


@pytest.mark.parametrize("lost", [("REPORT", 6), ("USAGE", 9)])
def test_a_record_that_lost_an_event_gives_nothing_and_says_so(tmp_path, lost):
    write_record(str(tmp_path), drop=lost)
    run = a_run(tmp_path)
    for name in train_events.NAMES:
        assert read(name, run) is None
    assert len(run["notes"]) == 1 and "does not hold the window's 12 turns" in run["notes"][0]


def test_the_run_record_stays_json(run):
    read("trainer.loop_cpu_ms", run)
    json.dumps({k: v for k, v in run.items()})  # --keep dumps it
