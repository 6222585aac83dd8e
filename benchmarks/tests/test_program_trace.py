"""The program-span reduction (``benchmarks/lib/program_trace.py``): its
interval arithmetic on made-up traces, and the eight readers that sit on it
on two steps of sft512 and of the MoE cell recorded on the chip."""
import json
import os

import pytest

from benchmarks.lib import cells, program_trace
from benchmarks.lib.program_trace import ProgramTrace
from benchmarks.lib.trace import Event, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "program_2steps.trace.json.gz")
READERS = os.path.join(cells.BENCH_DIR, "layer_metrics")
NEW = ("step.forward_share", "step.backward_share", "step.remat_share",
       "model.moe_expert_share", "model.moe_dispatch_share",
       "device.idle_between_programs_share", "control.idle_under_rpc_share",
       "trainer.report_lag_ms")


def read(name, run):
    return cells.load_reader(READERS, name).read(run)


def made_up():
    """Two steps of 10 s. The program runs 2..9 and 12..19 with a bubble of
    1 s inside each execution; between executions the device waits 3 s."""
    device, host, programs = [], [], []
    for s in (0.0, 10.0):
        host += [Event("bench.step", s, 9.5), Event("bench.device_put", s, 2.0),
                 Event("bench.wait_loss", s + 2.0, 7.0),
                 Event("bench.report", s + 9.0, 0.5)]
        programs.append(Event("jit_train_step(123)", s + 2.0, 7.0))
        device += [
            Event("fusion.1", s + 2.0, 2.0, "jit(train_step)/jvp(M)/layers_0/mlp/dot"),
            Event("fusion.2", s + 4.0, 1.0,
                  "jit(train_step)/transpose(jvp(M))/jvp(M)/checkpoint/"
                  "rematted_computation/layers_0/mlp/dot"),
            # the bubble: nothing runs from 5 to 6
            Event("fusion.3", s + 6.0, 2.0, "jit(train_step)/transpose(jvp(M))/lm_head/dot"),
            Event("fusion.4", s + 8.0, 1.0, "jit(train_step)/optimizer/mul"),
        ]
    trace = Trace({0: device}, {0: []}, host)
    program = ProgramTrace(threads=[[]], loop_thread=0, programs={0: programs})
    return trace, program


def as_run(trace, program):
    return {"trace_data": trace, "program_trace": program.to_json(), "notes": []}


def test_idle_splits_into_inside_and_outside_a_program_execution():
    run = as_run(*made_up())
    between, inside, window = program_trace.idle_split(run)
    assert window == (0.0, 19.5)
    assert between == [(0.0, 2.0), (9.0, 12.0), (19.0, 19.5)]
    assert inside == [(5.0, 6.0), (15.0, 16.0)]
    value = read("device.idle_between_programs_share", run)
    assert value == pytest.approx(100 * 5.5 / 19.5)
    total = read("device.idle_share", run)
    assert total == pytest.approx(100 * 7.5 / 19.5)  # the two parts sum to it
    assert any("inside a running step 10.25" in n for n in run["notes"])
    assert any("median turn 10000.0000 ms" in n for n in run["notes"])


def test_without_a_modules_line_an_execution_is_first_to_last_operation():
    trace, program = made_up()
    program.programs = {}
    for s in (0.0, 10.0):
        trace.host.append(Event("bench.dispatch", s + 1.5, 0.5))
    trace.host.sort(key=lambda e: (e.start, -e.dur))
    between, inside, _ = program_trace.idle_split(as_run(trace, program))
    assert inside == [(5.0, 6.0), (15.0, 16.0)]
    assert between == [(0.0, 2.0), (9.0, 12.0), (19.0, 19.5)]


def test_passes_add_up_and_a_mixed_fusion_counts_as_backward():
    trace, program = made_up()
    run = as_run(trace, program)
    assert read("step.forward_share", run) == pytest.approx(100 * 4 / 12)
    assert read("step.remat_share", run) == pytest.approx(100 * 2 / 12)
    assert read("step.backward_share", run) == pytest.approx(100 * 4 / 12)
    assert read("step.optimizer_share", run) == pytest.approx(100 * 2 / 12)
    # XLA fuses the update into a weight-gradient matmul: the fusion's body
    # holds both, whichever of them the fusion is named for
    program.bodies = {
        "fusion.3": ["jit(train_step)/transpose(jvp(M))/lm_head/dot",
                     "jit(train_step)/optimizer/mul"],
        "fusion.4": ["jit(train_step)/optimizer/add",
                     "jit(train_step)/transpose(jvp(M))/embed/dot"],
    }
    run = as_run(trace, program)
    assert read("step.backward_share", run) == pytest.approx(100 * 6 / 12)
    note, = [n for n in run["notes"] if n.startswith("step passes")]
    assert "optimizer 0.00 (sum 100.00)" in note
    assert "are 50.00 and count as backward (100.0% of it; 16.67 of them named" in note
    assert "hold replay instructions are 0.00" in note


def test_fusion_bodies_are_read_from_the_compiled_text():
    hlo = """
HloModule jit_train_step

%fused_computation.8 (param_0.1: f32[8]) -> (f32[8], f32[8]) {
  %param_0.1 = f32[8]{0} parameter(0)
  %dot.1 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(train_step)/transpose(jvp(M))/lm_head/dot_general"}
  %mul.2 = f32[8]{0} multiply(%dot.1, %dot.1), metadata={op_name="jit(train_step)/optimizer/mul" source_file="x.py"}
  ROOT %tuple = (f32[8]{0}, f32[8]{0}) tuple(%dot.1, %mul.2)
}

ENTRY %main.1 (Arg_0.1: f32[8]) -> (f32[8], f32[8]) {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="params"}
  ROOT %fusion.8 = (f32[8]{0}, f32[8]{0}) fusion(%Arg_0.1), kind=kOutput, calls=%fused_computation.8, metadata={op_name="jit(train_step)/transpose(jvp(M))/lm_head/dot_general"}
}
"""
    bodies = program_trace.fusion_bodies(hlo)
    assert bodies == {"fusion.8": [
        "jit(train_step)/transpose(jvp(M))/lm_head/dot_general",
        "jit(train_step)/optimizer/mul",
    ]}
    passes = program_trace.body_passes(bodies)
    assert program_trace.is_mixed(Event("fusion.8", 0, 1), passes)
    assert not program_trace.is_mixed(Event("fusion.9", 0, 1), passes)


def test_innermost_span_of_nested_spans():
    events = [Event("recv", 0.0, 10.0), Event("exec", 1.0, 6.0),
              Event("wait", 2.0, 3.0), Event("reply", 8.0, 1.0)]
    assert program_trace.innermost(events) == [
        (0.0, 1.0, "recv"), (1.0, 2.0, "exec"), (2.0, 5.0, "wait"),
        (5.0, 7.0, "exec"), (7.0, 8.0, "recv"), (8.0, 9.0, "reply"),
        (9.0, 10.0, "recv"),
    ]


def test_idle_under_another_threads_span_with_the_wait_cut_out():
    trace, program = made_up()
    # the actor thread: a call that waits for the report, packs it and is
    # answered; the next call arrives while the device still idles
    actor = [
        Event("ray_tpu.worker.exec", 1.0, 9.0),
        Event("ray_tpu.train.next_result", 1.0, 9.0),
        Event("ray_tpu.train.result_wait", 1.25, 8.25),  # until the report at 9.5
        Event("ray_tpu.worker.reply", 10.0, 1.5),
    ]
    reader = [Event("ray_tpu.worker.recv", 11.75, 0.25)]
    program.threads = [[Event("ray_tpu.train.report", 9.25, 0.25)], actor, reader]
    assert program_trace.rpc_intervals(program) == [
        [1.0, 1.25], [9.5, 11.5], [11.75, 12.0],
    ]
    run = as_run(trace, program)
    # idle between programs: 0..2, 9..12, 19..19.5 = 5.5 s; under an RPC span
    # and not in the wait: 1..1.25, 9.5..11.5, 11.75..12 = 2.5 s
    assert read("control.idle_under_rpc_share", run) == pytest.approx(100 * 2.5 / 5.5)
    note, = [n for n in run["notes"] if n.startswith("between-programs idle")]
    listed = json.loads(note.split("): ", 1)[1].split("; all of it")[0])
    assert [row[:2] for row in listed] == [
        ["ray_tpu.worker.reply", 1500.0], ["ray_tpu.train.result_wait", 1250.0],
        ["ray_tpu.train.next_result", 750.0], ["ray_tpu.worker.recv", 250.0],
    ]
    assert listed[0][2] == {"bench.device_put": 1500.0}
    assert listed[2][2] == {"outside": 500.0, "bench.device_put": 250.0}
    by_phase = json.loads(note.split("all of it by bench phase: ")[1])
    assert by_phase == {"bench.device_put": 4000.0, "bench.report": 1000.0,
                        "outside": 500.0}
    # a program that left no span (the parent commit) gives nothing to read
    program.threads = [[]]
    assert read("control.idle_under_rpc_share", as_run(trace, program)) is None
    assert read("trainer.report_lag_ms", as_run(trace, program)) is None


def test_reports_match_the_calls_that_carry_them_first_in_first_out():
    def call(start, end, wait_start, wait_end):
        return [Event("ray_tpu.train.next_result", start, end - start),
                Event("ray_tpu.train.result_wait", wait_start, wait_end - wait_start)]

    # the report at 0.5 went to the call in flight when the profiler started,
    # which left no span; the drain keeps up at 1.0 and falls behind at 2.0
    loop = [Event("ray_tpu.train.report", t, 0.1) for t in (0.5, 1.0, 2.0, 3.0)]
    actor = (call(0.0, 0.4, 0.05, 0.35)      # returned an item from before the trace
             + call(0.7, 1.3, 0.75, 1.2)     # waited through the put at 1.0
             + call(2.5, 2.75, 2.55, 2.6)    # began after the put at 2.0
             + call(3.0, 4.0, 3.05, 3.2))    # waited through the put at 3.0
    program = ProgramTrace(threads=[actor, loop], loop_thread=1)
    lags = program_trace.report_lags(program, (0.0, 10.0))
    assert lags == pytest.approx([0.3, 0.75, 1.0])
    assert program_trace.report_lags(program, (1.5, 10.0)) == pytest.approx([0.75, 1.0])
    trace, _ = made_up()
    program.programs = {0: [Event("jit_train_step", 2.0, 7.0)]}
    run = as_run(trace, program)
    assert read("trainer.report_lag_ms", run) == pytest.approx(750.0)
    assert any("last over first 3.33" in n for n in run["notes"])


def test_moe_scopes_split_the_layers_share():
    trace, program = made_up()
    paths = ["jit(train_step)/jvp(M)/layers_0/moe/router/dot",
             "jit(train_step)/transpose(jvp(M))/layers_0/moe/experts/dot",
             "jit(train_step)/jvp(M)/layers_0/moe/combine/all-reduce",
             "jit(train_step)/optimizer/mul"]
    for i, e in enumerate(trace.devices[0]):
        e.path = paths[i % 4]
    run = as_run(trace, program)
    assert read("model.moe_expert_share", run) == pytest.approx(100 * 2 / 12)
    assert read("model.moe_dispatch_share", run) == pytest.approx(100 * 8 / 12)
    assert read("model.moe_share", run) == pytest.approx(100 * 10 / 12)
    dense = as_run(*made_up())
    assert read("model.moe_expert_share", dense) is None
    assert read("model.moe_dispatch_share", dense) is None


# ------------------------------------------------- recorded on the chip


@pytest.fixture(scope="module", params=["sft512", "ep2seq2-4k"])
def recorded(request):
    return request.param, program_trace.recorded_run(RECORDED, request.param)


def test_the_recorded_traces_hold_the_programs_spans_and_the_modules_line(recorded):
    _, run = recorded
    program, trace, device, window = program_trace.of(run)
    assert len(program.programs[device]) == 2
    assert program.loop_thread is not None and len(program.threads) >= 2
    names = {e.name for t in program.threads for e in t}
    assert {"ray_tpu.train.report", "ray_tpu.train.next_result",
            "ray_tpu.train.result_wait", "ray_tpu.worker.reply"} <= names
    passes = program_trace.body_passes(program.bodies)
    assert any(program_trace.is_mixed(e, passes) for e in trace.devices[device])


def test_the_eight_readers_on_the_recorded_traces(recorded):
    cell, run = recorded
    got = {name: read(name, run) for name in NEW}
    old = {name: read(name, run) for name in
           ("step.optimizer_share", "model.moe_share", "device.idle_share")}
    passes = (got["step.forward_share"] + got["step.backward_share"]
              + got["step.remat_share"] + old["step.optimizer_share"])
    assert passes == pytest.approx(100.0, abs=1.0)
    assert 0 < got["device.idle_between_programs_share"] <= old["device.idle_share"]
    assert 0 <= got["control.idle_under_rpc_share"] <= 100
    assert 0 < got["trainer.report_lag_ms"] < 50
    if cell == "sft512":
        assert got["model.moe_expert_share"] is None
        assert got["model.moe_dispatch_share"] is None
    else:
        assert (got["model.moe_expert_share"] + got["model.moe_dispatch_share"]
                == pytest.approx(old["model.moe_share"], abs=1.0))
        assert got["model.moe_expert_share"] > got["model.moe_dispatch_share"]
    with open(os.path.join(DATA, "program_2steps.expected.json")) as f:
        expected = json.load(f)[cell]
    for name, value in got.items():
        assert value == (pytest.approx(expected[name]) if value is not None
                         else expected[name]), name
