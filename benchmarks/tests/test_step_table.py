"""The step table (``benchmarks/lib/step_table.py``) and its three readers on
the traces recorded on the chip before PR 50, whose loss carries no name: the
"before" of ``step.unnamed_share`` is pinned here."""
import gzip
import json
import os
import re

import pytest

from benchmarks.lib import cells, program_trace, step_table
from benchmarks.lib import trace as tracing
from benchmarks.lib.program_trace import ProgramTrace
from benchmarks.lib.trace import Event, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = os.path.join(cells.BENCH_DIR, "layer_metrics")
NEW = ("step.unnamed_share", "model.head_loss_share", "model.mlp_share")
RECORDED = ("sft512_2steps", "dropless4k_2steps", "xing4_2steps")


def read(name, run):
    return cells.load_reader(READERS, name).read(run)


def recorded(name):
    path = os.path.join(DATA, name + ".trace.json.gz")
    if name == "xing4_2steps":  # recorded with the program's part (PR 39)
        return program_trace.recorded_run(path, "xing4")
    with gzip.open(path, "rt") as f:
        trace = Trace.from_json(json.load(f))
    return {"trace_data": trace, "program_trace": ProgramTrace().to_json(), "notes": []}


def window_events(run):
    _, trace, device, window = program_trace.of(run)
    return [e for e in trace.devices[device]
            if e.end > window[0] and e.start < window[1]], window


@pytest.mark.parametrize("name", RECORDED)
def test_rows_sum_to_the_busy_time_and_the_note_says_them(name):
    run = recorded(name)
    found = step_table.note(run)
    events, window = window_events(run)
    busy = tracing.busy_seconds(events, window)
    assert found["busy_s"] == busy and found["steps"] == 2
    rows = sum(t for by_pass in found["rows"].values() for t in by_pass.values())
    assert rows == pytest.approx(busy, rel=5e-3)
    text, = [n for n in run["notes"] if n.startswith("step table:")]
    said = [line for line in text.splitlines() if line.startswith("rows sum to")]
    assert len(said) == 1
    assert float(re.search(r"([\d.]+)% of busy", said[0]).group(1)) == pytest.approx(100, abs=0.5)
    assert "Pallas kernels" in text and "  _fwd_kernel" in text
    step_table.note(run)  # said once
    assert sum(n.startswith("step table:") for n in run["notes"]) == 1
    json.dumps(run["step_table"])  # --keep dumps the record


@pytest.mark.parametrize("name", RECORDED)
def test_part_of_agrees_with_pass_of_path_on_every_event(name):
    events, _ = window_events(recorded(name))
    parts = set()
    for e in events:
        part, scope, pass_ = step_table.part_of(e.path)
        assert pass_ == program_trace.pass_of_path(e.path), e.path
        assert bool(part) == step_table.holds_a_name(e.path), e.path
        parts.add(part)
    assert parts >= {"layers", "head and loss", "embed_tokens", "final_norm"}, parts


def test_parts_scopes_and_passes_by_the_programs_names():
    step = "jit(train_step)/"
    for path, expected in {
        step + "jvp(M)/checkpoint/layers_3/attn/rotary/mul": ("layers", "attn/rotary", "forward"),
        step + "transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/layers_0/moe/"
        "dispatch/layout/sort": ("layers", "moe/dispatch/layout", "replay"),
        step + "jvp(M)/layers_1/moe/router/router/dot_general": ("layers", "moe/router", "forward"),
        step + "jvp(M)/layers_1/mixer_hc/hc/pre/jit(_pre_fwd)/pallas_call kernel_name=_hc_pre_fwd_kernel":
            ("layers", "hc/pre", "forward"),
        step + "transpose(jvp(M))/layers_1/hc/post/add": ("layers", "hc/post", "backward"),
        step + "jvp(M)/streams/broadcast_in_dim": ("layers", "streams", "forward"),
        step + "jvp(M)/lm_head/dot_general": ("head and loss", "lm_head", "forward"),
        step + "jvp(loss)/reduce_max": ("head and loss", "loss", "forward"),
        # a tied head: flax says the method after the module's name
        step + "jvp(M)/lm_head/embed_tokens.attend/dot_general":
            ("head and loss", "lm_head/embed_tokens", "forward"),
        step + "transpose(jvp(M))/embed_tokens.attend/dot_general": ("embed_tokens", "", "backward"),
        step + "transpose(jvp(loss))/while/body/closed_call/checkpoint/rematted_computation/"
        "head/dot_general": ("head and loss", "loss/head", "replay"),
        step + "jvp(mtp)/loss/while/body/closed_call/head/dot_general": ("mtp", "loss/head", "forward"),
        step + "jvp(M)/mtp/mtp_layer/mla/q_latent/q_b_proj/dot_general":
            ("mtp", "mtp_layer/mla/q_latent", "forward"),
        step + "optimizer/mul": ("optimizer", "", "optimizer"),
        step + "jvp(M)/embed_tokens/gather": ("embed_tokens", "", "forward"),
        # XLA names what it does to an argument for the argument
        "opt_state[0].nu[\\'params\\'][\\'lm_head\\'][\\'kernel\\']": ("head and loss", "lm_head", ""),
        "params['params']['layers_2']['moe']['w_up']": ("layers", "moe", ""),
        "params['params']['mtp_layer']['mla']['o_proj']['kernel']": ("mtp", "mtp_layer/mla", ""),
        # no name of the program: the loss before PR 50, JAX's own, a bare primitive
        step + "jvp()/while/body/closed_call/dot_general": ("", "", "forward"),
        step + "transpose(jvp(M))/jvp(M)/remat2": ("", "", "backward"),
        step + "jvp(M)/scan": ("", "", "forward"),  # the last segment is the primitive's
        "gather": ("", "", ""), "": ("", "", ""),
    }.items():
        assert step_table.part_of(path) == expected, path


def made_up(bodies=None):
    """Two steps; a fusion and a copy without a path beside three with one."""
    device, host = [], []
    for s in (0.0, 10.0):
        host.append(Event("bench.step", s, 9.0))
        device += [
            Event("fusion.1", s + 1.0, 2.0, "jit(train_step)/jvp(M)/layers_0/mlp/dot_general"),
            Event("fusion.2", s + 3.0, 1.0),  # named for none of its instructions
            Event("copy.3", s + 4.0, 0.5, "copy.3"),  # XLA's own, labelled with its own name
            Event("fusion.4", s + 5.0, 1.5, "jit(train_step)/jvp()/while/body/dot_general"),
            Event("fusion.5", s + 7.0, 1.0, "jit(train_step)/optimizer/mul"),
        ]
    program = ProgramTrace(threads=[[]], loop_thread=0, bodies=bodies or {})
    return {"trace_data": Trace({0: device}, {0: []}, host),
            "program_trace": program.to_json(), "notes": []}


def test_an_event_without_a_path_is_read_by_its_fusions_body():
    body = ["jit(train_step)/transpose(jvp(M))/layers_0/attn/mul",
            "jit(train_step)/transpose(jvp(M))/layers_0/attn/add",
            "jit(train_step)/jvp(M)/layers_0/mlp/mul", "parameter"]
    run = made_up({"fusion.2": body})
    found = step_table.table(run)
    assert found["rows"]["layers/attn"] == {"backward": pytest.approx(2.0)}
    assert found["unnamed"] == {"copy": pytest.approx(1.0), "with a path": pytest.approx(3.0)}
    assert read("step.unnamed_share", run) == pytest.approx(100 * 4.0 / 12.0)
    # without the body the fusion is nobody's
    bare = step_table.table(made_up())
    assert bare["unnamed"]["fusion"] == pytest.approx(2.0)
    assert "layers/attn" not in bare["rows"]


def test_an_event_without_a_path_or_a_body_is_read_by_what_takes_its_result(tmp_path):
    hlo = tmp_path / "step.hlo.txt"
    hlo.write_text(
        "ENTRY %main (p: f32[8]) -> f32[8] {\n"
        "  %copy.3 = f32[8]{0} copy(%p)\n"
        "  %bitcast.9 = f32[8]{0} bitcast(%copy.3)\n"
        '  %fusion.1 = f32[8]{0} fusion(%bitcast.9), kind=kLoop, calls=%fused, '
        'metadata={op_name="jit(train_step)/jvp(M)/layers_0/mlp/dot_general"}\n'
        "}\n"
    )
    run = made_up()
    run["hlo_path"] = str(hlo)
    found = step_table.table(run)
    assert "copy" not in found["unnamed"]
    assert found["rows"]["layers/mlp"] == {"forward": pytest.approx(4.0 + 1.0)}
    operands = step_table.operands_of(hlo.read_text())
    assert operands == {"copy.3": ["p"], "bitcast.9": ["copy.3"],
                        "fusion.1": ["bitcast.9", "fused"]}
    assert step_table.consumers_of(operands)["copy.3"] == ["bitcast.9"]
    # what only the step's result takes is read by what it reads
    hlo.write_text(
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused, '
        'metadata={op_name="jit(train_step)/optimizer/add"}\n'
        "  %copy.3 = f32[8]{0} copy(%fusion.1)\n"
        "  ROOT %tuple.4 = (f32[8]{0}) tuple(%copy.3)\n"
    )
    del run["step_table"]
    found = step_table.table(run)
    assert found["rows"]["optimizer"] == {"optimizer": pytest.approx(2.0 + 1.0)}


def test_the_recorded_before_of_the_unnamed_share():
    """Before PR 50 the full-logit loss (sft512, dropless-4k) and the chunked
    one with its head (Xing4) carry the empty name; the recordings hold no
    compiled text, so XLA's own copies stay where B2 would move them."""
    values = {name: read("step.unnamed_share", recorded(name)) for name in RECORDED}
    assert values["sft512_2steps"] == pytest.approx(11.52, abs=0.02)
    assert values["dropless4k_2steps"] == pytest.approx(4.68, abs=0.02)
    assert values["xing4_2steps"] == pytest.approx(10.37, abs=0.02)
    with_path, without = step_table.unnamed_seconds(step_table.table(recorded("xing4_2steps")))
    assert with_path / (with_path + without) == pytest.approx(0.52, abs=0.01)
    # the full-logit head is a flax module and was always named; the chunked
    # one is read only once the loss has a scope
    assert read("model.head_loss_share", recorded("sft512_2steps")) == pytest.approx(12.03, abs=0.02)
    assert read("model.head_loss_share", recorded("dropless4k_2steps")) == pytest.approx(16.99, abs=0.02)
    assert read("model.head_loss_share", recorded("xing4_2steps")) is None
    assert read("model.mlp_share", recorded("sft512_2steps")) == pytest.approx(57.94, abs=0.02)
    assert read("model.mlp_share", recorded("xing4_2steps")) == pytest.approx(6.09, abs=0.02)
    assert read("model.mlp_share", recorded("dropless4k_2steps")) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_return_none_on_a_run_without_a_trace(name):
    assert read(name, {"trace_data": None, "notes": []}) is None
    assert read(name, {"trace_data": Trace(), "notes": []}) is None
