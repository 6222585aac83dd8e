"""The trace -> metrics reduction: interval arithmetic on made-up events,
and the whole reduction on a small trace recorded on the chip."""
import json
import os

import pytest

from benchmarks.lib import trace as tracing
from benchmarks.lib.trace import Event, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_measure_clip_and_subtract():
    assert tracing.union([(3, 4), (0, 1), (0.5, 2), (5, 5)]) == [[0, 2], [3, 4]]
    assert tracing.measure([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert tracing.clip([(0, 2), (3, 6)], 1, 4) == [(1, 2), (3, 4)]
    assert tracing.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)
    ]


def test_self_time_takes_nested_events_out_of_their_parent():
    loop = Event("while.1", 0.0, 10.0)
    events = [loop, Event("fusion.1", 1.0, 2.0), Event("fusion.2", 4.0, 5.0),
              Event("copy.1", 12.0, 1.0)]
    got = {e.name: t for e, t in tracing.self_times(events)}
    assert got == {"while.1": 3.0, "fusion.1": 2.0, "fusion.2": 5.0, "copy.1": 1.0}


def made_up():
    """Two steps of 10 s; the device idles 2 s at the head of each while the
    host puts the batch, and a collective runs 3 s, 1 s of it alone."""
    device = []
    host = []
    for s in (0.0, 10.0):
        host += [Event("bench.step", s, 10.0), Event("bench.device_put", s, 2.0),
                 Event("bench.wait_loss", s + 2.0, 8.0)]
        device += [
            Event("fusion.1", s + 2.0, 4.0, "jit(step)/jvp(M)/layers_0/moe/dot"),
            Event("all-reduce.1", s + 5.0, 3.0, "jit(step)/transpose(jvp(M))/x"),
            Event("fusion.2", s + 8.0, 2.0, "jit(step)/mul"),
        ]
    return Trace({0: device}, {0: []}, host)


def test_the_reductions_on_a_made_up_trace():
    trace = made_up()
    window = tracing.step_window(trace)
    events = trace.devices[0]
    assert window == (0.0, 20.0)
    assert tracing.busy_seconds(events, window) == 16.0
    assert tracing.idle_gaps(events, window, trace.host) == [["bench.device_put", 4.0]]
    assert tracing.share_of_busy(events, window, tracing.is_collective) == 6 / 16
    assert tracing.collective_seconds(trace, 0, window) == 6.0
    assert tracing.exposed_collective_seconds(trace, 0, window) == 4.0
    # an asynchronous collective counts from its start to its done, and is
    # exposed only where the core executes nothing else beside it
    trace.overlapped[0].append(Event("all-gather-start.1", 1.0, 2.5))
    assert tracing.collective_seconds(trace, 0, window) == 8.5
    assert tracing.exposed_collective_seconds(trace, 0, window) == 5.0
    assert tracing.top_ops(events, window, 2) == [
        ["fusion.1 jit(step)/jvp(M)/layers_0/moe/dot", 8.0],
        ["all-reduce.1 jit(step)/transpose(jvp(M))/x", 6.0],
    ]
    again = Trace.from_json(json.loads(json.dumps(trace.to_json())))
    assert again == trace


def test_op_paths_reads_instruction_names_metadata_and_kernel_names():
    import base64

    # a Mosaic module's string table: the kernel, then the frames around it
    body = base64.b64encode(
        b"ML\xefR\x00stable_mosaic.version\x00_bwd_dq_kernel\x00iteration_bounds"
        b"\x00_fwd_kernel.<locals>._compute\x00_fwd_kernel\x00"
    ).decode()
    hlo = '''
  %fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/jvp(M)/layers_0/moe/mul" source_file="x.py"}
  ROOT %attn.3 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(M))/layers_0/attn/pallas_call"}, backend_config={"custom_call_config":{"body":"BODY","cost_estimate":{}}}
'''.replace("BODY", body)
    paths = tracing.op_paths(hlo)
    assert paths["fusion.7"] == "jit(train_step)/jvp(M)/layers_0/moe/mul"
    assert paths["attn.3"] == (
        "jit(train_step)/transpose(jvp(M))/layers_0/attn/pallas_call "
        "kernel_name=_bwd_dq_kernel"
    )


def test_the_reduction_on_a_trace_recorded_on_the_chip():
    """Two steps of mistral-7b-l4 at b2 x s512 on a v5e chip (PR 22's first
    traced run, when sft512 still had 2 sequences a batch, cut to its first
    two bench.step spans), reduced by the readers the benchmark ships. The numbers are that trace's own: a change to the
    reduction that moves them has changed the yardstick."""
    import gzip

    from benchmarks.lib import cells

    with gzip.open(os.path.join(DATA, "sft512_2steps.trace.json.gz"), "rt") as f:
        trace = Trace.from_json(json.load(f))
    events, window = trace.devices[0], tracing.step_window(trace)
    assert (len(events), len(trace.overlapped[0]), len(trace.host)) == (3036, 1108, 12)
    assert window[1] - window[0] == pytest.approx(0.111273215)
    assert tracing.busy_seconds(events, window) == pytest.approx(0.104658812)
    gaps = tracing.idle_gaps(events, window, trace.host)
    assert [name for name, _ in gaps[:2]] == ["bench.wait_loss", "bench.device_put"]
    assert gaps[0][1] == pytest.approx(0.004976778)
    assert sum(s for _, s in gaps) == pytest.approx(0.111273215 - 0.104658812)
    name, seconds = tracing.top_ops(events, window, 1)[0]
    assert name.startswith("fusion.8 jit(train_step)/transpose(jvp(LlamaForCausalLM))/lm_head")
    assert seconds == pytest.approx(0.006119918)

    cell = cells.load_cell("mistral-7b-l4.sft512")
    run = {"cell": cell, "trace_data": trace,
           "notes": [], "setup": {"device_kind": "TPU v5 lite"}}
    directory = os.path.join(cells.BENCH_DIR, "layer_metrics")
    want = {
        "device.idle_share": 5.944290367,
        "kernel.flash_share": 2.490559514,
        "kernel.flash_roofline": 47.300918479,
        "step.optimizer_share": 11.735781025,
        # one chip and a dense model: nothing to read
        "mesh.collective_share": None,
        "mesh.collective_exposed_share": None,
        "model.moe_share": None,
    }
    for metric, value in want.items():
        got = cells.load_reader(directory, metric).read(run)
        assert got == (None if value is None else pytest.approx(value)), metric
    assert "24 calls, 0 of them bound by compute" in run["notes"][-1]


def test_the_grouped_matmul_readers_on_a_trace_recorded_on_the_chip():
    """Two steps of olmoe-1b-7b-1chip.dropless-4k on a v5e chip (PR 30's
    traced run of its parent commit, PR 29's program, cut by the parent's
    ``trace record`` to its first two bench.step spans). The numbers are what
    the parent's readers gave on it, when ``kernel.gmm_roofline`` still read
    OLMoE's keys itself: a reader or a ``kernels`` function that moves them
    has changed the yardstick."""
    import gzip

    from benchmarks.lib import cells

    with gzip.open(os.path.join(DATA, "dropless4k_2steps.trace.json.gz"), "rt") as f:
        trace = Trace.from_json(json.load(f))
    calls = [tracing.kernel_of(e) for e in trace.devices[0]]
    assert (calls.count("_gmm_kernel"), calls.count("_tgmm_kernel")) == (36, 18)
    cell = cells.load_cell("olmoe-1b-7b-1chip.dropless-4k")
    run = {"cell": cell, "trace_data": trace,
           "notes": [], "setup": {"device_kind": "TPU v5 lite"}}
    directory = os.path.join(cells.BENCH_DIR, "layer_metrics")
    want = {
        "kernel.gmm_roofline": 69.60775424919562,
        "kernel.gmm_share": 26.282712968882855,
        "kernel.flash_roofline": 54.767811258491115,
        "kernel.flash_share": 8.351076039470653,
    }
    for metric, value in want.items():
        got = cells.load_reader(directory, metric).read(run)
        assert got == pytest.approx(value, rel=1e-12), metric
    assert "54 calls, 54 of them bound by compute" in run["notes"][0]
