"""Kimi-Linear's cell by hand: its required FLOPs, what one call of the scan
kernels needs, the kernels its step is held to, the configuration against
the catalog's published keys, the cell judged by new files alone, and the
four new readers on a made-up trace."""
import json
import os

import pytest

from benchmarks.lib import cells, result
from benchmarks.lib.flops import flash_call
from benchmarks.lib.flops_gmm import gmm_call
from benchmarks.lib.flops_kda import kda_call, recurrence_per_token
from benchmarks.lib.flops_kimi import (
    expert_layer_matmul_params, kda_matmul_params, kimi_linear_decoder,
    layer_kinds, mla_matmul_params,
)
from benchmarks.lib.trace import Event, Trace
from benchmarks.tests.test_harness import made_up_run

CELL = "kimi-linear-48b-a3b-l5.longctx-16k"
PUBLISHED = {  # the catalog row's config, every key
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                       22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840,
}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def test_the_file_holds_every_published_key_and_lists_exactly_what_it_cut(cell):
    cfg = cell["config"]
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(cfg["reduced"]) == differs
    entry = next(c for c in cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))["configs"]
                 if c["name"] == "kimi-linear-48b-a3b-l5")
    assert set(entry["reduced"]) == differs
    for key in differs:
        assert cfg["reduced"][key]["source"] == PUBLISHED[key]
        assert cfg["reduced"][key]["here"] == cfg[key]
        assert cfg[key + "_published"] == PUBLISHED[key]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 16, 20480)
    # the floors: a whole period after the leading dense layer, 8 experts, an eighth
    assert layer_kinds(cfg) == [("kda", "mlp"), ("kda", "moe"), ("kda", "moe"),
                                ("mla", "moe"), ("kda", "moe")]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "16 chips" in cfg["deployment"] and cfg["assumed"] and cfg["departures"]


def test_the_traffic_is_the_issues(cell):
    t = cell["traffic"]
    assert (t["batch"], t["seq"], t["batches"], t["mesh"], t["trace_steps"],
            t["compare_last"]) == (1, 16384, 8, {}, 3, 256)
    assert t["tokens"] == {"distribution": "zipf", "exponent": 1.0}
    assert t["loss"] == {"fn": "ray_tpu.models.llama:chunked_causal_lm_loss",
                         "takes": "model", "args": {"chunk_size": 2048}}
    assert t["expect"] == {"moe_dispatch": "gmm"}
    assert t["optimizer"] == cells.load_cell("mistral-7b-l4.long16k")["traffic"]["optimizer"]


def test_required_flops_match_the_hand_count(cell):
    cfg = cell["config"]
    assert cells.resolve(cfg["required_flops"]) is kimi_linear_decoder
    # KDA: q, k, v and o of 2304 x 4096; two low-rank gates 2304 x 128 and
    # 128 x 4096; beta 2304 x 32
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    assert kda_matmul_params(cfg) == kda == 39_460_864
    # MLA: q 2304 x 32 x 192; down 2304 x 576; up 512 x 32 x 256; o 4096 x 2304
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert mla_matmul_params(cfg) == mla == 29_114_368
    # an expert layer: the router at 256, the shared expert, and 8 x 16 / 256
    # = half a routed expert a token here
    expert = 3 * 2304 * 1024
    moe = 2304 * 256 + expert + 0.5 * expert
    assert expert_layer_matmul_params(cfg) == moe
    dense, head = 3 * 2304 * 9216, 2304 * 20480
    matmuls = 6 * (4 * kda + mla + dense + 4 * moe + head)
    attention = 3 * 16384 * 32 * (192 + 128)  # one MLA layer, the causal half
    recurrence = 4 * 32 * 3 * 7 * 128 * 128  # four KDA layers, 32 heads
    assert recurrence_per_token(128, 128) == 3 * 7 * 128 * 128
    assert kimi_linear_decoder(cfg, 16384) == matmuls + attention + recurrence
    assert matmuls / 1e9 == pytest.approx(2.056, abs=5e-4)
    assert attention / 1e9 == pytest.approx(0.503, abs=5e-4)
    assert recurrence / 1e9 == pytest.approx(0.044, abs=5e-4)
    # what the cell's `why` says: the two mixers are over 60% at 16k
    mixers = 6 * (4 * kda + mla) + attention + recurrence
    assert mixers / kimi_linear_decoder(cfg, 16384) > 0.6
    # the MLA layer's attention grows with the sequence, the scan's cost does not
    assert kimi_linear_decoder(cfg, 8192) == matmuls + attention / 2 + recurrence


# A chunk of one head, C = 64, dk = dv = 128. Forward: the causal halves of
# k k^T and q k^T (64 x 64 x 128 each), the triangular system for W and U0
# (64 x 64 x 256), three matmuls against the state (2 x 64 x 128 x 128 each),
# the causal half of Aqk U, the state's decay; q, k, v in bfloat16, g in
# float32, beta, and O out.
FORWARD = 64 * 64 * (3 * 128 + 2 * 128) + 6 * 64 * 128 * 128 + 128 * 128
INPUTS = 64 * (3 * 128 * 2 + 128 * 4 + 4)


@pytest.mark.parametrize("kernel,flops,nbytes", [
    ("_kda_fwd_kernel", FORWARD, INPUTS + 64 * 128 * 2),
    # backward: the forward again and twice that; the inputs, the float32
    # state and dO in, a cotangent of each input out
    ("_kda_bwd_kernel", 3 * FORWARD, 2 * INPUTS + 4 * 128 * 128 + 64 * 128 * 2),
])
def test_kda_call_counts_a_chunks_matmuls_and_each_operand_once(kernel, flops, nbytes):
    assert FORWARD == 8_929_280
    chunks = 32 * 16384 // 64
    assert kda_call(kernel, 32, 16384, 128, 128) == (chunks * flops, chunks * nbytes)
    # bound by bytes on a v5e in this count
    got = kda_call(kernel, 32, 16384, 128, 128)
    assert got[1] / 819e9 > got[0] / 197e12
    with pytest.raises(KeyError):
        kda_call("_other_kernel", 32, 16384, 128, 128)


def test_the_cell_states_its_kernels_counts_and_one_calls_need(cell):
    from benchmarks.lib.kernels_kimi import kimi_linear_decoder as kernels

    assert cells.resolve(cell["config"]["kernels"]) is kernels
    stated = cells.stated_kernels(cell)
    # one MLA layer, four KDA layers, four expert layers
    assert {k: s["least"] for k, s in stated.items()} == {
        "_fwd_kernel": 1, "_bwd_dkv_kernel": 1, "_bwd_dq_kernel": 1,
        "_kda_fwd_kernel": 4, "_kda_bwd_kernel": 4,
        "_gmm_kernel": 24, "_tgmm_kernel": 12}
    for kernel in ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel"):
        assert stated[kernel]["call"] == flash_call(
            kernel, 32, 16384, 16384, 192, causal=True, d_v=128)
    for kernel in ("_kda_fwd_kernel", "_kda_bwd_kernel"):
        assert stated[kernel]["call"] == kda_call(kernel, 32, 16384, 128, 128)
    # 16,384 tokens x top-8 = 131,072 pairs, a sixteenth of them here in
    # expectation; the static layout bounds at all of them
    for kernel in ("_gmm_kernel", "_tgmm_kernel"):
        assert stated[kernel]["call"] == gmm_call(kernel, 8192, 2304, 1024, 16)


def test_the_cell_is_judged_by_its_own_files(cell):
    names = {m["name"] for m in cell["per_layer"]}
    assert {"model.kda_share", "model.mla_share", "kernel.kda_share",
            "kernel.kda_roofline", "model.moe_share", "model.moe_expert_share",
            "model.moe_dispatch_share", "kernel.gmm_share", "kernel.flash_share",
            "kernel.flash_roofline"} <= names
    assert "kernel.gmm_roofline" not in names  # PERF.md, Open questions
    run = made_up_run(cell)
    line, _ = result.result_line(run)
    assert line["correct"] is True
    for kernel, stated in cells.stated_kernels(cell).items():
        run = made_up_run(cell)
        run["setup"]["pallas_kernels"][kernel] = stated["least"] - 1
        assert result.result_line(run)[0]["correct"] is False, kernel
    run = made_up_run(cell)
    run["setup"]["moe_dispatch"] = "capacity"
    assert result.result_line(run)[0]["correct"] is False
    # the program's config comes from the file through its own constructor
    cfg = cells.program_config(cell["config"])
    assert (cfg.num_experts, cfg.experts_held, cfg.vocab_size) == (256, (0, 16), 20480)
    # the new metrics exist in no other cell
    old = cells.load_cell("olmoe-1b-7b-1chip.dropless-4k")
    assert not {"model.kda_share", "kernel.kda_roofline"} & {
        m["name"] for m in old["per_layer"]}


def made_up_trace(cell, slow=2.0):
    """Two steps in which every stated kernel call takes ``slow`` times its
    floor, under the scopes the program gives them, and a matmul of 1 ms
    under each of /kda/, /mla/ and /moe/."""
    stated = cells.stated_kernels(cell)
    under = {"_fwd_kernel": "layers_3/mla", "_bwd_dkv_kernel": "layers_3/mla",
             "_bwd_dq_kernel": "layers_3/mla", "_kda_fwd_kernel": "layers_1/kda/scan",
             "_kda_bwd_kernel": "layers_1/kda/scan", "_gmm_kernel": "layers_1/moe/experts",
             "_tgmm_kernel": "layers_1/moe/experts"}
    device, host, at = [], [], 0.0
    seconds = {}
    for step in range(2):
        start = at
        for kernel, s in stated.items():
            flops, nbytes = s["call"]
            dur = slow * max(flops / 197e12, nbytes / 819e9)
            for i in range(s["least"]):
                device.append(Event(
                    f"{kernel}.{step}.{i}", at, dur,
                    f"jit(train_step)/jvp(M)/{under[kernel]}/x kernel_name={kernel}"))
                at += dur
                seconds[kernel] = seconds.get(kernel, 0.0) + dur
        for scope in ("layers_0/kda/q_proj", "layers_3/mla/latent/kv_b_proj",
                      "layers_1/moe/shared/up_proj", "lm_head"):
            device.append(Event(f"fusion.{step}.{scope}", at, 1e-3,
                                f"jit(train_step)/jvp(M)/{scope}/dot_general"))
            at += 1e-3
        host.append(Event("bench.step", start, at - start))
    return Trace({0: device}, {0: []}, host), seconds, at


def test_the_four_new_readers_on_a_made_up_trace(cell):
    trace, seconds, busy = made_up_trace(cell)
    run = made_up_run(cell, trace=True)
    run["trace_data"], run["notes"] = trace, []
    wanted = [m for m in cell["per_layer"] if m["name"] in (
        "model.kda_share", "model.mla_share", "kernel.kda_share", "kernel.kda_roofline")]
    assert len(wanted) == 4
    metrics = cells.read_metrics(
        wanted, os.path.join(cells.BENCH_DIR, "layer_metrics"), run)
    kda_kernels = seconds["_kda_fwd_kernel"] + seconds["_kda_bwd_kernel"]
    flash = sum(seconds[k] for k in ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel"))
    assert metrics["kernel.kda_roofline"]["value"] == pytest.approx(50.0)
    assert metrics["kernel.kda_share"]["value"] == pytest.approx(100 * kda_kernels / busy)
    assert metrics["model.kda_share"]["value"] == pytest.approx(
        100 * (kda_kernels + 2e-3) / busy)
    assert metrics["model.mla_share"]["value"] == pytest.approx(100 * (flash + 2e-3) / busy)
    assert "16 calls, 0 of them bound by compute, the rest by bytes" in run["notes"][0]
    # a roofline share over 100% would mean the count is too high
    fast, _, _ = made_up_trace(cell, slow=1.0)
    run["trace_data"], run["notes"] = fast, []
    assert cells.read_metrics(
        wanted, os.path.join(cells.BENCH_DIR, "layer_metrics"), run
    )["kernel.kda_roofline"]["value"] == pytest.approx(100.0)


def test_the_new_readers_find_nothing_in_a_program_without_the_mixers(cell):
    """The parent's program has no /kda/ or /mla/ scope and no scan kernel:
    the readers return None and raise nothing."""
    device = [Event("fusion.1", 0.0, 1e-3, "jit(train_step)/jvp(M)/layers_0/attn/q_proj/dot"),
              Event("flash.1", 1e-3, 1e-3, "jit(train_step)/layers_0/attn/x kernel_name=_fwd_kernel")]
    run = made_up_run(cell, trace=True)
    run["trace_data"] = Trace({0: device}, {0: []}, [Event("bench.step", 0.0, 2e-3)])
    run["notes"] = []
    wanted = [m for m in cell["per_layer"] if m["name"] in (
        "model.kda_share", "model.mla_share", "kernel.kda_share", "kernel.kda_roofline")]
    assert cells.read_metrics(
        wanted, os.path.join(cells.BENCH_DIR, "layer_metrics"), run) == {}
    run["trace_data"] = None  # an untraced run
    assert cells.read_metrics(
        wanted, os.path.join(cells.BENCH_DIR, "layer_metrics"), run) == {}


def test_benchmark_json_gained_one_configuration_one_cell_and_four_metrics():
    bench = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    assert bench["configs"][-1]["name"] == "kimi-linear-48b-a3b-l5"
    assert bench["workloads"][-1]["name"] == CELL and bench["workloads"][-1]["chips"] == 1
    assert [m["name"] for m in bench["per_layer"][-4:]] == [
        "model.kda_share", "model.mla_share", "kernel.kda_share", "kernel.kda_roofline"]
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_per_chip"
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == {"model.moe_share", "model.moe_expert_share",
                      "model.moe_dispatch_share", "kernel.gmm_share",
                      "model.kda_share", "model.mla_share", "kernel.kda_share",
                      "kernel.kda_roofline"}
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_readings_tool_runs_at_the_rehearsal_size(tmp_path, monkeypatch):
    """benchmarks/tools/reference_readings_kimi.py walks its programs and
    its patched references on the CPU; at the tiny widths only the order of
    the readings is held: the programs of another function are further from
    the reference than the system is."""
    import sys

    from benchmarks.tools import reference_readings_kimi

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(sys, "argv", [
        "reference_readings_kimi.py", "--seeds", "4000000001", "--rehearse",
        "--out", str(tmp_path)])
    reference_readings_kimi.main()
    (line,) = (tmp_path / f"{CELL}.jsonl").read_text().splitlines()
    line = json.loads(line)
    assert line["seed"] == 4000000001 and line["positions"] == 64
    system = line["system"]["median"]
    for wrong in ("system_no_shared_expert", "system_no_scaling", "reference_e4m3"):
        assert line[wrong]["median"] > 1.3 * system, wrong
    # the rounding of the state is there (XLA drops a pair of converts)
    assert line["reference_state_bf16"]["median"] > 1e-3
    assert line["reference_drops_past_average"]["max"] > 1e-3


def test_the_cell_rehearses_through_the_normal_path(tmp_path):
    """``run.py --rehearse``: init -> JaxTrainer -> make_train_step at the
    files' rehearsal sizes on the CPU, the kernels interpreted."""
    import subprocess
    import sys

    env = {**os.environ, "RAY_TPU_NUM_CHIPS": "1", "JAX_PLATFORMS": "cpu",
           "RAY_TPU_PALLAS_INTERPRET": "1"}
    env.pop("XLA_FLAGS", None)  # the tests' eight virtual devices: one chip here
    done = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"), "--workload", CELL,
         "--rehearse", "--seconds", "1", "--seed", "3000000019"],
        env=env, capture_output=True, text=True, timeout=600, cwd=cells.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["failed"] == 0 and line["metrics"] == {}
    checks = next(l for l in done.stdout.splitlines() if "checks:" in l)
    for name in ("losses_finite", "loss_fell", "nothing_compiled_in_window",
                 "moe_dispatch", "mesh", "device_count"):
        assert f'"{name}": true' in checks, checks
