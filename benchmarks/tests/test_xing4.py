"""Xing4.0-29B-A4B's cell by hand: its required FLOPs and the bytes its
hyper-connections need, the kernels its step is held to, the configuration
against the catalog's published keys, the configuration and the cell found in
``BENCHMARK.json`` by name, the three new readers on a made-up trace and on
two steps recorded on the chip, and the reference's Sinkhorn and maps against
a per-token loop in numpy."""
import json
import os

import numpy as np
import pytest

from benchmarks.lib import cells, program_trace, result
from benchmarks.lib.flops import flash_call
from benchmarks.lib.flops_gmm import gmm_call
from benchmarks.lib.flops_hc import connection_bytes_per_token, step_bytes
from benchmarks.lib.flops_xing4 import (
    attention_per_token, connection_matmul_params, expert_layer_matmul_params,
    matmul_params, mla_matmul_params, xing4_decoder,
)
from benchmarks.lib.trace import Event, Trace
from benchmarks.tests.test_harness import made_up_run

CONFIG, CELL = "xing4-29b-a4b-l5", "xing4-29b-a4b-l5.pretrain-mtp-4k"
PUBLISHED = {  # the catalog row's config, every key
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144, "model_type": "xing4_0",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072,
}
READERS = os.path.join(cells.BENCH_DIR, "layer_metrics")
NEW_METRICS = ("model.hc_share", "model.hc_roofline", "model.mtp_share")
RECORDED = os.path.join(cells.BENCH_DIR, "tests", "data", "xing4_2steps.trace.json.gz")


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def benchmark():
    return cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))


def test_the_file_holds_every_published_key_and_lists_exactly_what_it_cut(cell):
    cfg = cell["config"]
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    # n_routed_experts is the router's width and stays; num_experts, the
    # sibling files' key, counts the experts held
    assert differs == {"num_hidden_layers", "first_k_dense_replace", "vocab_size"}
    assert set(cfg["reduced"]) == differs | {"num_experts"}
    entry = next(c for c in benchmark()["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(cfg["reduced"]) and entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    for key, cut in cfg["reduced"].items():
        assert cut["here"] == cfg[key] and cfg[key + "_published"] == cut["source"]
        assert cut["source"] == PUBLISHED["n_routed_experts" if key == "num_experts" else key]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["num_experts"],
            cfg["vocab_size"], cfg["num_nextn_predict_layers"]) == (5, 1, 16, 32768, 1)
    # the floors: four layers after the leading dense one, 8 experts, an eighth
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert cfg["num_experts"] * 4 == cfg["n_routed_experts"]
    assert cfg["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert "over 4 chips" in cfg["deployment"] and "6 bytes" in cfg["deployment"]
    assert {"hc_initial_values", "streams_start_and_end", "mtp_loss_weight",
            "mtp_placement", "q_latent_norm", "rope_scaling_type", "scoring",
            "initializer_range"} <= set(cfg["assumed"])
    assert len(cfg["departures"]) == 4
    # the held quarter's rows move at a cost that does not follow the routing
    assert cfg["program"]["set"]["held_rows"] == "gather"
    assert (cfg["mtp_loss_weight"], cfg["hc_alpha_init"], cfg["hc_res_diagonal_init"],
            cfg["initializer_range"]) == (0.3, 1.0, 2.0, 0.02)
    # no width is cut: the rehearsal sizes are the only place one changes
    for key in cfg["rehearsal"]:
        assert key in cfg


def test_the_traffic_is_the_issues(cell):
    t = cell["traffic"]
    assert (t["batch"], t["seq"], t["batches"], t["mesh"], t["trace_steps"],
            t["compare_last"]) == (1, 4096, 16, {}, 3, 256)
    assert t["tokens"] == {"distribution": "zipf", "exponent": 1.0}
    assert t["loss"] == {"fn": "ray_tpu.models.xing4:mtp_chunked_lm_loss",
                         "takes": "model", "args": {"chunk_size": 2048, "mtp_weight": 0.3}}
    assert t["loss"]["args"]["mtp_weight"] == cell["config"]["mtp_loss_weight"]
    assert t["expect"] == {"moe_dispatch": "gmm"}
    assert t["loop"] == "benchmarks.loops.train_lm:train_loop"
    assert t["optimizer"] == cells.load_cell("mistral-7b-l4.long16k")["traffic"]["optimizer"]
    assert t["seq"] == cell["config"]["rope_scaling"]["original_max_position_embeddings"]


def test_required_flops_match_the_hand_count(cell):
    cfg = cell["config"]
    assert cells.resolve(cfg["required_flops"]) is xing4_decoder
    # MLA: q 3584 x 768 and 768 x 32 x 192; down 3584 x 576; up 512 x 32 x 256;
    # o 32 x 128 x 3584
    mla = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 + 32 * 128 * 3584
    assert mla_matmul_params(cfg) == mla == 28_409_856
    phi = 4 * 3584 * (4 + 4 + 16)
    assert connection_matmul_params(cfg) == phi == 344_064
    # an expert layer: the router at 64, the shared expert, and 4 x 16 / 64 =
    # one routed expert a token here
    expert = 3 * 3584 * 1024
    moe = 3584 * 64 + expert + 1.0 * expert
    assert expert_layer_matmul_params(cfg) == moe
    dense, head, projection = 3 * 3584 * 9216, 3584 * 32768, 2 * 3584 * 3584
    # five layers and the module's: six mixers, twelve hyper-connections, one
    # dense FFN, five expert layers, the projection, two passes of the head
    params = 6 * (mla + 2 * phi) + dense + 5 * moe + projection + 2 * head
    assert matmul_params(cfg) == params
    assert params / 1e6 == pytest.approx(645.5, abs=0.05)
    attention = 3 * 6 * 4096 * 32 * (192 + 128)  # six layers, the causal half
    assert attention_per_token(cfg, 4096) == attention
    assert xing4_decoder(cfg, 4096) == 6 * params + attention
    assert xing4_decoder(cfg, 4096) / 1e9 == pytest.approx(4.63, abs=5e-3)
    assert 6 * params / 1e9 == pytest.approx(3.87, abs=5e-3)
    assert attention / 1e9 == pytest.approx(0.755, abs=5e-3)
    assert attention / xing4_decoder(cfg, 4096) == pytest.approx(0.16, abs=5e-3)
    # what the cell's `why` says: the module is 30% of the matmul FLOPs
    module = mla + 2 * phi + moe + projection + head
    assert module / params == pytest.approx(0.30, abs=5e-3)
    # attention grows with the sequence, the matmuls do not
    assert xing4_decoder(cfg, 8192) == 6 * params + 2 * attention
    # and the parameters held: 1,471 M with the maps, 8.22 GiB at 6 bytes
    layer = mla + 2 * (phi + 3 + 4 + 4 + 16) + 2 * 3584
    held = (2 * head + 6 * layer + 6 * 768 + 6 * 512 + dense
            + 5 * (3584 * 64 + 64 + 17 * expert) + projection + 4 * 3584)
    assert held / 1e6 == pytest.approx(1471.3, abs=0.5)
    assert held * 6 / 2**30 == pytest.approx(8.22, abs=0.01)


def test_the_hyper_connections_bytes_match_the_hand_count(cell):
    # a token's streams are 4 x 3584 two-byte channels. Forward: streams in,
    # u out, y in, streams out: 10 x 3584; backward: dX' and X in, y in, dy
    # out, du in, dX out: 15 x 3584
    assert connection_bytes_per_token(4, 3584) == (10 + 15) * 3584 * 2 == 179_200
    # twelve a step (two a layer, six layers with the module's) at 4,096 tokens
    assert step_bytes(cell["config"], 4096) == 12 * 4096 * 179_200
    assert step_bytes(cell["config"], 4096) / 1e9 == pytest.approx(8.81, abs=5e-3)
    assert step_bytes(cell["config"], 4096) / 819e9 * 1e3 == pytest.approx(10.75, abs=0.01)
    # one stream is a plain residual: x in, y in, x' out and as much back
    assert connection_bytes_per_token(1, 3584) == 10 * 3584 * 2


def test_the_cell_states_its_kernels_counts_and_one_calls_need(cell):
    from benchmarks.lib.kernels_xing4 import xing4_decoder as kernels

    assert cells.resolve(cell["config"]["kernels"]) is kernels
    stated = cells.stated_kernels(cell)
    # six MLA layers and five expert layers, the module's among both
    assert {k: s["least"] for k, s in stated.items()} == {
        "_fwd_kernel": 6, "_bwd_dkv_kernel": 6, "_bwd_dq_kernel": 6,
        "_gmm_kernel": 30, "_tgmm_kernel": 15}
    for kernel in ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel"):
        assert stated[kernel]["call"] == flash_call(
            kernel, 32, 4096, 4096, 192, causal=True, d_v=128)
    assert stated["_fwd_kernel"]["call"][0] == 2 * 32 * 4096 * 4096 // 2 * (192 + 128)
    # 4,096 tokens x top-4 = 16,384 pairs, a quarter of them here in expectation
    for kernel in ("_gmm_kernel", "_tgmm_kernel"):
        assert stated[kernel]["call"] == gmm_call(kernel, 4096, 3584, 1024, 16)
    assert stated["_gmm_kernel"]["call"] == (
        2 * 4096 * 3584 * 1024, 2 * (4096 * (3584 + 1024) + 16 * 3584 * 1024))


def test_the_cell_is_judged_by_its_own_files(cell):
    names = {m["name"] for m in cell["per_layer"]}
    assert {*NEW_METRICS, "model.mla_share", "model.mla_rotary_share", "model.moe_share",
            "model.moe_expert_share", "model.moe_dispatch_share", "kernel.gmm_share",
            "kernel.flash_share", "kernel.flash_roofline",
            "trainer.step_ms_p95_over_p50"} <= names
    assert not {"kernel.gmm_roofline", "model.kda_share", "kernel.kda_share"} & names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "mfu_required", "setup_s"}
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
    assert (cfg.num_experts, cfg.experts_held, cfg.vocab_size, cfg.num_layers,
            cfg.first_k_dense_replace, cfg.q_lora_rank) == (64, (0, 16), 32768, 5, 1, 768)
    assert cfg.rope_scaling.factor == 64 and cfg.mla_rope and not cfg.qk_head_norm
    assert (cfg.hyper_connections.mult, cfg.hyper_connections.sinkhorn_iters) == (4, 20)
    # the new metrics exist in no other cell
    for other in benchmark()["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & {
                m["name"] for m in cells.load_cell(other["name"])["per_layer"]}


def test_benchmark_json_holds_the_configuration_and_the_cell_by_name():
    """By name, not by position: a later PR appends after them."""
    bench = benchmark()
    configs = {c["name"]: c for c in bench["configs"]}
    workloads = {w["name"]: w for w in bench["workloads"]}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    assert configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size"]
    assert workloads[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "pretrain-mtp-4k", "chips": 1,
        "why": workloads[CELL]["why"]}
    assert len(workloads[CELL]["why"]) <= 200 and len(configs[CONFIG]["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(workloads) // 4)
    for name in NEW_METRICS:
        assert metrics[name] == {
            "name": name, "unit": "%", "better": "higher" if "roofline" in name else "lower",
            "source": "device_trace", "layer": "models",
            "moves": "tokens_per_s_per_chip", "workloads": [CELL]}
        assert os.path.exists(os.path.join(READERS, name + ".py"))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == {*NEW_METRICS, "model.moe_share", "model.moe_expert_share",
                      "model.moe_dispatch_share", "kernel.gmm_share", "model.mla_share",
                      "model.mla_rotary_share", "trainer.step_ms_p95_over_p50"}
    # each older list was appended to, after sarvam's cell, and not reordered
    for name in listed - set(NEW_METRICS):
        cells_of = metrics[name]["workloads"]
        assert cells_of.index(CELL) == cells_of.index("sarvam-105b-l5.pretrain-4k") + 1
    assert len(json.dumps(bench)) < 64 * 1024


# ------------------------------------------------------------- the readers


def made_up_trace(cell, slow=2.0):
    """Two steps in which every stated kernel call takes ``slow`` times its
    floor, the hyper-connections' operations together ``slow`` times the
    floor of a step's bytes, and 1 ms each goes to a q latent's matmul, a
    mixer's output projection, the module's projection, the module's pass of
    the head (forward and backward) and the main head."""
    stated = cells.stated_kernels(cell)
    hc_floor = step_bytes(cell["config"], 4096) / 819e9
    device, host, at = [], [], 0.0
    seconds = {"hc": 0.0, "mtp": 0.0}

    def op(name, dur, path, *counted):
        nonlocal at
        device.append(Event(name, at, dur, f"jit(train_step)/{path}"))
        at += dur
        for key in counted:
            seconds[key] = seconds.get(key, 0.0) + dur

    for step in range(2):
        start = at
        for kernel, s in stated.items():
            flops, nbytes = s["call"]
            dur = slow * max(flops / 197e12, nbytes / 819e9)
            under = "layers_1/moe/experts" if "gmm" in kernel else "layers_1/mla"
            for i in range(s["least"]):
                op(f"{kernel}.{step}.{i}", dur,
                   f"jvp(M)/{under}/x kernel_name={kernel}", kernel)
        for i, path in enumerate((
                "jvp(M)/layers_0/mixer_hc/hc/pre/dot_general",
                "jvp(M)/layers_0/mixer_hc/hc/sinkhorn/div",
                "jvp(M)/layers_0/hc/post/add",
                "transpose(jvp(M))/mtp/mtp_layer/ffn_hc/hc/sinkhorn/mul")):
            op(f"fusion.hc.{step}.{i}", slow * hc_floor / 4, path,
               *(("hc", "mtp") if "/mtp/" in path else ("hc",)))
        for path in ("jvp(M)/layers_0/mla/q_latent/q_b_proj/dot_general",
                     "jvp(M)/layers_3/mla/o_proj/dot_general",
                     "jvp(M)/lm_head/dot_general"):
            op(f"fusion.{step}.{path}", 1e-3, path)
        for path in ("jvp(M)/mtp/mtp_proj/dot_general",
                     "jvp(mtp)/while/body/closed_call/dot_general",
                     "transpose(jvp(mtp))/while/body/closed_call/dot_general"):
            op(f"fusion.{step}.{path}", 1e-3, path, "mtp")
        host.append(Event("bench.step", start, at - start))
    return Trace({0: device}, {0: []}, host), seconds, at


def test_the_new_readers_on_a_made_up_trace(cell):
    trace, seconds, busy = made_up_trace(cell)
    run = made_up_run(cell, trace=True)
    run["trace_data"], run["notes"] = trace, []
    wanted = [m for m in cell["per_layer"]
              if m["name"] in (*NEW_METRICS, "model.mla_share", "kernel.flash_roofline")]
    assert len(wanted) == 5
    metrics = cells.read_metrics(wanted, READERS, run)
    assert metrics["model.hc_share"]["value"] == pytest.approx(100 * seconds["hc"] / busy)
    assert metrics["model.hc_roofline"]["value"] == pytest.approx(50.0)
    assert metrics["model.mtp_share"]["value"] == pytest.approx(100 * seconds["mtp"] / busy)
    flash = sum(seconds[k] for k in ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel"))
    assert metrics["model.mla_share"]["value"] == pytest.approx(100 * (flash + 4e-3) / busy)
    assert metrics["kernel.flash_roofline"]["value"] == pytest.approx(50.0)
    note = next(n for n in run["notes"] if n.startswith("model.hc_roofline"))
    assert "2 steps, 17.616 GB needed" in note
    # a share of a floor cannot pass 100%: at the floor it reads it
    fast, _, _ = made_up_trace(cell, slow=1.0)
    run["trace_data"], run["notes"] = fast, []
    assert cells.read_metrics(wanted, READERS, run)[
        "model.hc_roofline"]["value"] == pytest.approx(100.0)


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(cell):
    """sarvam's program (and the parent's whole tree) has no /hc/ and no
    /mtp/ operation: each reader returns None and raises nothing."""
    device = [Event("fusion.1", 0.0, 1e-3, "jit(train_step)/jvp(M)/layers_3/mla/latent/kv_b_proj/dot"),
              Event("fusion.2", 1e-3, 1e-3, "jit(train_step)/jvp(M)/layers_0/mlp/up_proj/dot"),
              Event("flash.1", 2e-3, 1e-3, "jit(train_step)/layers_3/mla/x kernel_name=_fwd_kernel")]
    run = made_up_run(cell, trace=True)
    run["trace_data"] = Trace({0: device}, {0: []}, [Event("bench.step", 0.0, 3e-3)])
    run["notes"] = []
    wanted = [m for m in cell["per_layer"] if m["name"] in NEW_METRICS]
    assert cells.read_metrics(wanted, READERS, run) == {}
    run["trace_data"] = None  # an untraced run
    assert cells.read_metrics(wanted, READERS, run) == {}


def test_the_readers_on_two_steps_recorded_on_the_chip(cell):
    """Two steps of the cell as a v5e ran them (PR 39, call 1): the three new
    readers and the older ones the cell is listed under find their
    operations, and every share of a floor stays under 100%."""
    run = {**made_up_run(cell, trace=True), **program_trace.recorded_run(RECORDED, "xing4")}
    names = (*NEW_METRICS, "model.mla_share", "model.mla_rotary_share", "model.moe_share",
             "model.moe_expert_share", "model.moe_dispatch_share", "kernel.gmm_share",
             "kernel.flash_share", "kernel.flash_roofline")
    wanted = [m for m in cell["per_layer"] if m["name"] in names]
    metrics = {k: v["value"] for k, v in cells.read_metrics(wanted, READERS, run).items()}
    assert set(metrics) == set(names)
    assert all(0 < v < 100 for v in metrics.values()), metrics
    assert 5 < metrics["model.hc_share"] < 40 and 5 < metrics["model.hc_roofline"] < 100
    assert 10 < metrics["model.mtp_share"] < 40 and 20 < metrics["model.mla_share"] < 60
    assert metrics["model.moe_expert_share"] + metrics["model.moe_dispatch_share"] < \
        metrics["model.moe_share"]
    # the hyper-connections' three parts are all there, and the module's head
    paths = [e.path for e in run["trace_data"].devices[0]]
    for part in ("/hc/pre/", "/hc/sinkhorn/", "/hc/post/", "/mla/q_latent/",
                 "/mtp/mtp_layer/", "(mtp)/"):
        assert any(part in p for p in paths), part


# ------------------------- the reference against a naive second formulation


def naive_maps(p, streams, iters=20, eps=1e-6, rms_eps=1e-6, clamp=30.0):
    """H_pre, H_post, H_res a token at a time in numpy float64, Sinkhorn as
    two nested Python loops over rows and over columns."""
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    n = streams.shape[1]
    pre, post, res = [], [], []
    for x in np.asarray(streams, np.float64):
        flat = x.reshape(-1)
        h = flat / np.sqrt((flat * flat).mean() + rms_eps) @ w["phi"]
        pre.append(1 / (1 + np.exp(-(w["alpha"][0] * h[:n] + w["b_pre"]))))
        post.append(2 / (1 + np.exp(-(w["alpha"][1] * h[n:2 * n] + w["b_post"]))))
        m = np.exp(np.clip(w["alpha"][2] * h[2 * n:].reshape(n, n) + w["b_res"], -clamp, clamp))
        for _ in range(iters):
            for i in range(n):
                m[i, :] = m[i, :] / (m[i, :].sum() + eps)
            for j in range(n):
                m[:, j] = m[:, j] / (m[:, j].sum() + eps)
        res.append(m)
    return np.array(pre), np.array(post), np.array(res)


@pytest.fixture(scope="module")
def one_connection():
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    n, c, t = 4, 12, 40
    p = {"phi": rng.normal(size=(n * c, 2 * n + n * n)) * 0.3,
         "alpha": rng.uniform(0.5, 1.5, 3), "b_pre": rng.normal(size=n) * 0.5,
         "b_post": rng.normal(size=n) * 0.5,
         "b_res": 2.0 * np.eye(n) + rng.normal(size=(n, n)) * 0.5}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    streams = jnp.asarray(rng.normal(size=(t, n, c)), jnp.float32)
    cfg = {"rms_norm_eps": 1e-6, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
           "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30}
    return p, streams, cfg


def test_the_references_maps_are_the_naive_per_token_ones(one_connection):
    import jax

    from benchmarks.reference import xing4_decoder as reference

    p, streams, cfg = one_connection
    with jax.default_matmul_precision("highest"):
        got = reference.connection_maps(p, streams, cfg)
        out = reference.hyper_connected(p, streams, lambda u: 2.0 * u, cfg)
    want = naive_maps(p, streams)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-6)
    pre, post, res = want
    x = np.asarray(streams, np.float64)
    u = np.einsum("tn,tnc->tc", pre, x)
    expected = np.einsum("tij,tjc->tic", res, x) + post[:, :, None] * (2.0 * u)[:, None, :]
    np.testing.assert_allclose(out, expected, rtol=2e-4, atol=1e-5)
    # columns of H_res, normalised last, sum to one, rows nearly (twenty
    # rounds at these wide logits); H_post lies in (0, 2), H_pre in (0, 1)
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=5e-2)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
    assert 0 < pre.min() and pre.max() < 1 < post.max() < 2


@pytest.mark.parametrize("wrong", ["reference_rows_only", "reference_post_without_2",
                                   "reference_constant_maps", "one-iteration",
                                   "transposed"])
def test_the_naive_one_tells_another_function_from_the_reference(
        one_connection, wrong, monkeypatch):
    """What the comparison has to be able to see, on the reference's side:
    ``wrong_xing4.py``'s three patched maps, one iteration for twenty, and
    H_res applied transposed. (Columns normalised before rows is not among
    them: twenty rounds bring both orders to nearly the same matrix.)"""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import xing4_decoder as reference
    from benchmarks.tools import wrong_xing4

    p, streams, cfg = one_connection
    pre, post, res = naive_maps(p, streams)
    x = np.asarray(streams, np.float64)
    u = np.einsum("tn,tnc->tc", pre, x)
    want = np.einsum("tij,tjc->tic", res, x) + post[:, :, None] * u[:, None, :]
    if wrong.startswith("reference_"):
        name, replacement = wrong_xing4.references(None)[wrong]
        monkeypatch.setattr(reference, name, replacement(getattr(reference, name)))
    elif wrong == "one-iteration":
        cfg = {**cfg, "hc_sinkhorn_iters": 1}
    else:
        plain = reference.connection_maps
        monkeypatch.setattr(reference, "connection_maps", lambda *a: (
            lambda pre, post, res: (pre, post, jnp.swapaxes(res, -1, -2)))(*plain(*a)))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference.hyper_connected(p, streams, lambda v: v, cfg))
    rel = np.linalg.norm((got - want).reshape(len(x), -1), axis=-1) / np.linalg.norm(
        want.reshape(len(x), -1), axis=-1)
    assert np.median(rel) > 0.02, rel


def test_the_reference_imports_nothing_of_the_programs():
    import ast

    path = os.path.join(cells.BENCH_DIR, "reference", "xing4_decoder.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    modules = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    modules += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert not [m for m in modules if "ray_tpu" in m]
    assert set(modules) <= {"__future__", "jax", "jax.numpy", "common",
                            "kimi_linear_decoder", "sarvam_mla_decoder"}


def test_the_readings_tool_runs_at_the_rehearsal_size(tmp_path, monkeypatch):
    """benchmarks/tools/reference_readings_of.py walks wrong_xing4.py's
    programs and patched references on the CPU; at the tiny widths only the
    order of the readings is held: every program of another function is
    further from the reference than the system is."""
    import sys

    monkeypatch.setattr(sys, "path", list(sys.path))  # the tool prepends the repository
    from benchmarks.tools import reference_readings_of

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(sys, "argv", [
        "reference_readings_of.py", "--workload", CELL, "--wrong",
        "benchmarks.tools.wrong_xing4", "--seeds", "4000000001", "--rehearse",
        "--out", str(tmp_path)])
    reference_readings_of.main()
    (line,) = (tmp_path / f"{CELL}.jsonl").read_text().splitlines()
    line = json.loads(line)
    assert line["seed"] == 4000000001 and line["positions"] == 64
    system = line["system"]["median"]
    for wrong in ("system_one_sinkhorn_iteration", "system_no_scaling", "system_no_mscale",
                  "reference_rows_only", "reference_post_without_2",
                  "reference_constant_maps", "reference_no_q_latent_norm", "reference_e4m3"):
        assert line[wrong]["median"] > 1.3 * system, wrong


def test_the_cell_rehearses_through_the_normal_path():
    """``run.py --rehearse``: init -> JaxTrainer -> make_train_step at the
    files' rehearsal sizes on the CPU, the kernels interpreted."""
    import subprocess
    import sys

    env = {**os.environ, "RAY_TPU_NUM_CHIPS": "1", "JAX_PLATFORMS": "cpu",
           "RAY_TPU_PALLAS_INTERPRET": "1"}
    env.pop("XLA_FLAGS", None)  # the tests' eight virtual devices: one chip here
    done = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"), "--workload", CELL,
         "--rehearse", "--seconds", "2", "--seed", "3000000019"],
        env=env, capture_output=True, text=True, timeout=900, cwd=cells.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["failed"] == 0 and line["metrics"] == {}
    checks = next(l for l in done.stdout.splitlines() if "checks:" in l)
    for name in ("reference_agrees", "losses_finite", "loss_fell",
                 "nothing_compiled_in_window", "moe_dispatch", "mesh", "device_count"):
        assert f'"{name}": true' in checks, checks
