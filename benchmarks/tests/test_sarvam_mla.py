"""sarvam-105b's cell by hand: the reference against a naive per-position
formulation, its required FLOPs, the kernels its step is held to, the
configuration against the catalog's published keys, the cell judged by new
files alone, and the new reader on a made-up trace."""
import json
import math
import os

import numpy as np
import pytest

from benchmarks.lib import cells, result
from benchmarks.lib.flops import flash_call
from benchmarks.lib.flops_gmm import gmm_call
from benchmarks.lib.flops_sarvam import (
    attention_per_token, expert_layer_matmul_params, mla_matmul_params,
    sarvam_mla_decoder,
)
from benchmarks.lib.trace import Event, Trace
from benchmarks.tests.test_harness import made_up_run

CELL = "sarvam-105b-l5.pretrain-4k"
PUBLISHED = {  # the catalog row's config, every key
    "attn_implementation": None, "default_theta": 10000,
    "first_k_dense_replace": 1, "head_dim": 576, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "sarvam_mla",
    "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_shared_experts": 1, "q_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "deepseek_yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "tie_word_embeddings": False, "use_qk_norm": True, "v_head_dim": 128,
    "vocab_size": 262144,
}
READERS = os.path.join(cells.BENCH_DIR, "layer_metrics")


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def test_the_file_holds_every_published_key_and_lists_exactly_what_it_cut(cell):
    cfg = cell["config"]
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(cfg["reduced"]) == differs
    entry = next(c for c in cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))["configs"]
                 if c["name"] == "sarvam-105b-l5")
    assert set(entry["reduced"]) == differs and entry["source"] == cfg["source"]
    for key in differs:
        assert cfg["reduced"][key]["source"] == PUBLISHED[key]
        assert cfg["reduced"][key]["here"] == cfg[key]
        assert cfg[key + "_published"] == PUBLISHED[key]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 8, 32768)
    # the floors: four layers after the leading dense one, 8 experts, an eighth
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "16 chips" in cfg["deployment"] and "over 8" in cfg["deployment"]
    assert {"use_qk_norm_place", "router_score", "norm_topk_prob", "initializer_range",
            "rotary_pairing", "router_dtype"} <= set(cfg["assumed"])
    assert len(cfg["departures"]) == 3 and "router_learns" not in cfg


def test_the_traffic_is_the_issues(cell):
    t = cell["traffic"]
    assert (t["batch"], t["seq"], t["batches"], t["mesh"], t["trace_steps"],
            t["compare_last"]) == (1, 4096, 16, {}, 3, 256)
    assert t["tokens"] == {"distribution": "zipf", "exponent": 1.0}
    assert t["loss"] == {"fn": "ray_tpu.models.llama:chunked_causal_lm_loss",
                         "takes": "model", "args": {"chunk_size": 2048}}
    assert t["expect"] == {"moe_dispatch": "gmm"}
    assert t["loop"] == "benchmarks.loops.train_lm:train_loop"
    assert t["optimizer"] == cells.load_cell("mistral-7b-l4.long16k")["traffic"]["optimizer"]


def test_required_flops_match_the_hand_count(cell):
    cfg = cell["config"]
    assert cells.resolve(cfg["required_flops"]) is sarvam_mla_decoder
    # MLA: q 4096 x 64 x 192; down 4096 x 576; up 512 x 64 x 256; o 64 x 128 x 4096
    mla = 4096 * 64 * 192 + 4096 * 576 + 512 * 64 * 256 + 64 * 128 * 4096
    assert mla_matmul_params(cfg) == mla == 94_633_984
    # an expert layer: the router at 128, the shared expert, and 8 x 8 / 128 =
    # half a routed expert a token here
    expert = 3 * 4096 * 2048
    moe = 4096 * 128 + expert + 0.5 * expert
    assert expert_layer_matmul_params(cfg) == moe
    dense, head = 3 * 4096 * 16384, 4096 * 32768
    params = 5 * mla + dense + 4 * moe + head
    assert params / 1e6 == pytest.approx(961.8, abs=0.05)
    attention = 3 * 5 * 4096 * 64 * (192 + 128)  # five layers, the causal half
    assert attention_per_token(cfg, 4096) == attention
    assert sarvam_mla_decoder(cfg, 4096) == 6 * params + attention
    assert sarvam_mla_decoder(cfg, 4096) / 1e9 == pytest.approx(7.03, abs=5e-3)
    assert 6 * params / 1e9 == pytest.approx(5.77, abs=5e-3)
    assert attention / 1e9 == pytest.approx(1.26, abs=5e-3)
    # what the cell's `why` says: the five mixers are 58%
    mixers = 6 * 5 * mla + attention
    assert mixers / 1e9 == pytest.approx(4.10, abs=5e-3)
    assert mixers / sarvam_mla_decoder(cfg, 4096) == pytest.approx(0.58, abs=5e-3)
    # attention grows with the sequence, the matmuls do not
    assert sarvam_mla_decoder(cfg, 8192) == 6 * params + 2 * attention
    # and the parameters held: 1,851 M, 10.34 GiB at 6 bytes
    held = (2 * head + 5 * mla + dense + 4 * (4096 * 128 + 9 * expert))
    assert held / 1e6 == pytest.approx(1851, abs=1)
    assert held * 6 / 2**30 == pytest.approx(10.34, abs=0.01)


def test_the_cell_states_its_kernels_counts_and_one_calls_need(cell):
    from benchmarks.lib.kernels_sarvam import sarvam_mla_decoder as kernels

    assert cells.resolve(cell["config"]["kernels"]) is kernels
    stated = cells.stated_kernels(cell)
    # five MLA layers, four expert layers
    assert {k: s["least"] for k, s in stated.items()} == {
        "_fwd_kernel": 5, "_bwd_dkv_kernel": 5, "_bwd_dq_kernel": 5,
        "_gmm_kernel": 24, "_tgmm_kernel": 12}
    for kernel in ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel"):
        assert stated[kernel]["call"] == flash_call(
            kernel, 64, 4096, 4096, 192, causal=True, d_v=128)
    # 64 heads' causal halves of 4096 x 4096, scores at 192 and values at 128
    assert stated["_fwd_kernel"]["call"][0] == 2 * 64 * 4096 * 4096 // 2 * (192 + 128)
    # 4,096 tokens x top-8 = 32,768 pairs, a sixteenth of them here in
    # expectation; the static layout bounds at all of them
    for kernel in ("_gmm_kernel", "_tgmm_kernel"):
        assert stated[kernel]["call"] == gmm_call(kernel, 2048, 4096, 2048, 8)
    assert stated["_gmm_kernel"]["call"] == (
        2 * 2048 * 4096 * 2048, 2 * (2048 * (4096 + 2048) + 8 * 4096 * 2048))


def test_the_cell_is_judged_by_its_own_files(cell):
    names = {m["name"] for m in cell["per_layer"]}
    assert {"model.mla_share", "model.mla_rotary_share", "model.moe_share",
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
    assert (cfg.num_experts, cfg.experts_held, cfg.vocab_size, cfg.num_layers) == (
        128, (0, 8), 32768, 5)
    assert cfg.rope_scaling.factor == 40 and cfg.qk_head_norm and cfg.mla_rope
    # the new metric exists in no other cell
    for other in ("kimi-linear-48b-a3b-l5.longctx-16k", "olmoe-1b-7b-1chip.dropless-4k"):
        assert "model.mla_rotary_share" not in {
            m["name"] for m in cells.load_cell(other)["per_layer"]}


def made_up_trace(cell, slow=2.0):
    """Two steps in which every stated kernel call takes ``slow`` times its
    floor under the scopes the program gives them, and 1 ms each of a q
    projection, the latent's up-projection, the rotation's concatenate (forward
    and backward), the QK norm, a shared expert's matmul and the head."""
    stated = cells.stated_kernels(cell)
    device, host, at = [], [], 0.0
    seconds = {}
    for step in range(2):
        start = at
        for kernel, s in stated.items():
            flops, nbytes = s["call"]
            dur = slow * max(flops / 197e12, nbytes / 819e9)
            under = "layers_1/moe/experts" if "gmm" in kernel else "layers_1/mla"
            for i in range(s["least"]):
                device.append(Event(
                    f"{kernel}.{step}.{i}", at, dur,
                    f"jit(train_step)/jvp(M)/{under}/x kernel_name={kernel}"))
                at += dur
                seconds[kernel] = seconds.get(kernel, 0.0) + dur
        for path in ("jvp(M)/layers_0/mla/q_proj/dot_general",
                     "jvp(M)/layers_3/mla/latent/kv_b_proj/dot_general",
                     "jvp(M)/layers_3/mla/rope/concatenate",
                     "transpose(jvp(M))/layers_3/mla/rope/mul",
                     "jvp(M)/layers_3/mla/qk_norm/q_norm/mul",
                     "jvp(M)/layers_1/moe/shared/shared/up_proj/dot_general",
                     "jvp(M)/lm_head/dot_general"):
            device.append(Event(f"fusion.{step}.{path}", at, 1e-3,
                                f"jit(train_step)/{path}"))
            at += 1e-3
        host.append(Event("bench.step", start, at - start))
    return Trace({0: device}, {0: []}, host), seconds, at


def test_the_new_reader_and_the_old_ones_on_a_made_up_trace(cell):
    trace, seconds, busy = made_up_trace(cell)
    run = made_up_run(cell, trace=True)
    run["trace_data"], run["notes"] = trace, []
    wanted = [m for m in cell["per_layer"] if m["name"] in (
        "model.mla_share", "model.mla_rotary_share", "kernel.flash_share",
        "kernel.flash_roofline", "kernel.gmm_share", "model.moe_share")]
    assert len(wanted) == 6
    metrics = cells.read_metrics(wanted, READERS, run)
    flash = sum(seconds[k] for k in ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel"))
    gmm = seconds["_gmm_kernel"] + seconds["_tgmm_kernel"]
    # three of the seven 1 ms operations a step are the rotation's and the norm's
    assert metrics["model.mla_rotary_share"]["value"] == pytest.approx(100 * 6e-3 / busy)
    assert metrics["model.mla_share"]["value"] == pytest.approx(
        100 * (flash + 10e-3) / busy)
    assert metrics["kernel.flash_share"]["value"] == pytest.approx(100 * flash / busy)
    assert metrics["kernel.flash_roofline"]["value"] == pytest.approx(50.0)
    assert "30 calls, 30 of them bound by compute" in run["notes"][0]
    assert metrics["kernel.gmm_share"]["value"] == pytest.approx(100 * gmm / busy)
    assert metrics["model.moe_share"]["value"] == pytest.approx(100 * (gmm + 2e-3) / busy)
    # a roofline share over 100% would mean the count is too high
    fast, _, _ = made_up_trace(cell, slow=1.0)
    run["trace_data"], run["notes"] = fast, []
    assert cells.read_metrics(wanted, READERS, run)[
        "kernel.flash_roofline"]["value"] == pytest.approx(100.0)


def test_the_new_reader_finds_nothing_in_a_program_without_the_scopes(cell):
    """Kimi-Linear's MLA layers (and the parent's whole program) have no
    /mla/rope/ or /mla/qk_norm/ operation, and the dense models' /attn/qk_norm/
    is another thing: the reader returns None and raises nothing."""
    device = [Event("fusion.1", 0.0, 1e-3, "jit(train_step)/jvp(M)/layers_3/mla/latent/kv_b_proj/dot"),
              Event("fusion.2", 1e-3, 1e-3, "jit(train_step)/jvp(M)/layers_0/attn/qk_norm/q_norm/mul"),
              Event("flash.1", 2e-3, 1e-3, "jit(train_step)/layers_3/mla/x kernel_name=_fwd_kernel")]
    run = made_up_run(cell, trace=True)
    run["trace_data"] = Trace({0: device}, {0: []}, [Event("bench.step", 0.0, 3e-3)])
    run["notes"] = []
    wanted = [m for m in cell["per_layer"] if m["name"] == "model.mla_rotary_share"]
    assert cells.read_metrics(wanted, READERS, run) == {}
    run["trace_data"] = None  # an untraced run
    assert cells.read_metrics(wanted, READERS, run) == {}


def test_benchmark_json_gained_one_configuration_one_cell_and_one_metric():
    bench = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    assert bench["configs"][-1]["name"] == "sarvam-105b-l5" and len(bench["configs"]) == 5
    assert bench["workloads"][-1]["name"] == CELL and bench["workloads"][-1]["chips"] == 1
    assert len(bench["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["workloads"][-1]["why"]) <= 200
    last = bench["per_layer"][-1]
    assert last == {"name": "model.mla_rotary_share", "unit": "%", "better": "lower",
                    "source": "device_trace", "layer": "models",
                    "moves": "tokens_per_s_per_chip", "workloads": [CELL]}
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == {"model.moe_share", "model.moe_expert_share",
                      "model.moe_dispatch_share", "kernel.gmm_share",
                      "model.mla_share", "trainer.step_ms_p95_over_p50",
                      "model.mla_rotary_share"}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []) and m["name"] != "model.mla_rotary_share":
            assert m["workloads"][-1] == CELL  # appended, nothing else changed
    assert len(json.dumps(bench)) < 64 * 1024


# ------------------------- the reference against a naive second formulation


def naive_mixer(p, x, cfg, rows=None):
    """The mixer one query position (of ``rows``, or every one) and one head
    at a time, in numpy float64: complex multiplication for the rotation, a
    Python loop over the visible keys for the softmax."""
    from benchmarks.reference.sarvam_mla_decoder import yarn_inv_freq

    x = np.asarray(x, np.float64)
    w = {k: np.asarray(v["kernel"] if "kernel" in v else v["scale"], np.float64)
         for k, v in p.items()}
    t_len, heads = x.shape[0], cfg["num_attention_heads"]
    rank, nope, pe = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    eps = cfg["rms_norm_eps"]
    s = cfg["rope_scaling"]
    mscale = 0.1 * s["mscale_all_dim"] * math.log(s["factor"]) + 1
    scale = (nope + pe) ** -0.5 * mscale * mscale
    inv_freq = yarn_inv_freq(pe, cfg["rope_theta"], s)

    def norm(v, weight):
        return v / math.sqrt((v * v).mean() + eps) * weight

    def turn(v, t):  # pairs (i, i + pe / 2) as complex numbers
        z = (v[: pe // 2] + 1j * v[pe // 2:]) * np.exp(1j * t * inv_freq)
        return np.concatenate([z.real, z.imag])

    q_all, k_all, v_all = [], [], []
    for t in range(t_len):
        latent = x[t] @ w["kv_a_proj"]
        c = norm(latent[:rank], w["kv_a_norm"])
        qs, ks, vs = [], [], []
        for n in range(heads):
            q = norm(x[t] @ w["q_proj"][:, n], w["q_norm"])
            kv = c @ w["kv_b_proj"][:, n]
            k = norm(np.concatenate([kv[:nope], latent[rank:]]), w["k_norm"])
            qs.append(np.concatenate([q[:nope], turn(q[nope:], t)]))
            ks.append(np.concatenate([k[:nope], turn(k[nope:], t)]))
            vs.append(kv[nope:])
        q_all.append(qs), k_all.append(ks), v_all.append(vs)
    out = np.zeros((t_len, x.shape[1]))
    for t in range(t_len) if rows is None else rows:
        for n in range(heads):
            scores = np.array([q_all[t][n] @ k_all[j][n] * scale for j in range(t + 1)])
            weights = np.exp(scores - scores.max())
            weights /= weights.sum()
            o = sum(weights[j] * v_all[j][n] for j in range(t + 1))
            out[t] += o @ w["o_proj"][n]
    return out


@pytest.fixture(scope="module")
def tiny():
    """The reference's keys at a tiny size with the published scaling, one
    layer's mixer parameters drawn at random (norm weights away from one), and
    tokens."""
    import jax.numpy as jnp

    cfg = {**PUBLISHED, "hidden_size": 24, "num_attention_heads": 3,
           "kv_lora_rank": 8, "qk_nope_head_dim": 6, "qk_rope_head_dim": 8,
           "v_head_dim": 4}
    rng = np.random.default_rng(7)
    shapes = {"q_proj": (24, 3, 14), "kv_a_proj": (24, 16), "kv_b_proj": (8, 3, 10),
              "o_proj": (3, 4, 24)}
    p = {k: {"kernel": jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)}
         for k, s in shapes.items()}
    for name, dim in (("kv_a_norm", 8), ("q_norm", 14), ("k_norm", 14)):
        p[name] = {"scale": jnp.asarray(rng.uniform(0.5, 1.5, dim), jnp.float32)}
    # positions up to 511: the slow pairs turn visibly too
    x = jnp.asarray(rng.normal(size=(512, 24)), jnp.float32)
    return cfg, p, x


def test_the_references_mixer_is_the_naive_per_position_one(tiny):
    import jax

    from benchmarks.reference import sarvam_mla_decoder as reference

    cfg, p, x = tiny
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference.mla(p, x, cfg))
    rows = [0, 1, 2, 17, 255, 256, 300, 511]  # both sides of a block's edge
    want = naive_mixer(p, np.asarray(x), cfg, rows)
    np.testing.assert_allclose(got[rows], want[rows], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("wrong", ["no-mscale", "unrotated-key", "no-qk-norm",
                                   "norm-after-rotation", "interleaved-pairs"])
def test_the_naive_one_tells_another_function_from_the_reference(tiny, wrong, monkeypatch):
    """What the comparison has to be able to see, on the reference's side: a
    softmax scale without mscale squared, an unrotated key part, a left-out QK
    norm, the norm on the other side of the rotation (with weights away from
    one), and another pairing of the rotated channels."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import sarvam_mla_decoder as reference

    cfg, p, x = tiny
    x = x[:48]
    want = naive_mixer(p, np.asarray(x), cfg)
    plain = reference.rotate
    if wrong == "no-mscale":
        monkeypatch.setattr(reference, "softmax_scale", lambda c: 14 ** -0.5)
    elif wrong == "unrotated-key":
        calls = []
        monkeypatch.setattr(reference, "rotate", lambda t, c: (
            calls.append(1), plain(t, c) if len(calls) % 2 else t)[1])
    elif wrong == "no-qk-norm":
        cfg = {**cfg, "use_qk_norm": False}
    elif wrong == "norm-after-rotation":
        norm = reference.rms_norm
        cfg = {**cfg, "use_qk_norm": False}
        monkeypatch.setattr(reference, "causal_attention", lambda q, k, v, s, f=reference.causal_attention: f(
            norm(q, p["q_norm"]["scale"], 1e-6), norm(k, p["k_norm"]["scale"], 1e-6), v, s))
    else:
        def interleaved(t, c):
            pe = t.shape[-1]
            order = np.concatenate([np.arange(0, pe, 2), np.arange(1, pe, 2)])
            return plain(t[..., order], c)[..., np.argsort(order)]

        monkeypatch.setattr(reference, "rotate", interleaved)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference.mla(p, jnp.asarray(x), cfg))
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert np.median(rel[1:]) > 0.02, rel


def test_the_references_gates_are_eight_sigmoids_renormalised_times_2_5():
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import sarvam_mla_decoder as reference

    rng = np.random.default_rng(3)
    cfg = {"num_experts_published": 128, "num_experts": 8, "num_experts_per_tok": 8,
           "routed_scaling_factor": 2.5, "num_shared_experts": 1}
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    p = {"router": {"kernel": jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)},
         "router_bias": jnp.asarray(rng.normal(size=128) * 0.3, jnp.float32)}
    gates = np.asarray(reference.router_gates(p, x, cfg))
    scores = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(p["router"]["kernel"], np.float64)))
    for t in range(40):
        chosen = np.argsort(-(scores[t] + np.asarray(p["router_bias"])))[:8]
        want = np.zeros(128)
        want[chosen] = scores[t][chosen] / scores[t][chosen].sum() * 2.5
        np.testing.assert_allclose(gates[t], want, rtol=1e-5, atol=1e-7)
    assert reference.held_experts({**cfg, "expert_rank": 3}) == (24, 32)


def test_the_reference_imports_nothing_of_the_programs():
    import ast

    path = os.path.join(cells.BENCH_DIR, "reference", "sarvam_mla_decoder.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    modules = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    modules += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert not [m for m in modules if "ray_tpu" in m]
    assert set(modules) <= {"__future__", "math", "jax", "jax.numpy", "numpy", "common",
                            "kimi_linear_decoder"}


def test_the_readings_tool_runs_at_the_rehearsal_size(tmp_path, monkeypatch):
    """benchmarks/tools/reference_readings_of.py walks wrong_sarvam.py's
    programs and patched references on the CPU; at the tiny widths only the order of
    the readings is held: every program of another function is further from
    the reference than the system is."""
    import sys

    from benchmarks.tools import reference_readings_of

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(sys, "argv", [
        "reference_readings_of.py", "--workload", CELL, "--wrong",
        "benchmarks.tools.wrong_sarvam", "--seeds", "4000000001", "--rehearse",
        "--out", str(tmp_path)])
    reference_readings_of.main()
    (line,) = (tmp_path / f"{CELL}.jsonl").read_text().splitlines()
    line = json.loads(line)
    assert line["seed"] == 4000000001 and line["positions"] == 64
    system = line["system"]["median"]
    for wrong in ("system_no_mscale", "system_no_qk_norm", "system_no_rotation",
                  "system_no_shared_expert", "system_no_scaling", "reference_e4m3",
                  "reference_unrotated_key"):
        assert line[wrong]["median"] > 1.3 * system, wrong
    assert line["reference_router_bf16"]["max"] > 1e-3


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
         "--rehearse", "--seconds", "2", "--seed", "3000000019"],
        env=env, capture_output=True, text=True, timeout=600, cwd=cells.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["failed"] == 0 and line["metrics"] == {}
    checks = next(l for l in done.stdout.splitlines() if "checks:" in l)
    for name in ("losses_finite", "loss_fell", "nothing_compiled_in_window",
                 "moe_dispatch", "mesh", "device_count"):
        assert f'"{name}": true' in checks, checks
