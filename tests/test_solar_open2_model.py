"""Solar-Open2's architecture through the program's models, on the CPU.

``SolarOpen2ForCausalLM`` (one period: a softmax layer of grouped-query
attention without rotation under an element-wise output gate, then three KDA
layers that write with beta in (0, 2), each over an expert layer with
sigmoid routing, a shared expert and one expert-parallel rank's share of the
routed experts through the ``gmm`` dispatch, all kernels interpreted) against
the benchmark's plain reference
(``benchmarks/reference/solar_open2_decoder.py``) at the configuration file's
rehearsal size on seeded random weights in float32: logits, loss and every
gradient. The programs and references of ``benchmarks/tools/wrong_solar.py``,
each another function; the five ranks' shares of one expert layer against the
uncut reference; the configuration file against the catalog's row; and the
models that share ``Attention`` and ``KDAMixer``, whose parameter trees and
lowered forward passes are what they were.

This file holds the model's logits against its reference, the wrong programs
and references, and the configuration file's cases. The loss and gradients
(``tests/test_solar_open2_gradients.py``) and the ranks' shares and sibling
models (``test_solar_open2_layers.py``) are beside it, over
``tests/solar_open2_cases.py``.
"""
import dataclasses
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import solar_open2_decoder as reference
from benchmarks.tools import wrong_solar
from ray_tpu.models.solar_open2 import SolarOpen2ForCausalLM
from ray_tpu.util import tracing

from solar_open2_cases import (  # noqa: F401 - fixtures
    CONFIG, SEQ, interpret, solar,
)


# Past these a float32 program is another function than the reference.
FAR = {"per_position_rel_err": 1e-3, "min_share_within": 0.5}


@pytest.fixture(scope="module")
def expected(solar):
    config, _, params, ids = solar
    return reference.forward(params, ids, config, SEQ)


def test_the_configuration_builds_solar_open2s_program(solar):
    config, model, params, _ = solar
    cfg = model.cfg
    # the full layer first in a period: the source counts gqa_layers from 0
    assert cfg.layers == ((tracing.ATTN, "moe"),) + ((tracing.KDA, "moe"),) * 3
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.num_shared_experts, cfg.moe_dispatch, cfg.held_rows) == (
        "sigmoid", True, 1, 1, "gmm", "gather")
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (20, (0, 4), 4)
    assert (cfg.use_rope, cfg.use_gqa_gate, cfg.kda_allow_neg_eigval) == (False, True, True)
    p = params["params"]
    assert set(p["layers_0"]) == {"input_norm", "attn", "post_attn_norm", "moe"}
    assert set(p["layers_1"]) == {"input_norm", "kda", "post_attn_norm", "moe"}
    attn = p["layers_0"]["attn"]
    assert set(attn) == {"q_proj", "k_proj", "v_proj", "g_proj", "o_proj"}
    assert attn["q_proj"]["kernel"].shape == (128, 4, 32)
    assert attn["k_proj"]["kernel"].shape == (128, 2, 32)
    assert attn["g_proj"]["kernel"].shape == (128, 4, 32)  # a gate of q's width
    assert set(p["layers_1"]["kda"]) == {
        "q_proj", "k_proj", "v_proj", "q_conv", "k_conv", "v_conv", "f_a_proj",
        "f_b_proj", "b_proj", "A_log", "dt_bias", "g_a_proj", "g_b_proj",
        "o_norm", "o_proj"}
    assert p["layers_1"]["moe"]["router"]["kernel"].shape == (128, 20)
    assert p["layers_1"]["moe"]["w_gate"].shape == (4, 128, 64)  # the experts held
    # and at the published sizes the file gives the published architecture
    full = cells.program_config(cells.load_json(CONFIG))
    assert full.layers == cfg.layers
    assert (full.hidden_size, full.expert_width, full.num_heads, full.num_kv_heads,
            full.head_dim_, full.kda_num_heads, full.kda_head_dim,
            full.short_conv_kernel_size, full.rms_eps, full.vocab_size,
            full.num_layers, full.tie_embeddings) == (
        4096, 1280, 64, 8, 128, 64, 128, 4, 1e-5, 24576, 4, False)
    assert (full.num_experts, full.experts_held, full.num_experts_per_tok,
            full.num_shared_experts, full.routed_scaling_factor) == (
        320, (0, 8), 8, 1, 1)
    kind = full.attention(tracing.ATTN)
    assert (kind.num_heads, kind.freqs, kind.window, kind.gate, kind.gate_channels) == (
        64, None, None, True, True)
    rotated = dataclasses.replace(full, use_rope=True).attention(tracing.ATTN)
    assert rotated.freqs.shape == (64,)


@pytest.mark.parametrize("published", [False, True], ids=["tiny", "published"])
def test_num_params_counts_layer_by_layer(solar, published):
    model = solar[1]
    if published:
        model = SolarOpen2ForCausalLM(cells.program_config(cells.load_json(CONFIG)))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    held = sum(math.prod(leaf.shape) for leaf in jax.tree_util.tree_leaves(shapes))
    assert model.cfg.num_params() == held
    if published:  # the issue's 1,295 M: 9.65 GiB at 8 bytes a parameter
        assert 1.2950e9 < held < 1.2953e9
        p = shapes["params"]
        count = lambda tree: sum(  # noqa: E731
            math.prod(leaf.shape) for leaf in jax.tree_util.tree_leaves(tree))
        assert round(count(p["layers_1"]["kda"]) / 1e5) == 1377  # 137.7 M
        assert round(count(p["layers_0"]["attn"]) / 1e5) == 1091  # 109.1 M


@pytest.mark.parametrize("change,message", [
    ({"kda_use_full_proj": True}, "low-rank"),
    ({"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                             "num_heads": 64, "num_kv_heads": 8}}, "head count"),
], ids=["full-rank gate maps", "fewer value heads"])
def test_what_the_mixer_cannot_build_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        cells.program_config({**cells.load_json(CONFIG), **change})


# -------------------------------------------- the model against the reference


def test_logits_agree_with_the_reference_in_float32(solar, expected):
    _, model, params, ids = solar
    system = jax.jit(model.apply)(params, ids[None])[0]
    assert system.dtype == jnp.float32
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 2e-5, "min_share_within": 1.0})
    assert result["ok"], result


def test_beta_runs_over_zero_to_two(solar):
    """The fixture's b_proj reaches both ends: the comparisons above and
    below are of a model that writes with beta > 1.9 and < 0.1 somewhere."""
    config, _, params, ids = solar
    p = params["params"]
    x = p["embed_tokens"]["embedding"][ids]
    h = reference.rms_norm(x, p["layers_1"]["input_norm"]["scale"], 1e-5)
    beta = np.asarray(reference.write_strength(p["layers_1"]["kda"], h, config))
    assert beta.max() > 1.9 and beta.min() < 0.1 and (beta > 1).mean() > 0.25


def without(params, names):
    """The tree less the mixers' parameters ``names``."""
    return {"params": {
        layer: {k: {n: w for n, w in v.items() if n not in names}
                if k in tracing.MIXERS else v for k, v in sub.items()}
        if layer.startswith("layers_") else sub
        for layer, sub in params["params"].items()}}


@pytest.mark.parametrize("name", [
    "system_beta_undoubled", "system_no_gate", "system_rotated"])
def test_a_wrong_program_is_far_from_the_reference(solar, expected, name):
    _, model, params, ids = solar
    cfg, *drop = wrong_solar.programs(model.cfg)[name]
    if drop:
        params = without(params, drop[0])
    system = jax.jit(SolarOpen2ForCausalLM(cfg).apply)(params, ids[None])[0]
    result = logits_agreement(system, expected, FAR)
    assert not result["ok"], result


def bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def beta_capped(plain):
    return lambda p, x, c: jnp.minimum(plain(p, x, c), 1.0)


@pytest.mark.parametrize("function,replacement", [
    *wrong_solar.references(bf16).values(),
    ("write_strength", beta_capped),
], ids=[*wrong_solar.references(bf16), "reference_beta_capped_at_one"])
def test_a_wrong_reference_is_far_from_the_program(
        solar, expected, monkeypatch, function, replacement):
    """One gate value a head, a router over the held experts alone, a
    bfloat16 state, beta capped at 1: each moves the logits past what float32
    leaves between the program and the reference."""
    config, _, params, ids = solar
    monkeypatch.setattr(
        reference, function, replacement(getattr(reference, function)))
    other = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(other, expected, FAR)
    assert not result["ok"], result


# --------------------------------------------------- the configuration file


def test_the_file_holds_every_published_key_and_lists_exactly_what_it_cut():
    """Against the catalog's row where the catalog is installed, else the
    sizes the issue names."""
    cfg = cells.load_json(CONFIG)
    bench = cells.load_json(f"{cells.ROOT}/BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    cut = {"num_hidden_layers": (48, 4), "n_routed_experts": (320, 8),
           "vocab_size": (196608, 24576)}
    assert set(cfg["reduced"]) == set(entry["reduced"]) == set(cut)
    assert entry["source"] == cfg["source"] and len(cfg["source"]) < 200
    for key, (source, here) in cut.items():
        assert (cfg["reduced"][key]["source"], cfg["reduced"][key]["here"],
                cfg[key], cfg[key + "_published"]) == (source, here, here, source)
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["routed_scaling_factor"], cfg["rms_norm_eps"]) == (
        4096, 128, 64, 8, 1280, 8, 1, 1, 1e-5)
    assert cfg["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert cfg["gqa_layers"] == list(range(0, 48, 4))
    for key in ("gqa_gate_width", "router_score", "selection_bias_and_groups",
                "gate_low_rank_width", "kda_initialisers", "initializer_range",
                "router_dtype", "intermediate_size", "hidden_act"):
        assert key in cfg["assumed"], key
    gate = cfg["assumed"]["gqa_gate_width"]  # the count both ways
    assert "250.29 B" in gate and "14.74 B" in gate and "249.89 B" in gate
    assert "over 40 chips" in cfg["deployment"] and "Layers 0-3" in cfg["deployment"]
    assert cfg["program"]["set"] == {
        "param_dtype": "bfloat16", "dtype": "bfloat16", "remat": True,
        "remat_policy": "nothing", "moe_dispatch": "gmm",
        "router_score": "sigmoid", "held_rows": "gather"}
    cell = next(w for w in bench["workloads"] if w["config"] == cfg["name"])
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b-l4.pretrain-4k", "pretrain-4k", 1)
    assert sum(w["config"] == cfg["name"] for w in bench["workloads"]) == 1
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [cells.json.loads(line) for line in open(catalog)]
    except OSError:
        return
    published = next(r for r in rows if r["name"] == "Solar-Open2-250B")
    assert published["source_url"] == cfg["source"]
    differs = {k for k, v in published["config"].items() if cfg.get(k, "absent") != v}
    assert differs == set(cut)


def test_the_cells_flops_and_kernels_follow_the_layers():
    cell = cells.load_cell("solar-open2-250b-l4.pretrain-4k")
    flops = importlib.import_module("benchmarks.lib.flops_solar")
    config, seq = cell["config"], 4096
    assert flops.layer_kinds(config) == [("attn", "moe")] + [("kda", "moe")] * 3
    per_token = flops.solar_open2_decoder(config, seq)
    assert 18.3e12 < per_token * seq < 18.5e12  # 18.4 TFLOP a step
    # by hand: matmul parameters a token passes through, attention, the scan
    h, d, w = 4096, 128, 1280
    kda = 4 * h * 64 * d + 2 * (h * d + d * 64 * d) + h * 64
    gqa = 3 * h * 64 * d + 2 * h * 8 * d
    sparse = h * 320 + 3 * h * w + 8 * 8 / 320 * 3 * h * w
    params = 3 * kda + gqa + 4 * sparse + h * 24576
    assert per_token == pytest.approx(
        6 * params + 6.0 * seq * 64 * d + 3 * 64 * 21 * d * d, rel=1e-12)
    assert 0.55 < (18 * kda + 3 * 64 * 21 * d * d) / per_token < 0.58  # KDA's share
    stated = cells.stated_kernels(cell)
    assert {k: s["least"] for k, s in stated.items()} == {
        "_fwd_kernel": 1, "_bwd_dkv_kernel": 1, "_bwd_dq_kernel": 1,
        "_kda_fwd_kernel": 3, "_kda_bwd_kernel": 3,
        "_gmm_kernel": 24, "_tgmm_kernel": 12}
    # a causal call at 64 q heads (K and V repeated to them), a scan at 64
    assert stated["_fwd_kernel"]["call"][0] == 2.0 * 64 * seq * seq / 2 * 2 * d
    kda_flops = importlib.import_module("benchmarks.lib.flops_kda")
    assert stated["_kda_fwd_kernel"]["call"] == kda_flops.kda_call(
        "_kda_fwd_kernel", 64, seq, d, d)
    # the cell reports the mixers' readers and they find nothing in an empty run
    names = {m["name"] for m in cell["per_layer"]}
    assert {"model.gqa_share", "model.attn_gate_share", "model.kda_share",
            "kernel.kda_share", "kernel.kda_roofline", "kernel.flash_roofline",
            "model.moe_share", "kernel.gmm_share",
            "trainer.step_ms_p95_over_p50"} <= names
    assert not {"kernel.gmm_roofline", "model.mla_share", "model.swa_share"} & names
    for name in ("model.gqa_share", "model.attn_gate_share"):
        reader = cells.load_reader(f"{cells.BENCH_DIR}/layer_metrics", name)
        assert reader.read({"trace_data": None}) is None
