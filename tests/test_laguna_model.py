"""Laguna's architecture through the program's models, on the CPU.

``LagunaForCausalLM`` (two periods of one full-attention layer to three
sliding-window layers, each kind with its own head count and rotation, a
gate a head on the attention's output, a leading dense FFN and expert layers
with sigmoid routing, a shared expert and one expert-parallel rank's share of
the routed experts through the ``gmm`` dispatch, all kernels interpreted)
against the benchmark's plain reference
(``benchmarks/reference/laguna_decoder.py``) at a tiny size on seeded random
weights in float32: logits, loss and gradients. The YaRN table against its
closed form at the published numbers, what a full layer's rotation turns,
the four ranks' shares of one expert layer against the uncut reference, the
programs and references of ``benchmarks/tools/wrong_laguna.py`` and three
more made wrong, and the configuration file against the catalog's row.

This file holds the model's logits against its reference, the wrong programs
and references, and the configuration file's cases. The loss and gradients and
the ranks' shares are in ``tests/test_laguna_gradients.py`` beside it, over
``tests/laguna_cases.py``.
"""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import laguna_decoder as reference
from benchmarks.tools import wrong_laguna
from ray_tpu.models.laguna import LagunaForCausalLM, LayerAttention
from ray_tpu.models.llama import _rope
from ray_tpu.util import tracing

from laguna_cases import (  # noqa: F401 - fixtures
    CONFIG, SEQ, interpret, laguna,
)


# Past these a float32 program is another function than the reference.
FAR = {"per_position_rel_err": 1e-3, "min_share_within": 0.5}


@pytest.fixture(scope="module")
def expected(laguna):
    config, _, params, ids = laguna
    return reference.forward(params, ids, config, SEQ)


def test_the_configuration_builds_lagunas_program(laguna):
    config, model, params, _ = laguna
    cfg = model.cfg
    period = ((tracing.ATTN, "moe"),) + ((tracing.SWA, "moe"),) * 3
    assert cfg.layers == ((tracing.ATTN, "mlp"),) + period[1:] + period
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.num_shared_experts, cfg.moe_dispatch, cfg.gating) == (
        "sigmoid", True, 2.5, 1, "gmm", True)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (16, (0, 4), 2)
    p = params["params"]
    assert set(p["layers_0"]) == {"input_norm", "attn", "post_attn_norm", "mlp"}
    assert set(p["layers_1"]) == {"input_norm", "swa", "post_attn_norm", "moe"}
    assert set(p["layers_4"]) == {"input_norm", "attn", "post_attn_norm", "moe"}
    for mixer, heads in ((p["layers_4"]["attn"], 2), (p["layers_5"]["swa"], 4)):
        assert set(mixer) == {"q_proj", "k_proj", "v_proj", "g_proj", "o_proj"}
        assert mixer["q_proj"]["kernel"].shape == (128, heads, 32)
        assert mixer["k_proj"]["kernel"].shape == (128, 2, 32)
        assert mixer["g_proj"]["kernel"].shape == (128, heads)  # a gate a head
        assert mixer["o_proj"]["kernel"].shape == (heads, 32, 128)
    assert p["layers_1"]["moe"]["router"]["kernel"].shape == (128, 16)
    assert p["layers_1"]["moe"]["w_gate"].shape == (4, 128, 64)  # the experts held
    # and at the published sizes the file gives the published architecture
    full = cells.program_config(cells.load_json(CONFIG))
    assert full.layers == cfg.layers
    assert (full.hidden_size, full.intermediate_size, full.expert_width,
            full.num_kv_heads, full.head_dim_, full.rms_eps, full.vocab_size,
            full.num_layers, full.tie_embeddings) == (
        2048, 8192, 512, 8, 128, 1e-6, 12544, 8, False)
    assert (full.num_experts, full.experts_held, full.num_experts_per_tok,
            full.num_shared_experts, full.routed_scaling_factor) == (
        256, (0, 32), 8, 1, 2.5)
    assert dict(full.attentions) == {
        tracing.ATTN: LayerAttention(
            48, 500000, "yarn", 0.5, 64, 4096, 64, 1, 1.4158883083359672, None),
        tracing.SWA: LayerAttention(64, 10000, "default", 1, window=512),
    }
    kind = full.attention(tracing.SWA)
    assert (kind.num_heads, kind.window, kind.gate, kind.rope_amplitude,
            kind.freqs.shape) == (64, 512, True, 1.0, (64,))
    kind = full.attention(tracing.ATTN)
    assert (kind.num_heads, kind.window, kind.gate, kind.freqs.shape) == (
        48, None, True, (32,))


@pytest.mark.parametrize("published", [False, True], ids=["tiny", "published"])
def test_num_params_counts_layer_by_layer(laguna, published):
    """Head counts differ by layer, so the count goes by ``layers``."""
    model = laguna[1]
    if published:
        model = LagunaForCausalLM(cells.program_config(cells.load_json(CONFIG)))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    held = sum(math.prod(leaf.shape) for leaf in jax.tree_util.tree_leaves(shapes))
    assert model.cfg.num_params() == held
    if published:  # 1,118 M: 6.7 GB of weights and moments, 42% of the chip
        assert 1.117e9 < held < 1.119e9


def test_layer_kinds_with_two_head_counts_are_refused():
    config = cells.load_json(CONFIG)
    config = {**config, "num_attention_heads_per_layer": [48, 64, 48, 64, 48, 64, 64, 64]}
    with pytest.raises(ValueError, match="different head counts"):
        cells.program_config(config)


# ------------------------------------------------------------- the rotation


def test_the_yarn_table_is_the_closed_form_at_the_published_numbers():
    """64 turning channels, base 500,000, factor 64, 4,096 positions, 64
    turns and 1."""
    full = cells.program_config(cells.load_json(CONFIG))

    def pair(turns):
        return 64 * math.log(4096 / (2 * math.pi * turns)) / (2 * math.log(500000))

    low, high = math.floor(pair(64)), math.ceil(pair(1))
    assert (low, high) == (5, 16)
    want = []
    for i in range(32):
        f = 500000 ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f / 64 * ramp + f * (1 - ramp))
    kind = full.attention(tracing.ATTN)
    np.testing.assert_allclose(kind.freqs, want, rtol=1e-6)
    assert kind.rope_amplitude == pytest.approx(0.1 * math.log(64) + 1, rel=1e-7)
    rope = cells.load_json(CONFIG)["rope_parameters"]["full_attention"]
    np.testing.assert_allclose(reference.inv_freq(rope, 64), want, rtol=1e-12)
    # the sliding layers' table is the plain one over the whole head
    np.testing.assert_allclose(
        full.attention(tracing.SWA).freqs,
        [10000 ** (-2 * i / 128) for i in range(64)], rtol=1e-6)


def test_the_first_half_of_a_full_layers_head_turns_and_grows():
    kind = cells.program_config(cells.load_json(CONFIG)).attention(tracing.ATTN)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 2, 6, 128)), jnp.float32)
    positions = jnp.arange(6)[None]
    out = _rope(x, positions, kind.freqs, leading=True, amplitude=kind.rope_amplitude)
    np.testing.assert_array_equal(out[..., 64:], x[..., 64:])  # the second half passes
    norm = lambda a: jnp.linalg.norm(a, axis=-1)  # noqa: E731
    np.testing.assert_allclose(  # a rotation, times attention_factor
        norm(out[..., :64]), norm(x[..., :64]) * kind.rope_amplitude, rtol=1e-5)
    # channel i with channel i + 32: position 1 turns pair 0 by one radian
    a, b = x[0, 0, 1, 0], x[0, 0, 1, 32]
    amp = kind.rope_amplitude
    assert float(out[0, 0, 1, 0]) == pytest.approx(
        float(amp * (a * math.cos(1) - b * math.sin(1))), rel=1e-5)
    # and the default leaves today's callers where they were: the last
    # channels, no amplitude
    plain = _rope(x, positions, kind.freqs)
    np.testing.assert_array_equal(plain[..., :64], x[..., :64])


# -------------------------------------------- the model against the reference


def test_logits_agree_with_the_reference_in_float32(laguna, expected):
    _, model, params, ids = laguna
    system = jax.jit(model.apply)(params, ids[None])[0]
    assert system.dtype == jnp.float32
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 2e-5, "min_share_within": 1.0})
    assert result["ok"], result


@pytest.mark.parametrize("name", [
    "system_no_window", "system_no_attention_factor", "system_no_gate"])
def test_a_wrong_program_is_far_from_the_reference(laguna, expected, name):
    _, model, params, ids = laguna
    cfg, *drop = wrong_laguna.programs(model.cfg)[name]
    if drop:
        params = {"params": {
            layer: {k: {n: w for n, w in v.items() if n not in drop[0]}
                    if k in tracing.MIXERS else v for k, v in sub.items()}
            if layer.startswith("layers_") else sub
            for layer, sub in params["params"].items()}}
    system = jax.jit(LagunaForCausalLM(cfg).apply)(params, ids[None])[0]
    result = logits_agreement(system, expected, FAR)
    assert not result["ok"], result


def bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def window_dropped_in_one_layer(plain):
    return lambda cfg, layer: None if layer == 5 else plain(cfg, layer)


@pytest.mark.parametrize("function,replacement", [
    *wrong_laguna.references(bf16).values(),
    ("window_of", window_dropped_in_one_layer),
], ids=[*wrong_laguna.references(bf16), "reference_one_layer_without_window"])
def test_a_wrong_reference_is_far_from_the_program(
        laguna, expected, monkeypatch, function, replacement):
    """The window off by one, dropped in one layer of eight, the rotation on
    the wrong half, a bfloat16 router: each moves the logits past what
    float32 leaves between the program and the reference."""
    config, _, params, ids = laguna
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
    cut = {"num_hidden_layers": (40, 8), "num_experts": (256, 32),
           "vocab_size": (100352, 12544)}
    assert set(cfg["reduced"]) == set(entry["reduced"]) == set(cut)
    assert entry["source"] == cfg["source"] and len(cfg["source"]) < 200
    for key, (source, here) in cut.items():
        assert (cfg["reduced"][key]["source"], cfg["reduced"][key]["here"],
                cfg[key], cfg[key + "_published"]) == (source, here, here, source)
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"], cfg["moe_routed_scaling_factor"]) == (
        2048, 8192, 128, 8, 512, 512, 8, 512, 2.5)
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert cfg["layer_types"][:4] == ["full_attention"] + ["sliding_attention"] * 3
    for key in ("gating", "router_score", "norm_topk_prob", "hidden_act",
                "qk_norm", "yarn", "sliding_window", "selection_bias_and_groups"):
        assert key in cfg["assumed"], key
    assert "33.43 B" in cfg["assumed"]["gating"] and "34.06 B" in cfg["assumed"]["gating"]
    assert "rank 0 of 8" in cfg["deployment"] and "layers 0-7" in cfg["deployment"]
    assert cfg["rehearsal"]["sliding_window"] < 256  # the traffic's rehearsal seq
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [cells.json.loads(line) for line in open(catalog)]
    except OSError:
        return
    published = next(r for r in rows if r["name"] == "Laguna-XS.2")
    assert published["source_url"] == cfg["source"]
    differs = {k for k, v in published["config"].items() if cfg.get(k, "absent") != v}
    assert differs == set(cut)


def test_the_cells_flops_and_kernels_follow_the_layers():
    cell = cells.load_cell("laguna-xs2-33b-a3b-l8.longctx-16k")
    flops = importlib.import_module("benchmarks.lib.flops_laguna")
    config, seq, w = cell["config"], 16384, 512
    assert flops.band_pairs(seq, w) == sum(min(i + 1, w) for i in range(seq))
    per_token = flops.laguna_decoder(config, seq)
    assert 64e12 < per_token * seq < 66e12  # the issue's 65 TFLOP a step
    # by hand: matmul parameters a token passes through, then attention
    h, d = 2048, 128
    attn = lambda heads: 2 * h * d * (heads + 8) + h * heads  # noqa: E731
    sparse = h * 256 + 3 * h * 512 + 1.0 * 3 * h * 512
    params = (2 * attn(48) + 6 * attn(64) + 3 * h * 8192 + 7 * sparse + h * 12544)
    attention = 12.0 * d * (2 * 48 * seq / 2 + 6 * 64 * flops.band_pairs(seq, w) / seq)
    assert per_token == pytest.approx(6 * params + attention, rel=1e-12)
    stated = cells.stated_kernels(cell)
    assert {k: s["least"] for k, s in stated.items()} == {
        "_fwd_kernel": 2, "_bwd_dkv_kernel": 2, "_bwd_dq_kernel": 2,
        "_fwd_window_kernel": 6, "_bwd_dkv_window_kernel": 6,
        "_bwd_dq_window_kernel": 6, "_gmm_kernel": 42, "_tgmm_kernel": 21}
    # a causal call at 48 heads, a windowed one at 64 over the band's pairs
    assert stated["_fwd_kernel"]["call"][0] == 2.0 * 48 * seq * seq / 2 * 2 * d
    call = stated["_fwd_window_kernel"]["call"]
    assert call[0] == 2.0 * 64 * flops.band_pairs(seq, w) * 2 * d
    assert call[1] == (64 * 2 + 8 * 2) * seq * d * 2 + 64 * seq * 4
    # the cell reports the new readers and they find nothing in an empty run
    names = {m["name"] for m in cell["per_layer"]}
    assert {"model.swa_share", "kernel.swa_share", "kernel.swa_roofline",
            "kernel.flash_roofline", "model.moe_share", "kernel.gmm_share"} <= names
    assert "kernel.gmm_roofline" not in names
    for name in ("model.swa_share", "kernel.swa_share", "kernel.swa_roofline"):
        reader = cells.load_reader(f"{cells.BENCH_DIR}/layer_metrics", name)
        assert reader.read({"trace_data": None}) is None
