"""LFM2-MoE on the CPU at the configuration's rehearsal size (hidden 128, 4
query heads of 32 over 2 K/V heads, 8 experts of 128, top-2) and at one odd
size, seeded float32 weights: the config builder on the source's own keys,
for the whole 24-layer source and for the held range; ``num_params()`` against
the tree at both sizes, at the published widths and whole; logits, the chunked
loss and every gradient leaf against the plain reference
(``benchmarks/reference/lfm2_moe_decoder``: the convolution as three shifted
products, the experts by a dense loop), the kernels interpreted; each wrong
program and wrong reference of ``benchmarks/tools/wrong_lfm2.py`` far from it;
``ops/kda.py``'s ``gated_conv`` against the line it stands for and ``jax.grad``
of it; the per-head QK norm; the expert layer whole and as four ranks' shares;
and what the benchmark states of the cell.

The gated convolution alone, ``conv_silu`` beside it and the expert layer's
shares are in ``tests/test_lfm2_layers.py`` beside this file.
"""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import lfm2_moe_decoder as reference
from benchmarks.tools import wrong_lfm2
from ray_tpu.models.lfm2 import Lfm2Config, Lfm2ForCausalLM
from ray_tpu.models.llama import Attention, chunked_causal_lm_loss
from ray_tpu.ops import kda
from ray_tpu.util import tracing


SEQ = 256  # one block of the reference's query rows; the flash kernels from 128
CELL = "lfm2-8b-a1b-l5.dropfree-4k"
CONFIG = f"{cells.BENCH_DIR}/configs/lfm2-8b-a1b-l5.json"
# 6 query heads of 24 over 3 K/V heads, 6 experts top-3, a hidden size whose
# thirds fill no vreg and a sequence that is no whole number of tiles: the
# convolution's XLA lines, the attention's reference path.
ODD = {"hidden_size": 72, "intermediate_size": 160, "moe_intermediate_size": 128,
       "num_attention_heads": 6, "num_key_value_heads": 3, "head_dim": 24,
       "vocab_size": 384, "num_experts": 6, "num_experts_per_tok": 3}
ODD_SEQ = 100
# Past these a float32 program is another function than the reference.
FAR = {"per_position_rel_err": 1e-3, "min_share_within": 0.5}
NEAR = {"per_position_rel_err": 5e-5, "min_share_within": 1.0}


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # The convolution's and the grouped matmuls' kernels and, from 128 rows,
    # the flash ones.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def in_float32(config):
    return {**config, "program": {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": "float32",
                "param_dtype": "float32"}}}


def build(sizes, seq, seed):
    config = in_float32({**cells.load_json(CONFIG), **sizes})
    model = Lfm2ForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(seed).integers(0, config["vocab_size"], seq)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), ids[None, :8])
    # The draws of 0.02 leave every projection of 128 channels near zero: both
    # gates are then nothing and every router score a half. Projections of unit
    # size give the gates, the soft-max and the top-k data; a selection bias
    # that is not zero gives the choice its second term.
    bias = jax.random.normal(jax.random.PRNGKey(seed + 100), (config["num_experts"],)) * 0.2

    def drawn(path, w):
        if path[-1].key == "router_bias":
            return bias
        if path[-1].key == "kernel" and path[-2].key in (
                "q_proj", "k_proj", "v_proj", "in_proj", "router"):
            return w * 8.0
        return w

    return config, model, {"params": jax.tree_util.tree_map_with_path(
        drawn, params["params"])}, ids


@pytest.fixture(scope="module")
def lfm2():
    """(configuration dict at the rehearsal size, model, params, ids), float32."""
    return build(cells.load_json(CONFIG)["rehearsal"], SEQ, 0)


@pytest.fixture(scope="module")
def odd():
    return build(ODD, ODD_SEQ, 1)


@pytest.fixture(scope="module")
def expected(lfm2):
    config, _, params, ids = lfm2
    return reference.forward(params, ids, config, SEQ)


# ---------------------------------------------------------------- the config


CONV_MLP, CONV_MOE = (tracing.SHORTCONV, tracing.MLP), (tracing.SHORTCONV, tracing.MOE)
ATTN_MOE = (tracing.ATTN, tracing.MOE)


def test_the_builder_reads_the_sources_own_keys():
    config = cells.load_json(CONFIG)
    assert len(config["layer_types"]) == 24 == config["num_hidden_layers_published"]
    assert [i for i, t in enumerate(config["layer_types"]) if t == "full_attention"] == [
        2, 6, 10, 14, 18, 21]
    cfg = cells.program_config(config)
    assert isinstance(cfg, Lfm2Config)
    assert cfg.layers == (CONV_MLP, ATTN_MOE, CONV_MOE, CONV_MOE, CONV_MOE)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
            cfg.intermediate_size, cfg.expert_width, cfg.vocab_size, cfg.max_seq_len) == (
        2048, 32, 8, 64, 7168, 1792, 8192, 128000)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held, cfg.router_score,
            cfg.norm_topk_prob, cfg.routed_scaling_factor, cfg.moe_dispatch,
            cfg.num_shared_experts, cfg.router_aux_loss_coef) == (
        32, 4, None, "sigmoid", True, 1, "gmm", 0, 0.0)
    assert (cfg.conv_taps, cfg.tie_embeddings, cfg.rms_eps, cfg.initializer_range,
            cfg.rope_theta) == (3, True, 1e-5, 0.02, 1000000)
    assert (cfg.remat, cfg.remat_policy, cfg.remat_prevent_cse) == (True, "nothing", False)
    kind = cfg.attention(tracing.ATTN)
    assert (kind.num_heads, kind.freqs.shape, kind.qk_head_norm, kind.scale, kind.window,
            kind.gate) == (32, (32,), True, None, None, False)
    assert not cfg.qk_norm  # OLMoE's, over the whole projection


@pytest.mark.parametrize("first,count,kinds", [
    (0, 24, [CONV_MLP, CONV_MLP, ATTN_MOE] + [CONV_MOE, CONV_MOE, CONV_MOE, ATTN_MOE] * 4
     + [CONV_MOE, CONV_MOE, ATTN_MOE, CONV_MOE, CONV_MOE]),
    (1, 5, [CONV_MLP, ATTN_MOE, CONV_MOE, CONV_MOE, CONV_MOE]),
    (6, 4, [ATTN_MOE, CONV_MOE, CONV_MOE, CONV_MOE]),
    (19, 5, [CONV_MOE, CONV_MOE, ATTN_MOE, CONV_MOE, CONV_MOE]),
], ids=["the whole source", "the held range", "a later period", "the last stage"])
def test_layers_follow_layer_types_and_num_dense_layers(first, count, kinds):
    """``layer_types`` and ``num_dense_layers`` are the source's, whole; a
    stage reads its own range: 18 conv and 6 full_attention, the two leading
    layers over the dense SwiGLU."""
    cfg = cells.program_config(
        {**cells.load_json(CONFIG), "first_layer": first, "num_hidden_layers": count})
    assert list(cfg.layers) == kinds
    if count == 24:
        mixers = [m for m, _ in cfg.layers]
        assert (mixers.count(tracing.SHORTCONV), mixers.count(tracing.ATTN)) == (18, 6)
        assert [f for _, f in cfg.layers].count(tracing.MLP) == 2


def test_the_files_numbers_are_the_catalogs_but_for_the_two_it_reduces():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of public architectures is not on this machine")
    config = cells.load_json(CONFIG)
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "LFM2-8B-A1B"]
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {"num_hidden_layers", "vocab_size"}
    assert config["num_experts"] == row["config"]["num_experts"] == 32  # not reduced
    assert "num_experts" in config["not_reduced"]
    for key in ("tie_word_embeddings", "head_dim", "hidden_act", "initializer_range",
                "router_precision", "in_proj_order", "qk_norm", "intermediate_size",
                "pretraining_length"):
        assert key in config["assumed"], key
    assert len(config["departures"]) == 3 and "no expert parallelism" in config["deployment"]
    assert not {"held_rows", "experts_held"} & set(config["program"]["set"])


@pytest.mark.parametrize("change,message", [
    ({"conv_bias": True}, "no bias"),
    ({"use_expert_bias": False}, "plus a bias"),
    ({"layer_types": ["conv", "mamba"] * 12}, "mamba"),
    ({"layer_types": ["conv"] * 5}, "short of layers 1 to 5"),
], ids=["a filter bias", "no selection bias", "an unknown layer type", "too few layer types"])
def test_what_the_builder_cannot_build_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        cells.program_config({**cells.load_json(CONFIG), **change})


def leaves(tree):
    return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))


def test_num_params_is_the_tree_at_the_published_widths_and_whole():
    config = cells.load_json(CONFIG)
    cfg = cells.program_config(config)
    shapes = jax.eval_shape(Lfm2ForCausalLM(cfg).init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    assert leaves(shapes) == cfg.num_params() == config["parameters_held"] == 1_548_007_680
    p = shapes["params"]
    assert leaves(p["layers_0"]["shortconv"]) == 16_783_360
    assert leaves(p["layers_1"]["attn"]) == 10_485_888
    assert leaves(p["layers_0"]["mlp"]) == 44_040_192
    assert leaves(p["layers_1"]["moe"]) == 352_387_104
    assert [leaves(p[f"layers_{i}"]) for i in range(5)] == [
        60_827_648, 362_877_088, 369_174_560, 369_174_560, 369_174_560]
    conv, attn, moe = p["layers_0"]["shortconv"], p["layers_1"]["attn"], p["layers_1"]["moe"]
    assert conv["in_proj"]["kernel"].shape == (2048, 6144)
    assert conv["conv"].shape == (3, 2048)
    assert conv["out_proj"]["kernel"].shape == (2048, 2048)
    assert set(conv) == {"in_proj", "conv", "out_proj"}  # no bias, no norm
    assert attn["q_proj"]["kernel"].shape == (2048, 32, 64)
    assert attn["k_proj"]["kernel"].shape == attn["v_proj"]["kernel"].shape == (2048, 8, 64)
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (64,)
    assert moe["router"]["kernel"].shape == (2048, 32) and moe["router_bias"].shape == (32,)
    assert moe["w_gate"].shape == moe["w_up"].shape == (32, 2048, 1792)
    assert moe["w_down"].shape == (32, 1792, 2048)
    assert "lm_head" not in p and p["embed_tokens"]["embedding"].shape == (8192, 2048)
    whole = cells.program_config(
        {**config, "first_layer": 0, "num_hidden_layers": 24, "vocab_size": 65536})
    assert whole.num_params() == config["parameters_whole_model"] == 8_339_930_560
    assert whole.num_params() == (
        2 * 60_827_648 + 6 * 362_877_088 + 16 * 369_174_560 + 134_219_776)


@pytest.mark.parametrize("which", ["lfm2", "odd"])
def test_num_params_is_the_tree_at_the_small_sizes(request, which):
    _, model, params, _ = request.getfixturevalue(which)
    assert leaves(params) == model.cfg.num_params()


# -------------------------------------------- the model against the reference


def test_logits_agree_with_the_reference_in_float32(lfm2, expected):
    _, model, params, ids = lfm2
    system = jax.jit(model.apply)(params, ids[None])[0]
    assert system.dtype == jnp.float32
    result = logits_agreement(system, expected, NEAR)
    assert result["ok"], result


def test_logits_agree_at_an_odd_size(odd):
    config, model, params, ids = odd
    assert kda._gated_blocks(jax.ShapeDtypeStruct((1, ODD_SEQ, 216), jnp.float32),
                             jax.ShapeDtypeStruct((3, 72), jnp.float32)) is None
    system = jax.jit(model.apply)(params, ids[None])[0]
    # the reference's query rows go a block at a time: the odd length whole
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr("benchmarks.reference.common.Q_BLOCK", ODD_SEQ)
        wanted = reference.forward(params, ids, config, ODD_SEQ)
    result = logits_agreement(system, wanted, NEAR)
    assert result["ok"], result


WRONG = {
    **{name: ("program", entry)
       for name, entry in wrong_lfm2.programs(
           cells.program_config(in_float32(
               {**cells.load_json(CONFIG), **cells.load_json(CONFIG)["rehearsal"]}))).items()},
    **{name: ("reference", entry)
       for name, entry in wrong_lfm2.references(
           lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)).items()},
}


@pytest.mark.parametrize("name", list(WRONG))
def test_a_wrong_program_or_reference_is_refused(lfm2, expected, monkeypatch, name):
    """Gates not renormalised, soft-max scores; B's gate absent, C's gate
    absent, the filter reversed in time, a SiLU after the convolution, the
    four largest scores without the selection bias (the weights' bias is not
    zero here), a QK norm over the whole projection, no rotation: each moves
    the logits past what float32 leaves between program and reference."""
    config, _, params, ids = lfm2
    kind, entry = WRONG[name]
    if kind == "program":
        other = jax.jit(Lfm2ForCausalLM(entry[0]).apply)(params, ids[None])[0]
    else:
        function, replacement = entry
        monkeypatch.setattr(
            reference, function, replacement(getattr(reference, function)))
        other = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(other, expected, FAR)
    assert not result["ok"], result


@pytest.fixture(scope="module")
def both_gradients(lfm2):
    config, model, params, ids = lfm2
    targets = np.roll(ids, -1)
    system = jax.jit(jax.value_and_grad(
        lambda p: chunked_causal_lm_loss(
            model, p, ids[None], targets[None], chunk_size=64)
    ))(params)
    wanted = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, ids, targets, config)
    ))(params)
    return system, wanted


def test_the_chunked_loss_agrees_with_the_reference(both_gradients):
    (loss, _), (wanted, _) = both_gradients
    assert float(loss) == pytest.approx(float(wanted), rel=1e-5)


def test_every_gradient_agrees_with_the_references(both_gradients):
    (_, grads), (_, wanted) = both_gradients
    flat = dict(jax.tree_util.tree_leaves_with_path(grads["params"]))
    errors = {}
    for path, want in jax.tree_util.tree_leaves_with_path(wanted["params"]):
        got, want = np.asarray(flat[path]), np.asarray(want)
        assert got.shape == want.shape, path
        if path[-1].key == "router_bias":  # a buffer: no gradient reaches it
            assert not got.any() and not want.any()
            continue
        assert np.abs(want).max() > 0, path
        errors[jax.tree_util.keystr(path)] = np.abs(got - want).max() / np.abs(want).max()
    assert max(errors.values()) < 1e-4, max(errors.items(), key=lambda e: e[1])
    # a layer's 2 norms; 4 conv mixers' 3 leaves and the attention's 6; the
    # dense SwiGLU's 3 and 4 expert layers' router and 3 stacks; the tied
    # embedding and the final norm
    assert len(errors) == 5 * 2 + 4 * 3 + 6 + 3 + 4 * 4 + 2


# ------------------------------------------------------ the per-head QK norm


def test_the_qk_norm_is_an_rmsnorm_over_each_heads_channels_before_the_rotation(lfm2):
    """``Attention`` under ``AttentionKind.qk_head_norm`` against a
    hand-written line: q and k each divided by the root mean square of a
    head's own 32 channels, times one weight [32] shared by the heads, then
    turned; and a norm over the whole projection is another function."""
    from benchmarks.reference.common import causal_gqa, rotary

    config, model, params, _ = lfm2
    cfg = model.cfg
    p = params["params"]["layers_1"]["attn"]
    scale = jax.random.uniform(jax.random.PRNGKey(3), (2, cfg.head_dim_), jnp.float32, 0.5, 1.5)
    p = {**p, "q_norm": {"scale": scale[0]}, "k_norm": {"scale": scale[1]}}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, SEQ, cfg.hidden_size), jnp.float32)
    positions = jnp.arange(SEQ)[None]
    got = Attention(cfg, name=tracing.ATTN).apply({"params": p}, x, positions)[0]

    def by_hand(norm):
        q, k, v = (jnp.einsum("th,hnd->tnd", x[0], p[n]["kernel"])
                   for n in ("q_proj", "k_proj", "v_proj"))
        q, k = norm(q) * scale[0], norm(k) * scale[1]
        o = causal_gqa(rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta), v)
        return jnp.einsum("tnd,ndh->th", o, p["o_proj"]["kernel"])

    a_head = lambda t: t * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(t * t, axis=-1, keepdims=True) + cfg.rms_eps)
    whole = lambda t: t * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(t * t, axis=(-2, -1), keepdims=True) + cfg.rms_eps)
    with jax.default_matmul_precision("highest"):
        want, other = by_hand(a_head), by_hand(whole)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(other - want).max()) > 1e-2 * float(jnp.abs(want).max())


# ------------------------------------------------- what the benchmark states


def test_the_required_flops_a_token_are_the_issues_arithmetic():
    from benchmarks.lib.flops_lfm2 import macs_per_token

    cell = cells.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    per_token = cells.resolve(config["required_flops"])(config, traffic["seq"])
    expert_layer_macs = 4 * 3 * 2048 * 1792 + 2048 * 32
    conv, dense = 2048 * 6144 + 2048 * 2048, 3 * 2048 * 7168
    projections = 2 * 2048 * 64 * (32 + 8)
    scores, head = 4096 * 32 * 64, 2048 * 8192
    by_hand = {"experts_and_router": 4 * expert_layer_macs, "shortconv": 4 * conv,
               "dense_swiglu": dense, "attention_projections": projections,
               "attention_scores": scores, "head": head}
    assert macs_per_token(config, 4096) == by_hand
    assert {k: round(v / 1e6, 1) for k, v in by_hand.items()} == {
        "experts_and_router": 176.4, "shortconv": 67.1, "dense_swiglu": 44.0,
        "attention_projections": 10.5, "attention_scores": 8.4, "head": 16.8}
    assert sum(by_hand.values()) == 323_223_552
    assert per_token == 6.0 * 323_223_552 == pytest.approx(1.94e9, rel=1e-3)
    shares = {k: round(100 * v / 323_223_552, 1) for k, v in by_hand.items()}
    assert shares == {"experts_and_router": 54.6, "shortconv": 20.8, "dense_swiglu": 13.6,
                      "attention_projections": 3.2, "attention_scores": 2.6, "head": 5.2}


def test_the_stated_kernels_are_the_steps():
    from benchmarks.lib.flops import flash_call
    from benchmarks.lib.flops_gmm import gmm_call
    from benchmarks.lib.flops_lfm2 import gated_conv_call

    cell = cells.load_cell(CELL)
    stated = cells.stated_kernels(cell)
    assert {k: s["least"] for k, s in stated.items()} == {
        "_fwd_kernel": 1, "_bwd_dkv_kernel": 1, "_bwd_dq_kernel": 1,
        "_gmm_kernel": 24, "_tgmm_kernel": 12,
        "_gated_conv_fwd_kernel": 1, "_gated_conv_bwd_kernel": 1}
    # every pair is here: 2 x 4,096 tokens x 4 experts, at 2048 x 1792
    assert stated["_gmm_kernel"]["call"] == gmm_call("_gmm_kernel", 32768, 2048, 1792, 32)
    assert stated["_gmm_kernel"]["call"][0] == 2.0 * 32768 * 2048 * 1792
    assert stated["_fwd_kernel"]["call"] == flash_call(
        "_fwd_kernel", 64, 4096, 4096, 64, causal=True)
    # forward 4 and backward 7 arrays of [8192, 2048] bfloat16
    assert stated["_gated_conv_fwd_kernel"]["call"] == (8.0 * 8192 * 2048, 4.0 * 8192 * 2048 * 2)
    assert stated["_gated_conv_bwd_kernel"]["call"] == (24.0 * 8192 * 2048, 7.0 * 8192 * 2048 * 2)
    with pytest.raises(KeyError):
        gated_conv_call("_conv_fwd_kernel", 8192, 2048, 3)


def test_the_cell_reads_the_metrics_of_its_layers():
    cell = cells.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    ours = {"model.shortconv_share", "kernel.shortconv_share", "kernel.shortconv_roofline"}
    assert ours | {"model.moe_share", "model.moe_expert_share", "model.moe_dispatch_share",
                   "kernel.gmm_share", "kernel.gmm_roofline", "kernel.flash_share",
                   "kernel.flash_roofline", "model.gqa_share", "model.mlp_share",
                   "trainer.step_ms_p95_over_p50", "step.unnamed_share",
                   "model.head_loss_share", "device.peak_hbm_gib"} <= names
    assert not {"kernel.ssd_share", "model.mamba_share", "model.kda_share",
                "model.mla_share"} & names
    traffic = cell["traffic"]
    assert (cell["chips"], cell["traffic_name"], traffic["batch"], traffic["seq"],
            traffic["loss"]["args"]["chunk_size"], traffic["batches"],
            traffic["compare_last"], traffic["trace_steps"], traffic["expect"]) == (
        1, "dropfree-4k", 2, 4096, 2048, 16, 256, 3, {"moe_dispatch": "gmm"})
    bench = cells.load_json(f"{cells.ROOT}/BENCHMARK.json")
    for metric in bench["per_layer"]:
        if metric["name"] in ours:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "tokens_per_s_per_chip"
            reader = cells.load_reader(f"{cells.BENCH_DIR}/layer_metrics", metric["name"])
            assert reader.read({"trace_data": None}) is None
