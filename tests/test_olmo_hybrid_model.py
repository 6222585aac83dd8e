"""Olmo-Hybrid on the CPU at a tiny size whose key and value heads differ and
fill no vreg (6 heads of 24/48, hidden 96), seeded float32 weights: the
config builder on the source's own keys; ``num_params()`` against the tree,
tiny and at the published widths; logits, the chunked loss and every gradient
leaf against the plain reference (``benchmarks/reference/olmo_hybrid_decoder``:
the recurrence token by token, the norms after the sublayers), the scan's and
the convolution's kernels interpreted; each wrong program and wrong reference
of ``benchmarks/tools/wrong_olmo_hybrid.py`` far from it; the reordered norm
against a pre-norm body with the same weights; and what the benchmark states
of the cell (its FLOPs a token, its kernels, its metrics)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import olmo_hybrid_decoder as reference
from benchmarks.tools import wrong_olmo_hybrid
from ray_tpu.models.llama import chunked_causal_lm_loss
from ray_tpu.models.olmo_hybrid import (
    OlmoHybridConfig, OlmoHybridForCausalLM, olmo_hybrid_config,
)
from ray_tpu.util import tracing

SEQ = 128
CELL = "olmo-hybrid-7b-l4.pretrain-8k"
CONFIG = f"{cells.BENCH_DIR}/configs/olmo-hybrid-7b-l4.json"
TINY = {"hidden_size": 96, "intermediate_size": 256, "num_attention_heads": 6,
        "num_key_value_heads": 6, "head_dim": 16, "vocab_size": 512,
        "linear_num_key_heads": 6, "linear_num_value_heads": 6,
        "linear_key_head_dim": 24, "linear_value_head_dim": 48}
# Past these a float32 program is another function than the reference.
FAR = {"per_position_rel_err": 1e-3, "min_share_within": 0.5}


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # The scan kernels, the convolution's where its lanes tile (v's 288
    # channels do not: XLA's passes there) and, at 128 rows, the flash kernels.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def in_float32(config):
    return {**config, "program": {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": "float32",
                "param_dtype": "float32"}}}


@pytest.fixture(scope="module")
def olmo():
    """(configuration dict at the tiny size, model, params, ids), float32."""
    config = in_float32({**cells.load_json(CONFIG), **TINY})
    model = OlmoHybridForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    # The draws of 0.02 leave beta within 0.25 of 1 and the decay's softplus
    # near dt_bias: widen both, so that beta runs over (0, 2), the decay from
    # weak to strong, and a doubling or a decay left out is far from the model.
    # Layer 0 reads the embedding itself (no norm comes before a mixer): at
    # its draw of 0.02 and 96 channels every map of that layer is near zero
    # and its output under the norm's eps; a table of unit size gives the
    # first layer the stream the later ones see.
    p = dict(params["params"])
    p["embed_tokens"] = {"embedding": p["embed_tokens"]["embedding"] * 50.0}
    for i in (0, 1, 2):
        gdn = dict(p[f"layers_{i}"]["gdn"])
        gdn["b_proj"] = {"kernel": gdn["b_proj"]["kernel"] * 12.0}
        gdn["a_proj"] = {"kernel": gdn["a_proj"]["kernel"] * 12.0}
        p[f"layers_{i}"] = {**p[f"layers_{i}"], "gdn": gdn}
    return config, model, {"params": p}, ids


@pytest.fixture(scope="module")
def expected(olmo):
    config, _, params, ids = olmo
    return reference.forward(params, ids, config, SEQ)


# ---------------------------------------------------------------- the config


def test_the_builder_reads_the_sources_own_keys():
    config = cells.load_json(CONFIG)
    assert len(config["layer_types"]) == 32 == config["num_hidden_layers_published"]
    assert config["rope_parameters"] == {"rope_theta": None}
    cfg = cells.program_config(config)
    assert isinstance(cfg, OlmoHybridConfig)
    assert cfg.layers == ((tracing.GDN, tracing.MLP),) * 3 + ((tracing.ATTN, tracing.MLP),)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
            cfg.intermediate_size, cfg.vocab_size) == (3840, 30, 30, 128, 11008, 12544)
    assert (cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.linear_allow_neg_eigval) == (30, 96, 192, 4, True)
    assert (cfg.norm_after, cfg.qk_norm, cfg.rms_eps, cfg.tie_embeddings,
            cfg.remat_prevent_cse) == (True, True, 1e-6, False, True)
    kind = cfg.attention(tracing.ATTN)
    assert (kind.num_heads, kind.freqs, kind.window, kind.gate) == (30, None, None, False)


def test_a_theta_builds_the_rotating_kind_and_the_whole_pattern_has_eight_full_layers():
    config = cells.load_json(CONFIG)
    whole = cells.program_config({
        **config, "num_hidden_layers": 32, "vocab_size": 100352,
        "rope_parameters": {"rope_theta": 500000.0}})
    assert whole.attention(tracing.ATTN).freqs.shape == (64,)
    mixers = [m for m, _ in whole.layers]
    assert mixers.count(tracing.ATTN) == 8 and mixers[3::4] == [tracing.ATTN] * 8
    # The file's own check: the whole model, by the same count.
    assert whole.num_params() == config["parameters_whole_model"] == 7_430_870_688


@pytest.mark.parametrize("change,message", [
    ({"linear_num_value_heads": 60}, "head count"),
    ({"layer_types": ["linear_attention", "sliding_attention"] * 2}, "sliding_attention"),
    ({"layer_types": ["linear_attention"] * 3}, "short of 4"),
], ids=["more value heads", "an unknown layer type", "too few layer types"])
def test_what_the_builder_cannot_build_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        cells.program_config({**cells.load_json(CONFIG), **change})


def leaves(tree):
    return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))


def test_num_params_is_the_tree_at_the_published_widths():
    cfg = cells.program_config(cells.load_json(CONFIG))
    shapes = jax.eval_shape(OlmoHybridForCausalLM(cfg).init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    assert leaves(shapes) == cfg.num_params() == 928_862_196
    p = shapes["params"]
    assert leaves(p["layers_0"]["gdn"]) == 88_750_332  # 88.75 M
    assert leaves(p["layers_3"]["attn"]) == 58_990_080
    assert leaves(p["layers_0"]["mlp"]) == 126_812_160
    gdn = p["layers_0"]["gdn"]
    assert gdn["qk_proj"]["kernel"].shape == (3840, 2 * 30 * 96)
    assert gdn["qk_conv"].shape == (4, 5760) and gdn["v_conv"].shape == (4, 5760)
    assert gdn["A_log"].shape == gdn["dt_bias"].shape == (30,)
    assert gdn["o_norm"]["scale"].shape == (192,)
    assert set(p["layers_3"]) == {"attn", "mlp", "post_mixer_norm", "post_ffn_norm"}
    assert p["layers_3"]["attn"]["q_norm"]["scale"].shape == (3840,)


def test_num_params_is_the_tree_at_the_tiny_size(olmo):
    _, model, params, _ = olmo
    assert leaves(params) == model.cfg.num_params()


# -------------------------------------------- the model against the reference


def test_logits_agree_with_the_reference_in_float32(olmo, expected):
    _, model, params, ids = olmo
    system = jax.jit(model.apply)(params, ids[None])[0]
    assert system.dtype == jnp.float32
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 5e-5, "min_share_within": 1.0})
    assert result["ok"], result


def test_beta_runs_over_zero_to_two_and_the_decay_from_weak_to_strong(olmo):
    config, _, params, ids = olmo
    p = params["params"]
    x = p["embed_tokens"]["embedding"][ids]
    beta = np.asarray(reference.write_strength(p["layers_0"]["gdn"], x, config))
    assert beta.max() > 1.8 and beta.min() < 0.2 and (beta > 1).mean() > 0.25
    g = np.asarray(reference.log_decay(p["layers_0"]["gdn"], x))
    assert g.shape == (SEQ, 6) and g.max() <= 0 and g.min() < -1.0 and g.max() > -0.05


def without(params, names):
    """The tree less the mixers' parameters ``names``."""
    return {"params": {
        layer: {k: {n: w for n, w in v.items() if n not in names}
                if k in tracing.MIXERS else v for k, v in sub.items()}
        if layer.startswith("layers_") else sub
        for layer, sub in params["params"].items()}}


@pytest.mark.parametrize("name", [
    "system_beta_undoubled", "system_rotated", "system_no_qk_norm"])
def test_a_wrong_program_is_far_from_the_reference(olmo, expected, name):
    _, model, params, ids = olmo
    cfg, *drop = wrong_olmo_hybrid.programs(model.cfg)[name]
    if drop:
        params = without(params, drop[0])
    system = jax.jit(OlmoHybridForCausalLM(cfg).apply)(params, ids[None])[0]
    result = logits_agreement(system, expected, FAR)
    assert not result["ok"], result


def bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


@pytest.mark.parametrize(
    "function,replacement", wrong_olmo_hybrid.references(bf16).values(),
    ids=list(wrong_olmo_hybrid.references(bf16)))
def test_a_wrong_reference_is_far_from_the_program(
        olmo, expected, monkeypatch, function, replacement):
    """A sigmoid for the gate's SiLU, the decay left out, the norms before the
    sublayers, a bfloat16 state: each moves the logits past what float32
    leaves between the program and the reference."""
    config, _, params, ids = olmo
    monkeypatch.setattr(
        reference, function, replacement(getattr(reference, function)))
    other = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(other, expected, FAR)
    assert not result["ok"], result


def test_the_reordered_norm_is_not_a_pre_norm_body_with_the_same_weights(olmo, expected):
    """``norm_after`` off binds the two norms before the sublayers under the
    pre-norm names: given the same weights under them it is another function,
    and the one ``wrong_olmo_hybrid``'s pre-norm reference computes."""
    config, model, params, ids = olmo
    renamed = {"params": {
        name: {{tracing.POST_MIXER_NORM: tracing.INPUT_NORM,
                tracing.POST_FFN_NORM: tracing.POST_ATTN_NORM}.get(k, k): v
               for k, v in layer.items()} if name.startswith("layers_") else layer
        for name, layer in params["params"].items()}}
    pre = OlmoHybridForCausalLM(dataclasses.replace(model.cfg, norm_after=False))
    system = jax.jit(pre.apply)(renamed, ids[None])[0]
    assert not logits_agreement(system, expected, FAR)["ok"]
    function, replacement = wrong_olmo_hybrid.references(bf16)["reference_prenorm"]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(reference, function, replacement(getattr(reference, function)))
        prenorm = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(
        system, prenorm, {"per_position_rel_err": 2e-5, "min_share_within": 1.0})
    assert result["ok"], result


@pytest.fixture(scope="module")
def both_gradients(olmo):
    config, model, params, ids = olmo
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
    checked = 0
    for path, want in jax.tree_util.tree_leaves_with_path(wanted["params"]):
        got, want = np.asarray(flat[path]), np.asarray(want)
        name = jax.tree_util.keystr(path)
        assert got.shape == want.shape and np.abs(want).max() > 0, name
        np.testing.assert_allclose(
            got, want, rtol=5e-3, atol=1e-4 * np.abs(want).max(), err_msg=name)
        checked += 1
    # a layer: 2 norms and the MLP's 3 weights; a gdn mixer's 11 leaves, the
    # full mixer's 6 (q and k normed); embedding, final norm, head
    assert checked == 4 * 5 + 3 * 11 + 6 + 3


# ------------------------------------------------- what the benchmark states


def test_the_required_flops_a_token_are_the_issues_arithmetic():
    cell = cells.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    per_token = cells.resolve(config["required_flops"])(config, traffic["seq"])
    matmul_params = 3 * 88_704_000 + 58_982_400 + 4 * 126_812_160 + 3840 * 12544
    assert round(matmul_params / 1e5) == 8805  # 880.5 M
    recurrence = 3 * 30 * 21 * 96 * 192
    assert per_token == 6.0 * matmul_params + 6.0 * 8192 * 3840 + recurrence
    assert round(per_token / 1e7) == 551  # 5.51 GFLOP
    gdn = 3 * 6 * 88_704_000 + recurrence
    assert 0.29 < gdn / per_token < 0.31  # the three mixers' share
    assert 0.05 < 6 * 3840 * 12544 / per_token < 0.06  # the head's


def test_the_stated_kernels_are_the_steps_and_count_the_published_head_dims():
    from benchmarks.lib.flops_gdn import gdn_call
    from benchmarks.lib.flops_kda import kda_call

    stated = cells.stated_kernels(cells.load_cell(CELL))
    assert {k: s["least"] for k, s in stated.items()} == {
        "_fwd_kernel": 1, "_bwd_dkv_kernel": 1, "_bwd_dq_kernel": 1,
        "_gdn_fwd_kernel": 3, "_gdn_bwd_kernel": 3,
        "_conv_fwd_kernel": 2, "_conv_bwd_kernel": 2}
    flops, nbytes = stated["_gdn_fwd_kernel"]["call"]
    assert (flops, nbytes) == gdn_call("_gdn_fwd_kernel", 30, 8192, 96, 192)
    # The chunked form's own products at 96/192, KDA's count at dk != dv; the
    # decay four bytes a head and token where KDA moves four a channel.
    assert flops == kda_call("_kda_fwd_kernel", 30, 8192, 96, 192)[0]
    chunks = 30 * 128
    assert nbytes == chunks * 64 * ((2 * 96 + 3 * 192) * 2 + 8)
    assert stated["_gdn_bwd_kernel"]["call"][0] == 3 * flops
    with pytest.raises(KeyError):
        gdn_call("_kda_fwd_kernel", 30, 8192, 96, 192)


def test_the_cell_reads_the_metrics_of_its_layers_and_not_kdas():
    cell = cells.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert {"model.gdn_share", "kernel.gdn_share", "kernel.gdn_roofline",
            "model.mlp_share", "kernel.flash_share", "kernel.flash_roofline",
            "trainer.step_ms_p95_over_p50", "step.unnamed_share",
            "model.head_loss_share"} <= names
    assert not {"kernel.kda_share", "kernel.kda_roofline", "model.kda_share",
                "model.moe_share", "kernel.gmm_share", "model.gqa_share"} & names
    assert (cell["traffic"]["batch"], cell["traffic"]["seq"],
            cell["traffic"]["loss"]["args"]["chunk_size"]) == (1, 8192, 2048)
    assert "expect" not in cell["traffic"]
    for name in ("model.gdn_share", "kernel.gdn_share", "kernel.gdn_roofline"):
        reader = cells.load_reader(f"{cells.BENCH_DIR}/layer_metrics", name)
        assert reader.read({"trace_data": None}) is None
