"""sarvam-105b's architecture through the program's models, on the CPU.

``SarvamMLAForCausalLM`` (a leading dense layer and expert layers, each under
latent attention whose 64-wide parts are rotated under YaRN, with a per-head
QK norm; sigmoid routing with a selection bias, a shared expert, one
expert-parallel rank's share of the routed experts through the ``gmm``
dispatch in interpret mode) against the benchmark's plain reference
(``benchmarks/reference/sarvam_mla_decoder.py``) at the configuration file's
own ``rehearsal`` size on seeded random weights: logits, loss and gradients.
The YaRN table against its closed form at the published numbers, what the
rotation turns and what it leaves, the sixteen ranks' shares of one expert
layer against the uncut reference, and ``MLAMixer`` with its new fields off
against the mixer Kimi-Linear had.
"""
import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import sarvam_mla_decoder as reference
from ray_tpu.models.llama import (
    RMSNorm, _rope, chunked_causal_lm_loss, rope_frequencies, weight_init,
)
from ray_tpu.models.mixtral import MoELayer
from ray_tpu.models.mla import YarnScaling, yarn_frequencies, yarn_mscale
from ray_tpu.models.sarvam_mla import SarvamMLAConfig, SarvamMLAForCausalLM
from ray_tpu.ops.attention import flash_attention

SEQ = 128
CONFIG = f"{cells.BENCH_DIR}/configs/sarvam-105b-l5.json"
PUBLISHED_YARN = YarnScaling(
    factor=40, original_max_position_embeddings=4096, beta_fast=32, beta_slow=1,
    mscale=1, mscale_all_dim=1,
)


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def sarvam(dtype: str):
    """(configuration dict at its rehearsal size, model, params, ids)."""
    config = cells.load_json(CONFIG)
    config = {**config, **config["rehearsal"]}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": dtype, "param_dtype": dtype},
    }
    model = SarvamMLAForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    # Norm weights away from their initial ones, so that a norm on the wrong
    # side of the rotation, or left out, shows.
    rng = np.random.default_rng(3)
    p = jax.tree_util.tree_map(lambda a: a, params)
    for i in range(config["num_hidden_layers"]):
        for name in ("q_norm", "k_norm"):
            scale = p["params"][f"layers_{i}"]["mla"][name]["scale"]
            p["params"][f"layers_{i}"]["mla"][name]["scale"] = jnp.asarray(
                rng.uniform(0.5, 1.5, scale.shape), scale.dtype)
    return config, model, p, ids


@pytest.fixture(scope="module")
def sarvam_f32():
    return sarvam("float32")


@pytest.fixture(scope="module")
def sarvam_bf16():
    return sarvam("bfloat16")


def test_the_configuration_builds_sarvams_program(sarvam_f32):
    config, model, params, _ = sarvam_f32
    cfg = model.cfg
    assert cfg.layers == (("mla", "mlp"),) + (("mla", "moe"),) * 4
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.num_shared_experts, cfg.moe_dispatch) == (
        "sigmoid", True, 2.5, 1, "gmm")
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (
        16, (0, 4), 4)
    assert (cfg.mla_rope, cfg.qk_head_norm, cfg.rope_scaling) == (
        True, True, PUBLISHED_YARN)
    p = params["params"]
    assert set(p["layers_0"]) == {"input_norm", "mla", "post_attn_norm", "mlp"}
    assert set(p["layers_4"]) == {"input_norm", "mla", "post_attn_norm", "moe"}
    mla = p["layers_0"]["mla"]
    assert set(mla) == {"q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
                        "q_norm", "k_norm", "o_proj"}
    # one weight over a head's nope + pe channels, shared by the heads
    assert mla["q_norm"]["scale"].shape == mla["k_norm"]["scale"].shape == (32,)
    moe = p["layers_1"]["moe"]
    assert moe["router"]["kernel"].shape == (128, 16)  # the router's width
    assert moe["w_gate"].shape == (4, 128, 64)  # the experts held
    assert not np.asarray(moe["router_bias"]).any()
    # and at the published sizes the file gives the published architecture
    full = cells.program_config(cells.load_json(CONFIG))
    assert (full.hidden_size, full.intermediate_size, full.expert_width,
            full.num_heads, full.kv_lora_rank, full.qk_nope_head_dim,
            full.qk_rope_head_dim, full.v_head_dim, full.rms_eps,
            full.rope_theta) == (4096, 16384, 2048, 64, 512, 128, 64, 128, 1e-6, 10000)
    assert (full.num_experts, full.experts_held, full.num_experts_per_tok,
            full.vocab_size, full.num_layers, full.tie_embeddings) == (
        128, (0, 8), 8, 32768, 5, False)
    assert full.layers == cfg.layers and full.rope_scaling == PUBLISHED_YARN


def test_another_kind_of_rope_scaling_is_refused():
    from ray_tpu.models.sarvam_mla import sarvam_mla_config

    with pytest.raises(ValueError, match="linear"):
        sarvam_mla_config(num_experts_held=8, rope_scaling={"type": "linear", "factor": 2})


# ------------------------------------------------------ the YaRN table


def test_the_yarn_table_is_the_closed_form_at_the_published_numbers():
    """Dimension 64, factor 40, 4,096 positions, 32 turns and 1."""
    def pair(turns):
        return 64 * math.log(4096 / (2 * math.pi * turns)) / (2 * math.log(10000))

    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == (10, 23)
    f = [10000 ** (-2 * i / 64) for i in range(32)]
    want = []
    for i in range(32):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f[i] / 40 * ramp + f[i] * (1 - ramp))
    got = yarn_frequencies(64, 10000, PUBLISHED_YARN)
    assert got.shape == (32,) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the first pairs turn as without scaling, the last at a fortieth
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], np.asarray(f[23:]) / 40, rtol=1e-6)
    assert got[0] == 1.0
    assert got[-1] == pytest.approx(10000 ** (-62 / 64) / 40, rel=1e-6)
    # between them every pair is slower than plain and faster than a fortieth
    assert ((got[11:23] < f[11:23]) & (got[11:23] > np.asarray(f[11:23]) / 40)).all()
    # and the reference's own table is the same closed form
    np.testing.assert_allclose(
        reference.yarn_inv_freq(64, 10000, cells.load_json(CONFIG)["rope_scaling"]),
        want, rtol=1e-12)


def test_the_softmax_scale_carries_mscale_squared():
    assert yarn_mscale(40, 1) == pytest.approx(0.1 * math.log(40) + 1)
    assert yarn_mscale(40, 1) ** 2 == pytest.approx(1.8740, abs=2e-4)
    assert yarn_mscale(1, 1) == 1.0 and yarn_mscale(40, 0) == 1.0
    config = cells.load_json(CONFIG)
    assert reference.softmax_scale(config) == pytest.approx(192 ** -0.5 * 1.8740, rel=1e-4)
    # cos and sin would carry mscale / mscale_all_dim, which is one here; a
    # scaling that has them apart is refused, since nothing multiplies them
    assert yarn_mscale(40, config["rope_scaling"]["mscale"]) == yarn_mscale(
        40, config["rope_scaling"]["mscale_all_dim"])
    with pytest.raises(ValueError, match="mscale"):
        dataclasses.replace(PUBLISHED_YARN, mscale=0.707)


@pytest.mark.parametrize("table", ["plain", "yarn"])
def test_only_the_last_64_of_192_channels_turn_and_position_0_turns_nothing(table):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 2, 8, 192)), jnp.float32)
    positions = jnp.arange(8)[None]
    freqs = (rope_frequencies(64, 10000.0) if table == "plain"
             else jnp.asarray(yarn_frequencies(64, 10000, PUBLISHED_YARN)))
    out = np.asarray(_rope(x, positions, freqs))
    x = np.asarray(x)
    np.testing.assert_array_equal(out[..., :128], x[..., :128])
    np.testing.assert_allclose(out[:, :, 0], x[:, :, 0], rtol=1e-6)
    assert (out[:, :, -1, 128:] != x[:, :, -1, 128:]).all()
    # channel 128 + i turns with channel 160 + i by position x frequency i
    angle = np.arange(8)[:, None] * np.asarray(freqs)
    a, b = x[..., 128:160], x[..., 160:]
    np.testing.assert_allclose(out[..., 128:160], a * np.cos(angle) - b * np.sin(angle),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out[..., 160:], b * np.cos(angle) + a * np.sin(angle),
                               rtol=1e-5, atol=1e-6)
    # a rotation: each pair's length stays
    np.testing.assert_allclose(out[..., 128:160] ** 2 + out[..., 160:] ** 2,
                               a ** 2 + b ** 2, rtol=1e-5)
    # the whole head turns where the table is as wide as the head
    whole = np.asarray(_rope(jnp.asarray(x[..., 128:]), positions, freqs))
    np.testing.assert_array_equal(whole, out[..., 128:])


# -------------------------------------------- the model against the reference


def test_logits_agree_with_the_reference_in_float32(sarvam_f32):
    config, model, params, ids = sarvam_f32
    system = model.apply(params, ids[None])[0]
    expected = reference.forward(params, ids, config, SEQ)
    assert system.dtype == jnp.float32
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 2e-4, "min_share_within": 1.0}
    )
    assert result["ok"], result


def test_logits_in_bfloat16_are_near_the_reference_and_not_it(sarvam_bf16):
    config, model, params, ids = sarvam_bf16
    system = model.apply(params, ids[None])[0]
    expected = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 0.1, "min_share_within": 0.9}
    )
    assert result["ok"], result
    assert result["rel_err_median"] > 1e-4  # the system is not the reference


def without_mscale(cfg):
    """YaRN's table kept, the softmax scale's mscale squared left out."""
    return dataclasses.replace(cfg, rope_scaling=dataclasses.replace(
        cfg.rope_scaling, mscale=0.0, mscale_all_dim=0.0))


@pytest.mark.parametrize("wrong", [
    without_mscale,  # 192^-1/2 alone
    lambda cfg: dataclasses.replace(cfg, rope_scaling=None),  # the plain table
    lambda cfg: dataclasses.replace(cfg, mla_rope=False),  # nothing rotated
    lambda cfg: dataclasses.replace(cfg, qk_head_norm=False),  # the QK norm left out
    lambda cfg: dataclasses.replace(cfg, routed_scaling_factor=1.0),  # the 2.5 left out
    lambda cfg: dataclasses.replace(cfg, num_shared_experts=0),
    lambda cfg: dataclasses.replace(cfg, norm_topk_prob=False),
    lambda cfg: dataclasses.replace(cfg, experts_held=(4, 8)),  # another rank's experts
], ids=["no-mscale", "no-yarn", "no-rotation", "no-qk-norm", "no-scaling",
        "no-shared-expert", "no-renormalisation", "another-rank"])
def test_a_program_of_another_function_is_far_from_the_reference(sarvam_f32, wrong):
    config, model, params, ids = sarvam_f32
    other = SarvamMLAForCausalLM(wrong(model.cfg))
    if not other.cfg.qk_head_norm:
        params = jax.tree_util.tree_map(lambda a: a, params)
        for i in range(5):
            del params["params"][f"layers_{i}"]["mla"]["q_norm"]
            del params["params"][f"layers_{i}"]["mla"]["k_norm"]
    expected = reference.forward(sarvam_f32[2], ids, config, SEQ)
    result = logits_agreement(
        other.apply(params, ids[None])[0], expected,
        {"per_position_rel_err": 1e-3, "min_share_within": 0.5},
    )
    assert not result["ok"], result


def test_an_unrotated_key_part_is_far_from_the_reference(sarvam_f32, monkeypatch):
    """q's 64-wide part rotated and the shared key's not."""
    config, model, params, ids = sarvam_f32
    from ray_tpu.models import mla

    turn, calls = mla._rope, []

    def q_only(t, positions, freqs):
        calls.append(t.shape)
        return turn(t, positions, freqs) if len(calls) % 2 else t

    monkeypatch.setattr(mla, "_rope", q_only)
    system = model.apply(params, ids[None])[0]
    assert len(calls) == 10  # q and k of five layers
    result = logits_agreement(
        system, reference.forward(params, ids, config, SEQ),
        {"per_position_rel_err": 1e-3, "min_share_within": 0.5},
    )
    assert not result["ok"], result


def test_routing_in_bfloat16_is_far_from_the_reference(sarvam_f32, monkeypatch):
    """The router's matmul and sigmoid in bfloat16 pick other experts for
    some tokens and give others' gates: the float32 comparison refuses it."""
    config, model, params, ids = sarvam_f32
    sigmoid = jax.nn.sigmoid
    monkeypatch.setattr(
        jax.nn, "sigmoid",
        lambda x: sigmoid(x.astype(jnp.bfloat16)).astype(x.dtype)
        if x.ndim == 3 and x.shape[-1] == 16 else sigmoid(x))
    system = model.apply(params, ids[None])[0]
    result = logits_agreement(
        system, reference.forward(params, ids, config, SEQ),
        {"per_position_rel_err": 1e-3, "min_share_within": 0.5},
    )
    assert not result["ok"], result


@pytest.fixture(scope="module")
def both_gradients(sarvam_f32):
    config, model, params, ids = sarvam_f32
    targets = np.roll(ids, -1)
    system = jax.jit(jax.value_and_grad(
        lambda p: chunked_causal_lm_loss(
            model, p, ids[None], targets[None], chunk_size=64)
    ))(params)
    expected = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, ids, targets, config)
    ))(params)
    return system, expected


def test_the_chunked_loss_takes_the_model_and_agrees_with_the_reference(both_gradients):
    (loss, _), (expected, _) = both_gradients
    assert float(loss) == pytest.approx(float(expected), rel=1e-5)


def leaf(tree, path):
    for key in path:
        tree = tree[key]
    return np.asarray(tree)


@pytest.mark.parametrize("path", [
    ("layers_0", "mla", "q_proj", "kernel"),
    ("layers_0", "mla", "kv_a_proj", "kernel"),
    ("layers_0", "mla", "kv_a_norm", "scale"),
    ("layers_0", "mla", "kv_b_proj", "kernel"),
    ("layers_0", "mla", "q_norm", "scale"),
    ("layers_0", "mla", "k_norm", "scale"),
    ("layers_0", "mla", "o_proj", "kernel"),
    ("layers_0", "mlp", "down_proj", "kernel"),
    ("layers_0", "input_norm", "scale"),
    ("layers_1", "mla", "q_proj", "kernel"),
    ("layers_1", "moe", "w_gate"),
    ("layers_1", "moe", "w_down"),
    ("layers_1", "moe", "shared", "up_proj", "kernel"),
    ("layers_1", "moe", "router", "kernel"),
    ("layers_2", "mla", "k_norm", "scale"),
    ("layers_3", "mla", "kv_a_proj", "kernel"),
    ("layers_3", "moe", "w_up"),
    ("layers_3", "moe", "router", "kernel"),
    ("layers_4", "mla", "o_proj", "kernel"),
    ("layers_4", "moe", "shared", "down_proj", "kernel"),
    ("final_norm", "scale"),
    ("lm_head", "kernel"),
    ("embed_tokens", "embedding"),
], ids="/".join)
def test_gradients_agree_with_the_references(both_gradients, path):
    (_, grads), (_, expected) = both_gradients
    got, want = leaf(grads["params"], path), leaf(expected["params"], path)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-5 * np.abs(want).max())


def test_no_gradient_reaches_the_selection_bias_and_the_router_learns(both_gradients):
    """The bias stays where it is (the stated departure); the router's
    weights are trained, through the held experts' gates, in the program and
    in the reference's loss alike."""
    (_, grads), (_, expected) = both_gradients
    for i in range(1, 5):
        for tree in (grads, expected):
            moe = tree["params"][f"layers_{i}"]["moe"]
            assert not np.asarray(moe["router_bias"]).any()
            assert np.asarray(moe["router"]["kernel"]).any()


# ------------------------------------------------- the expert layer alone


def expert_layer(held):
    """One expert layer at sarvam's routing: 128 experts scored, top-8,
    sigmoid, renormalised, x 2.5, one shared expert; ``held`` of them here."""
    cfg = SarvamMLAConfig(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_experts=128, num_experts_per_tok=8, num_shared_experts=1,
        routed_scaling_factor=2.5, experts_held=held, initializer_range=0.5,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return MoELayer(cfg)


def layer_config(held) -> dict:
    """The reference's keys for that layer."""
    lo, hi = held or (0, 128)
    return {"num_experts_published": 128, "num_experts": hi - lo,
            "expert_rank": lo // (hi - lo), "num_experts_per_tok": 8,
            "routed_scaling_factor": 2.5, "num_shared_experts": 1}


def test_the_sixteen_ranks_shares_add_up_to_the_uncut_layer():
    """Sixteen ranks of eight experts each, the deployment's division: the
    routed parts they give, with the shared expert (which every rank computes
    alike) counted once, are the uncut reference's expert layer."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 48, 32)), jnp.float32)
    params = expert_layer(None).init(jax.random.PRNGKey(1), x)["params"]
    bias = np.random.default_rng(2).normal(size=128).astype(np.float32) * 0.3
    params = {**params, "router_bias": jnp.asarray(bias)}
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.moe(params, tokens, layer_config(None))
        shared = reference.swiglu(params["shared"], tokens)
    total, pairs = 0.0, 0
    for rank in range(16):
        held = (8 * rank, 8 * rank + 8)
        mine = {**params, **{k: params[k][held[0]:held[1]]
                             for k in ("w_gate", "w_up", "w_down")}}
        out = expert_layer(held).apply({"params": mine}, x).reshape(-1, 32)
        with jax.default_matmul_precision("highest"):
            want = reference.moe(mine, tokens, layer_config(held))
            gates = reference.router_gates(params, tokens, layer_config(held))
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        pairs += int((np.asarray(gates)[:, held[0]:held[1]] > 0).sum())
        total = total + (out - shared)
    assert pairs == 96 * 8  # every pair is held by exactly one rank
    np.testing.assert_allclose(total + shared, uncut, rtol=1e-4, atol=2e-5)
    # gates: eight a token, renormalised, times 2.5
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5)
    assert ((np.asarray(gates) > 0).sum(-1) == 8).all()


# ------------------------- the mixer Kimi-Linear had, and the one it has now


class MixerAsItWas(nn.Module):
    """``MLAMixer`` as ``models/kimi_linear.py`` had it before it moved
    (PR 36's tree), line for line."""
    cfg: Any
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, positions):
        from ray_tpu.models.kimi_linear import _dense
        from ray_tpu.util import tracing

        cfg = self.cfg
        H, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, pe, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        heads = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            (H, feats), axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name=name,
        )
        q = heads(nope + pe, "q_proj")(x)
        with tracing.scope(tracing.MLA_LATENT):
            latent = _dense(cfg, rank + pe, "kv_a_proj")(x)
            c = RMSNorm(cfg.rms_eps, cfg.param_dtype, name="kv_a_norm")(
                latent[..., :rank]
            )
            kv = heads(nope + dv, "kv_b_proj")(c)
            k_pe = jnp.broadcast_to(
                latent[..., None, rank:], (*kv.shape[:3], pe)
            )
            k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
            v = kv[..., nope:]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        o = flash_attention(q, k, v, causal=True, sm_scale=(nope + pe) ** -0.5)
        return nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name="o_proj",
        )(o.transpose(0, 2, 1, 3))


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_the_mixer_with_its_new_fields_off_lowers_to_kimi_linears(what):
    """Rotation, YaRN and the QK norm off (Kimi-Linear's configuration):
    the shared mixer's lowered text is the old mixer's, so the Kimi cell's
    step is the one it had."""
    from ray_tpu.models.kimi_linear import KimiLinearForCausalLM

    config = cells.load_json(f"{cells.BENCH_DIR}/configs/kimi-linear-48b-a3b-l5.json")
    cfg = cells.program_config({**config, **config["rehearsal"]})
    assert (cfg.mla_rope, cfg.rope_scaling, cfg.qk_head_norm) == (False, None, False)

    class AsItWas(KimiLinearForCausalLM):
        blocks = {**KimiLinearForCausalLM.blocks, "mla": MixerAsItWas}

    ids = jnp.zeros((1, 64), jnp.int32)
    texts = []
    for cls in (KimiLinearForCausalLM, AsItWas):
        model = cls(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
        fn = model.apply if what == "forward" else jax.grad(
            lambda p, i, model=model: chunked_causal_lm_loss(
                model, p, i, i, chunk_size=32))
        texts.append(jax.jit(fn).lower(params, ids).as_text())
    assert texts[0] == texts[1]
