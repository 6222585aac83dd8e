"""sarvam's architecture through the program's models, on the CPU: the YaRN table
and the softmax scale in closed form, the rotation of a head's last 64
channels, the bfloat16 program, the sixteen ranks' shares of an expert layer,
and the mixer Kimi-Linear had (``tests/test_sarvam_mla_model.py`` has the
model against its reference and says what the reference is;
``tests/sarvam_cases.py`` what the files share).
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
from ray_tpu.models.mla import yarn_frequencies, yarn_mscale
from ray_tpu.models.sarvam_mla import SarvamMLAConfig
from ray_tpu.ops.attention import flash_attention

from sarvam_cases import (  # noqa: F401 - fixtures
    CONFIG, PUBLISHED_YARN, SEQ, interpret, sarvam,
)


@pytest.fixture(scope="module")
def sarvam_bf16():
    return sarvam("bfloat16")


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


def test_logits_in_bfloat16_are_near_the_reference_and_not_it(sarvam_bf16):
    config, model, params, ids = sarvam_bf16
    system = jax.jit(model.apply)(params, ids[None])[0]
    expected = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 0.1, "min_share_within": 0.9}
    )
    assert result["ok"], result
    assert result["rel_err_median"] > 1e-4  # the system is not the reference


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
