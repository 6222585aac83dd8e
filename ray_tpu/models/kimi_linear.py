"""Kimi-Linear: a hybrid of gated delta-rule linear attention (KDA) and
latent attention (MLA) layers over a sigmoid-routed mixture of experts.

The model is llama.py's decoder body; what is here is its two mixers and
the configuration that says which layer has which (moonshotai's
``modeling_kimi.py`` and ``fla.layers.kda``, as far as they are known:
the benchmark's configuration file lists what was assumed). No layer has
a rotary embedding: KDA carries position in its state and MLA runs
without one (``mla_use_nope``).

KDA (``KDAMixer``, with its fields in ``KDAConfig``: the one KDA mixer, of
this model and of every other KDA hybrid, ``solar_open2.py``'s among them,
whatever its full-attention layers are): q, k, v projections, each through a
causal depthwise convolution of 4 taps and SiLU; q and k L2-normalised per
head; a per-channel log-decay g = -exp(A_log) softplus(W_f2 W_f1 x +
dt_bias) through a low-rank map of the head dim; a write strength per head,
whose range is the configuration's: beta = sigmoid(W_b x) in (0, 1) here
(Kimi-Linear), 2 sigmoid(W_b x) in (0, 2) where ``kda_allow_neg_eigval`` is
set (a transition I - beta k k^T with an eigenvalue 1 - beta in (-1, 1));
the recurrence; a per-head RMSNorm gated by sigmoid(W_g2 W_g1 x); the
output projection. The three normalisations over a head's channels and the
output gate are ``ops/kda.py``'s, on the blocks its kernels hold: the mixer
hands it q and k raw and takes o normalised and gated.

MLA (``mla.py``'s ``MLAMixer``, shared with ``sarvam_mla.py``): q heads of
128 + 64; keys and values from a shared latent of 512 and one 64-wide key
part shared by all heads; here without rotation, scaling or QK norm.

The expert layer is mixtral.py's ``MoELayer`` told to score by sigmoid, to
scale its renormalised gates, to add a shared expert and to hold a range of
the router's experts; the leading dense layer is llama.py's ``MLP``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.kda import chunk_kda, conv_silu, kda_gate
from ..util import tracing
from .llama import weight_init
from .mixtral import MixtralForCausalLM
from .mla import MLAConfig, MLAMixer


@dataclass(frozen=True)
class KDAConfig:
    """What ``KDAMixer`` reads of a config beside the decoder's own fields
    (hidden size, dtypes, ``rms_eps``, the initialiser): a family with KDA
    layers puts this before its decoder's config among its bases."""
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    # The source's key: beta = 2 sigmoid(W_b x), in (0, 2) (fla's
    # ``beta * 2``), where it is sigmoid(W_b x), in (0, 1).
    kda_allow_neg_eigval: bool = False


@dataclass(frozen=True)
class KimiLinearConfig(KDAConfig, MLAConfig):
    # Each layer's (mixer, ffn): "kda" or "mla", "mlp" or "moe".
    layer_kinds: Tuple[Tuple[str, str], ...] = ()
    router_score: str = "sigmoid"
    moe_dispatch: str = "gmm"
    remat_policy: str = "nothing"
    # Each KDA layer's replay keeps 512 MiB of chunk states at 16k tokens.
    remat_prevent_cse: bool = True
    router_aux_loss_coef: float = 0.0

    @property
    def layers(self):
        return self.layer_kinds


def kimi_linear_config(
    *, linear_attn_config: dict, first_k_dense_replace: int,
    moe_layer_freq: int, num_layers: int, num_experts_held: int,
    expert_rank: int = 0, **fields,
) -> KimiLinearConfig:
    """The program's config from the source's keys: its nested
    ``linear_attn_config`` (which layers, counted from 1, are KDA and which
    full attention; KDA's heads, head dim and convolution), the leading dense
    layers and the expert layers' frequency; and the deployment's: how many
    of the router's experts a rank holds, and which rank this is."""
    kda = set(linear_attn_config["kda_layers"])
    full = set(linear_attn_config["full_attn_layers"])
    kinds = []
    for i in range(num_layers):
        if i + 1 not in kda | full:
            raise ValueError(f"layer {i + 1} is neither a KDA nor a full-attention layer")
        sparse = i >= first_k_dense_replace and i % moe_layer_freq == 0
        kinds.append((tracing.KDA if i + 1 in kda else tracing.MLA,
                      tracing.MOE if sparse else tracing.MLP))
    first = expert_rank * num_experts_held
    return KimiLinearConfig(
        num_layers=num_layers, layer_kinds=tuple(kinds),
        kda_num_heads=linear_attn_config["num_heads"],
        kda_head_dim=linear_attn_config["head_dim"],
        short_conv_kernel_size=linear_attn_config["short_conv_kernel_size"],
        experts_held=(first, first + num_experts_held), **fields,
    )


def _a_log_init(key, shape, dtype):
    """fla's KDA: A uniform in [1, 16), kept as its logarithm."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype):
    """fla's KDA (Mamba's): dt log-uniform in [0.001, 0.1], kept as its
    inverse softplus."""
    lo, hi = math.log(0.001), math.log(0.1)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (hi - lo) + lo)
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


def _conv_init(key, shape, dtype):
    """torch's Conv1d default for a depthwise filter of 4 taps:
    uniform(-1/sqrt(taps), 1/sqrt(taps))."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _dense(cfg, features, name, use_bias=False, dtype=None):
    return nn.Dense(
        features, use_bias=use_bias, dtype=dtype or cfg.dtype,
        param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg), name=name,
    )


class NormWeight(nn.Module):
    """The weight of a norm that a kernel applies: ``RMSNorm``'s parameter
    under the name and shape ``RMSNorm`` gives it."""
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, dim):
        return self.param("scale", nn.initializers.ones, (dim,), self.param_dtype)


class KDAMixer(nn.Module):
    """The gated delta-rule mixer of a layer, whatever the model's other
    layers are. One device's: the recurrence is not sharded over the
    sequence."""
    # A decoder's config with ``KDAConfig`` among its bases.
    cfg: KDAConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        H, d = cfg.kda_num_heads, cfg.kda_head_dim
        B, T, _ = x.shape
        f32 = jnp.float32
        proj = {n: _dense(cfg, H * d, f"{n}_proj", dtype=f32)(x) for n in "qkv"}
        # The convolution and SiLU are float32, one pass over a projection
        # (``ops/kda.py`` ``conv_silu``). q and k leave it as they are, for
        # the scan's kernels to normalise on the blocks they hold; v is
        # rounded here, as that pass stores it.
        with tracing.scope(tracing.KDA_CONV):
            q, k, v = (
                conv_silu(proj[n], self.param(
                    f"{n}_conv", _conv_init,
                    (cfg.short_conv_kernel_size, H * d), cfg.param_dtype,
                ), cfg.dtype if n == "v" else f32).reshape(B, T, H, d)
                for n in "qkv"
            )
        # The low-rank maps of the gates have the head dim as their width.
        # The decay's comes out in float32: exp(A_log) is up to 16, the
        # log-decay adds up over a chunk and is exponentiated, so a bfloat16
        # rounding of it is a relative error of the state's whole horizon.
        f = _dense(cfg, H * d, "f_b_proj", dtype=f32)(
            _dense(cfg, d, "f_a_proj", dtype=f32)(x)
        )
        b = _dense(cfg, H, "b_proj", dtype=f32)(x)
        with tracing.scope(tracing.KDA_GATE):
            g = kda_gate(
                f.reshape(B, T, H, d),
                self.param("A_log", _a_log_init, (H,), f32),
                self.param("dt_bias", _dt_bias_init, (H * d,), f32).reshape(H, d),
            )
            beta = jax.nn.sigmoid(b)
            if cfg.kda_allow_neg_eigval:
                beta = 2.0 * beta
        gate = _dense(cfg, H * d, "g_b_proj", use_bias=True)(
            _dense(cfg, d, "g_a_proj")(x)
        )
        # q and k go to the scan raw, and so does the output gate: its
        # kernels normalise a head's channels on the blocks they hold (q's
        # and k's L2 norm with one rounding to v's dtype; o's RMSNorm and
        # gate with one rounding as it is stored).
        with tracing.scope(tracing.KDA_SCAN):
            o = chunk_kda(
                q, k, v, g, beta, gate.reshape(B, T, H, d),
                NormWeight(cfg.param_dtype, name="o_norm")(d),
                scale=d ** -0.5, rms_eps=cfg.rms_eps,
            )
        return _dense(cfg, cfg.hidden_size, "o_proj")(o.reshape(B, T, H * d))


class KimiLinearForCausalLM(MixtralForCausalLM):
    """The decoder body of llama.py with a mixer and an FFN chosen per
    layer (``KimiLinearConfig.layer_kinds``)."""

    blocks = {**MixtralForCausalLM.blocks, tracing.KDA: KDAMixer,
              tracing.MLA: MLAMixer}
