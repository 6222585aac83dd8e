"""Latent attention (DeepSeek-V2's MLA), the mixer of the models that have
it: Kimi-Linear's every fourth layer (``kimi_linear.py``) and every layer of
``sarvam_mla.py`` and of ``xing4.py``.

q heads of nope + pe from one projection, or through a latent of their own
(``q_lora_rank``); keys and values from a shared
latent (down-projection, RMSNorm, up-projection to nope + v a head) and one
pe-wide key part shared by all heads; softmax attention with q/k heads of
nope + pe and v heads of ``v_head_dim`` through the flash kernels, K and V
materialised (the training form: no absorbed projections, no latent cache).

What a model may add, each off where its config does not say so (Kimi-Linear
runs its MLA layers without any of them, ``mla_use_nope``):

- ``mla_rope``: the pe parts of q and of the one shared key are rotated,
  the nope parts never. The table is ``rope_frequencies`` or, under
  ``rope_scaling``, YaRN's (``yarn_frequencies``), and the softmax scale then
  carries ``yarn_mscale(factor, mscale_all_dim)`` squared.
- ``qk_head_norm``: an RMSNorm with a learned weight over each head's
  nope + pe channels of q and of k (the weight shared by the heads), before
  the rotation.
- ``q_lora_rank``: q through a latent as K and V are: a down-projection
  ``q_a_proj`` to that width, an RMSNorm, the up-projection ``q_b_proj`` to
  the heads, in place of the one ``q_proj`` (DeepSeek-V2's q latent).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from ..ops.attention import flash_attention
from ..ops.rotary import latent_qkv, latent_road
from ..util import tracing
from .llama import RMSNorm, _rope, rope_frequencies, weight_init
from .mixtral import MixtralConfig


@dataclass(frozen=True)
class YarnScaling:
    """``rope_scaling`` of type ``deepseek_yarn``, or of type ``yarn`` with
    the same keys, by the source's keys."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def __post_init__(self):
        # cos and sin would carry mscale over mscale_all_dim: no model here
        # has them apart, so ``_rope`` multiplies by nothing.
        if yarn_mscale(self.factor, self.mscale) != yarn_mscale(
            self.factor, self.mscale_all_dim
        ):
            raise ValueError(
                "rope_scaling with mscale apart from mscale_all_dim is not supported"
            )


@dataclass(frozen=True)
class MLAConfig(MixtralConfig):
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_rope: bool = False
    rope_scaling: Optional[YarnScaling] = None
    qk_head_norm: bool = False
    q_lora_rank: Optional[int] = None


# ``rope_scaling`` types whose keys and arithmetic ``YarnScaling`` has.
YARN_TYPES = ("deepseek_yarn", "yarn")


def yarn_scaling(rope_scaling: Optional[dict]) -> Optional[YarnScaling]:
    """The source's nested ``rope_scaling`` as ``YarnScaling``; None for none."""
    if rope_scaling is None:
        return None
    kind = rope_scaling["type"]
    if kind not in YARN_TYPES:
        raise ValueError(f"rope_scaling of type {kind!r} is not supported")
    return YarnScaling(**{k: v for k, v in rope_scaling.items() if k != "type"})


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, scaling: YarnScaling) -> np.ndarray:
    """YaRN's ``dim // 2`` frequencies: the plain ones where a channel pair
    turns more than ``beta_fast`` times over the original context, those
    over ``factor`` where it turns fewer than ``beta_slow`` times, a linear
    ramp over the pair index between the two."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_of(turns):  # the (real-valued) pair that turns this often
        return dim * math.log(
            scaling.original_max_position_embeddings / (2 * math.pi * turns)
        ) / (2 * math.log(theta))

    low = max(math.floor(pair_of(scaling.beta_fast)), 0)
    high = min(math.ceil(pair_of(scaling.beta_slow)), dim - 1)
    ramp = np.clip(
        (np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0
    )
    return (plain / scaling.factor * ramp + plain * (1 - ramp)).astype(np.float32)


class _NormWeight(nn.Module):
    """``RMSNorm``'s weight under ``RMSNorm``'s name, for the road that norms
    inside the kernel: the parameter tree is one whichever road runs."""
    width: int
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.width,), self.param_dtype)


class MLAMixer(nn.Module):
    cfg: MLAConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        H, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, pe, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        heads = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            (H, feats), axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name=name,
        )
        if cfg.q_lora_rank is None:
            q = heads(nope + pe, "q_proj")(x)  # [B, T, H, 192]: nope | pe
        else:
            with tracing.scope(tracing.MLA_Q_LATENT):
                c_q = nn.Dense(
                    cfg.q_lora_rank, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
                    name="q_a_proj",
                )(x)
                c_q = RMSNorm(cfg.rms_eps, cfg.param_dtype, name="q_a_norm")(c_q)
                q = heads(nope + pe, "q_b_proj")(c_q)
        with tracing.scope(tracing.MLA_LATENT):
            latent = nn.Dense(
                rank + pe, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
                name="kv_a_proj",
            )(x)
            c = RMSNorm(cfg.rms_eps, cfg.param_dtype, name="kv_a_norm")(
                latent[..., :rank]
            )
            kv = heads(nope + dv, "kv_b_proj")(c)  # [B, T, H, 256]: k nope | v
        norms, turns = cfg.qk_head_norm, cfg.mla_rope
        sm_scale = (nope + pe) ** -0.5
        freqs = None
        if turns:
            with tracing.scope(tracing.MLA_ROPE):
                scaling = cfg.rope_scaling
                if scaling is None:
                    freqs = rope_frequencies(pe, cfg.rope_theta)
                else:
                    freqs = jnp.asarray(
                        yarn_frequencies(pe, cfg.rope_theta, scaling)
                    )
                    sm_scale *= yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2
        # Which road follows from what the layer is and what it can see
        # (``ops/rotary.py`` ``latent_road``): one Pallas pass each for q and
        # for k where a head is 128 | 64 lanes, the lines below elsewhere.
        heads_first = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        B, T = x.shape[:2]
        if latent_road((B, H, T, nope + pe), (B, H, T, nope + dv), pe,
                       norms=norms, turns=turns) == "kernel":
            weights = (None, None)
            if norms:
                with tracing.scope(tracing.QK_NORM):
                    weights = tuple(
                        _NormWeight(nope + pe, cfg.param_dtype, name=name)()
                        for name in ("q_norm", "k_norm"))
            passes = functools.partial(
                latent_qkv, heads_first(q), heads_first(kv), latent[..., rank:],
                positions, freqs, *weights, cfg.rms_eps)
            if turns:
                with tracing.scope(tracing.MLA_ROPE):
                    q, k, v = passes()
            else:
                with tracing.scope(tracing.QK_NORM):
                    q, k, v = passes()
        else:
            with tracing.scope(tracing.MLA_LATENT):
                # The 64-wide key part is one for all heads.
                k_pe = jnp.broadcast_to(
                    latent[..., None, rank:], (*kv.shape[:3], pe)
                )
                k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
                v = kv[..., nope:]
            if norms:
                with tracing.scope(tracing.QK_NORM):
                    q = RMSNorm(cfg.rms_eps, cfg.param_dtype, name="q_norm")(q)
                    k = RMSNorm(cfg.rms_eps, cfg.param_dtype, name="k_norm")(k)
            q, k, v = (heads_first(t) for t in (q, k, v))
            if turns:
                with tracing.scope(tracing.MLA_ROPE):
                    q = _rope(q, positions, freqs)
                    k = _rope(k, positions, freqs)
        o = flash_attention(q, k, v, causal=True, sm_scale=sm_scale)
        return nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name="o_proj",
        )(o.transpose(0, 2, 1, 3))
