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

What a layer is it learns from the config by its own flax name
(``MLAConfig.latent`` -> ``LatentKind``), as llama.py's ``Attention`` does:
one kind a model in the three families above, and in ``dots3.py`` two, the
full layers' (``mla``) and the sliding ones' (``swa_mla``), each with widths,
a head count and a rotation of its own. A kind may also have:

- ``rescale``: each latent times (hidden / its rank) ** 0.5 after its norm.
- ``window``: row i sees keys 0 <= i - j < window (``flash_attention``'s band).
- ``gate``: o_h times sigmoid(x W_g)_h, one value a head and token, before
  ``o_proj`` (llama.py's ``AttentionKind.gate``).
- ``indexer``: DeepSeek-V3.2's lightning indexer. Index queries from the q
  latent, one index key a token from x through a LayerNorm, the heads'
  weights from x, the leading ``pe`` channels of both rotated as the layer
  rotates; ``ops/attention.py`` ``index_keys`` scores every (row, key) pair
  and keeps each row's ``topk`` highest, and every head's soft-max runs over
  those alone (``flash_attention(keys=)``). Nothing of it is differentiated,
  and its parameters take a zero gradient.
- ``heads_held``: the heads [first, past the last) of the kind's
  ``num_heads`` that this device holds, one tensor-parallel rank's share:
  the up-projections, the gate and ``o_proj`` are built at those heads alone,
  the layer's output is their part of ``o_proj``'s sum over heads and nothing
  stands in for the rest. The latents' down-projections, their norms and the
  indexer are every rank's alike and whole.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import flash_attention, index_keys
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
class Indexer:
    """The lightning indexer by the source's keys (``index_n_heads``,
    ``index_head_dim``, ``index_topk``)."""
    num_heads: int
    head_dim: int
    topk: int
    # The index key's LayerNorm (DeepSeek-V3.2's; a benchmark file lists it
    # under ``assumed``).
    norm_eps: float = 1e-6


@dataclass(frozen=True)
class LatentKind:
    """What one layer's latent attention is, as ``MLAConfig.latent`` gives it
    (the module's docstring says what each field adds)."""
    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    mla_rope: bool = False
    rope_scaling: Optional[YarnScaling] = None
    qk_head_norm: bool = False
    q_lora_rank: Optional[int] = None
    rescale: bool = False
    window: Optional[int] = None
    gate: bool = False
    indexer: Optional[Indexer] = None
    heads_held: Optional[Tuple[int, int]] = None

    @property
    def heads_here(self) -> int:
        first, past = self.heads_held or (0, self.num_heads)
        return past - first


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

    def latent(self, name: Optional[str]) -> LatentKind:
        """What the ``MLAMixer`` bound under the flax name ``name`` is. One
        kind here; a family whose layers differ tells them apart by the
        names its ``layers`` gives their mixers (dots3.py)."""
        return LatentKind(
            self.num_heads, self.kv_lora_rank, self.qk_nope_head_dim,
            self.qk_rope_head_dim, self.v_head_dim, self.rope_theta,
            self.mla_rope, self.rope_scaling, self.qk_head_norm,
            self.q_lora_rank,
        )


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


def _chosen_keys(cfg, indexer: Indexer, x, c_q, positions, freqs, dense, heads):
    """The words of the keys each row attends (``index_keys``), from the
    layer's normed input and its q latent; inside ``MLAMixer.__call__``, whose
    ``dense`` and ``heads`` make the projections (a function and no method:
    flax would put a method's name into every operation's path)."""
    if c_q is None or freqs is None:
        raise ValueError("an indexer reads the q latent and the layer's rotation")
    x, c_q = jax.lax.stop_gradient(x), jax.lax.stop_gradient(c_q)
    n, d = indexer.num_heads, indexer.head_dim
    with tracing.scope(tracing.INDEXER):
        q_i = heads(d, "index_q_proj", n)(c_q).transpose(0, 2, 1, 3)
        k_i = nn.LayerNorm(
            epsilon=indexer.norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="index_k_norm",
        )(dense(d, "index_k_proj")(x))
        w = dense(n, "index_w_proj")(x).astype(jnp.float32) * (n * d) ** -0.5
        q_i = _rope(q_i, positions, freqs, leading=True)
        k_i = _rope(k_i[:, None], positions, freqs, leading=True)[:, 0]
    with tracing.scope(tracing.SPARSE_SELECT):
        return index_keys(q_i, k_i, w, topk=indexer.topk)


class MLAMixer(nn.Module):
    cfg: MLAConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        kind = cfg.latent(self.name)
        H, rank = kind.heads_here, kind.kv_lora_rank
        nope, pe, dv = kind.qk_nope_head_dim, kind.qk_rope_head_dim, kind.v_head_dim
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name=name,
        )
        heads = lambda feats, name, n=H: nn.DenseGeneral(  # noqa: E731
            (n, feats), axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name=name,
        )

        def rescaled(c, width):  # apply_mla_qkv_lora_rescale
            if not kind.rescale:
                return c
            return (c.astype(jnp.float32)
                    * math.sqrt(cfg.hidden_size / width)).astype(c.dtype)

        c_q = None
        if kind.q_lora_rank is None:
            q = heads(nope + pe, "q_proj")(x)  # [B, T, H, 192]: nope | pe
        else:
            with tracing.scope(tracing.MLA_Q_LATENT):
                c_q = dense(kind.q_lora_rank, "q_a_proj")(x)
                c_q = RMSNorm(cfg.rms_eps, cfg.param_dtype, name="q_a_norm")(c_q)
                c_q = rescaled(c_q, kind.q_lora_rank)
                q = heads(nope + pe, "q_b_proj")(c_q)
        with tracing.scope(tracing.MLA_LATENT):
            latent = dense(rank + pe, "kv_a_proj")(x)
            c = RMSNorm(cfg.rms_eps, cfg.param_dtype, name="kv_a_norm")(
                latent[..., :rank]
            )
            kv = heads(nope + dv, "kv_b_proj")(rescaled(c, rank))  # [B, T, H, 256]: k nope | v
        norms, turns = kind.qk_head_norm, kind.mla_rope
        sm_scale = (nope + pe) ** -0.5
        freqs = None
        if turns:
            with tracing.scope(tracing.MLA_ROPE):
                scaling = kind.rope_scaling
                if scaling is None:
                    freqs = rope_frequencies(pe, kind.rope_theta)
                else:
                    freqs = jnp.asarray(
                        yarn_frequencies(pe, kind.rope_theta, scaling)
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
        keys = None
        if kind.indexer is not None:
            keys = _chosen_keys(cfg, kind.indexer, x, c_q, positions, freqs, dense, heads)
        o = flash_attention(q, k, v, causal=True, sm_scale=sm_scale,
                            window=kind.window, keys=keys)
        o = o.transpose(0, 2, 1, 3)
        if kind.gate:
            with tracing.scope(tracing.ATTN_GATE):
                gate = nn.sigmoid(dense(H, "g_proj")(x).astype(jnp.float32))
                o = o * gate[..., None].astype(o.dtype)
        return nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name="o_proj",
        )(o)
