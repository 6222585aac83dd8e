"""LFM2-MoE (``model_type`` ``lfm2_moe``): gated short-convolution mixers
with one grouped-query attention layer to every three of them, the leading
layers over a dense SwiGLU and every other over a sigmoid-routed mixture of
experts, the head tied to the embedding.

The model is llama.py's pre-norm decoder body. What ``layer_types`` calls
``full_attention`` has llama.py's ``Attention`` as its mixer, of a kind
``Lfm2Config.attention`` gives: grouped-query heads, an RMSNorm over each
head's channels of q and of k under one weight each before the rotation, the
whole head turned. What it calls ``conv`` has ``ShortConvMixer``:

    [B | C | x~] = x W_in          W_in [hidden, 3 hidden], thirds in that order
    c_t = sum_i w[i] (B x~)_{t - (K - 1) + i}     depthwise, causal, K = conv_L_cache taps
    mixer(x) = (C c) W_out         no activation, no bias, no positions

whose middle line with both gates is ``ops/kda.py``'s ``gated_conv``: the
thirds read where they lie in W_in's output, one pass forward and one back.
The FFN of source layer i is llama.py's ``MLP`` at ``intermediate_size`` for i
< ``num_dense_layers`` and else mixtral.py's ``MoELayer`` told to score by
sigmoid, to choose by score plus a selection bias no gradient reaches
(``use_expert_bias``), to renormalise its gates and to hold every expert. A
stage of a pipeline holds the source's layers ``first_layer`` .. ``first_layer
+ num_layers - 1`` and names them layers_0 on. What the source's
``config.json`` leaves open is listed in the benchmark's configuration file
under ``assumed``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn

from ..ops.kda import gated_conv
from ..util import tracing
from .kimi_linear import _conv_init, _dense
from .llama import AttentionKind, rope_frequencies
from .mixtral import MixtralConfig, MixtralForCausalLM

CONV, FULL_ATTENTION = "conv", "full_attention"


@dataclass(frozen=True)
class Lfm2Config(MixtralConfig):
    # Each layer's (mixer, ffn): "shortconv" or "attn", "mlp" or "moe".
    layer_kinds: Tuple[Tuple[str, str], ...] = ()
    # The filter's taps (``conv_L_cache``).
    conv_taps: int = 3
    router_score: str = "sigmoid"
    moe_dispatch: str = "gmm"
    remat_policy: str = "nothing"
    router_aux_loss_coef: float = 0.0
    tie_embeddings: bool = True
    rms_eps: float = 1e-5

    @property
    def layers(self):
        return self.layer_kinds

    def attention(self, name: Optional[str]) -> AttentionKind:
        return AttentionKind(
            self.num_heads, rope_frequencies(self.head_dim_, self.rope_theta),
            qk_head_norm=True,
        )

    def num_params(self) -> int:
        """The parameters held, layer by layer."""
        h, hd = self.hidden_size, self.head_dim_
        first, past = self.experts_held or (0, self.num_experts)
        mixer = {
            # in_proj, the filter, out_proj
            tracing.SHORTCONV: 3 * h * h + self.conv_taps * h + h * h,
            # q and o; k and v; the two norms' weights
            tracing.ATTN: 2 * h * self.num_heads * hd
            + 2 * h * self.num_kv_heads * hd + 2 * hd,
        }
        ffn = {
            tracing.MLP: 3 * h * self.intermediate_size,
            # the router's weight and its selection bias
            tracing.MOE: (h + 1) * self.num_experts
            + (past - first) * 3 * h * self.expert_width,
        }
        total = self.vocab_size * h * (1 if self.tie_embeddings else 2) + h
        return total + sum(
            mixer[m] + ffn[f] + 2 * h for m, f in self.layer_kinds
        )


def lfm2_config(
    *, layer_types, num_dense_layers: int, num_layers: int, conv_L_cache: int,
    first_layer: int = 0, conv_bias: bool = False, use_expert_bias: bool = True,
    **fields,
) -> Lfm2Config:
    """The program's config from the source's keys (``layer_types``, each
    layer ``conv`` or ``full_attention``; the ``num_dense_layers`` leading
    layers over a dense SwiGLU; ``conv_L_cache`` taps) and the deployment's:
    the first of the source's layers held here, ``num_layers`` of them. The
    model is built as published (no filter bias, a selection bias on the
    router): a key that says otherwise is refused."""
    if conv_bias or not use_expert_bias:
        raise ValueError("ShortConvMixer's filter has no bias, and the "
                         "sigmoid router chooses by score plus a bias")
    held = list(layer_types[first_layer:first_layer + num_layers])
    kinds = {CONV: tracing.SHORTCONV, FULL_ATTENTION: tracing.ATTN}
    unknown = set(held) - set(kinds)
    if unknown or len(held) < num_layers:
        raise ValueError(
            f"layer_types names {sorted(unknown)} or is short of layers "
            f"{first_layer} to {first_layer + num_layers - 1}")
    return Lfm2Config(
        num_layers=num_layers,
        layer_kinds=tuple(
            (kinds[t], tracing.MLP if first_layer + i < num_dense_layers else tracing.MOE)
            for i, t in enumerate(held)
        ),
        conv_taps=conv_L_cache, **fields,
    )


class ShortConvMixer(nn.Module):
    """The gated short convolution, a layer's whole mixer: two tokens of
    state and no positions."""
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h = cfg.hidden_size
        with tracing.scope(tracing.SHORTCONV_IN):
            p = _dense(cfg, 3 * h, "in_proj")(x)
        with tracing.scope(tracing.SHORTCONV_GATED):
            y = gated_conv(p, self.param(
                "conv", _conv_init, (cfg.conv_taps, h), cfg.param_dtype), cfg.dtype)
        with tracing.scope(tracing.SHORTCONV_OUT):
            return _dense(cfg, h, "out_proj")(y)


class Lfm2ForCausalLM(MixtralForCausalLM):
    """The decoder body of llama.py with ``ShortConvMixer`` or ``Attention``
    as a layer's mixer over the dense ``MLP`` or the expert layer
    (``Lfm2Config.layers``)."""

    blocks = {**MixtralForCausalLM.blocks, tracing.SHORTCONV: ShortConvMixer}
