"""Solar-Open2 (``model_type`` ``solar_open2``): gated delta-rule linear
attention (KDA) layers with one softmax-attention layer to every three of
them, every layer over a sigmoid-routed mixture of experts with a shared
expert.

The model is llama.py's decoder body. The layers the source lists in
``gqa_layers`` (counted from 0: the first of every four) have llama.py's
``Attention`` as their mixer, of a kind ``SolarOpen2Config.attention`` gives:
grouped-query heads, no rotation (``use_rope`` false: position reaches these
layers through the KDA layers' state alone) and, under ``use_gqa_gate``, a
sigmoid gate of q's width on the attention's output, from the layer's normed
input, before ``o_proj``. The others have kimi_linear.py's ``KDAMixer``, told
by ``kda_allow_neg_eigval`` to write with beta = 2 sigmoid(W_b x) in (0, 2),
its two gate maps low-rank through the head dim (``kda_use_full_proj``
false; the full-rank form is refused). The FFN of every layer past the
first ``first_k_dense_replace`` is mixtral.py's ``MoELayer`` told to score by
sigmoid, to renormalise its gates, to add the shared expert and to hold a
range of the router's experts. What the source's ``config.json`` leaves open
is listed in the benchmark's configuration file under ``assumed``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

from ..util import tracing
from .kimi_linear import KDAConfig, KDAMixer
from .llama import AttentionKind, rope_frequencies
from .mixtral import MixtralConfig, MixtralForCausalLM


@dataclass(frozen=True)
class SolarOpen2Config(KDAConfig, MixtralConfig):
    # Each layer's (mixer, ffn): "attn" or "kda", "mlp" or "moe".
    layer_kinds: Tuple[Tuple[str, str], ...] = ()
    use_rope: bool = False
    use_gqa_gate: bool = True
    router_score: str = "sigmoid"
    moe_dispatch: str = "gmm"
    remat_policy: str = "nothing"
    # Each KDA layer's replay keeps 256 MiB of chunk states at 4k tokens and
    # 64 heads; without the barrier XLA keeps every layer's residuals.
    remat_prevent_cse: bool = True
    router_aux_loss_coef: float = 0.0

    @property
    def layers(self):
        return self.layer_kinds

    def attention(self, name: Optional[str]) -> AttentionKind:
        freqs = None
        if self.use_rope:
            freqs = rope_frequencies(self.head_dim_, self.rope_theta)
        return AttentionKind(
            self.num_heads, freqs, gate=self.use_gqa_gate,
            gate_channels=self.use_gqa_gate,
        )

    def num_params(self) -> int:
        """The parameters held, layer by layer: a KDA or a GQA mixer, a dense
        or an expert FFN with the experts held here."""
        h, hd = self.hidden_size, self.head_dim_
        H, d = self.kda_num_heads, self.kda_head_dim
        first, past = self.experts_held or (0, self.num_experts)
        expert = 3 * h * self.expert_width
        mixer = {
            # q, k, v, o; the two low-rank pairs and the second one's bias;
            # b; three filters; A_log, dt_bias, o_norm
            tracing.KDA: 4 * h * H * d + 2 * (h * d + d * H * d) + H * d
            + h * H + 3 * self.short_conv_kernel_size * H * d + H + H * d + d,
            # q, o and the gate at q's width; k and v
            tracing.ATTN: (2 + self.use_gqa_gate) * h * self.num_heads * hd
            + 2 * h * self.num_kv_heads * hd,
        }
        ffn = {
            tracing.MLP: 3 * h * self.intermediate_size,
            # the router's weight and its selection bias
            tracing.MOE: (h + 1) * self.num_experts
            + (past - first + self.num_shared_experts) * expert,
        }
        total = self.vocab_size * h * (1 if self.tie_embeddings else 2) + h
        return total + sum(
            mixer[m] + ffn[f] + 2 * h for m, f in self.layer_kinds
        )


def solar_open2_config(
    *, linear_attn_config: dict, gqa_layers, first_k_dense_replace: int,
    num_layers: int, num_experts_held: int, expert_rank: int = 0,
    kda_use_full_proj: bool = False, **fields,
) -> SolarOpen2Config:
    """The program's config from the source's keys (``gqa_layers``, counted
    from 0, the softmax-attention layers, every other layer KDA; the nested
    ``linear_attn_config`` with KDA's heads, head dim and convolution; the
    leading dense layers) and the deployment's: how many of the router's
    experts a rank holds, and which rank this is."""
    if kda_use_full_proj:
        raise ValueError(
            "kda_use_full_proj: KDAMixer's two gate maps are low-rank "
            "through the head dim"
        )
    heads = linear_attn_config["num_heads"]
    if linear_attn_config.get("num_kv_heads") not in (None, heads):
        raise ValueError("KDAMixer's v has q's head count")
    full = set(gqa_layers)
    first = expert_rank * num_experts_held
    return SolarOpen2Config(
        num_layers=num_layers,
        layer_kinds=tuple(
            (tracing.ATTN if i in full else tracing.KDA,
             tracing.MLP if i < first_k_dense_replace else tracing.MOE)
            for i in range(num_layers)
        ),
        kda_num_heads=heads,
        kda_head_dim=linear_attn_config["head_dim"],
        short_conv_kernel_size=linear_attn_config["short_conv_kernel_size"],
        experts_held=(first, first + num_experts_held), **fields,
    )


class SolarOpen2ForCausalLM(MixtralForCausalLM):
    """The decoder body of llama.py with ``Attention`` or ``KDAMixer`` as a
    layer's mixer (``SolarOpen2Config.layers``) over the expert layer."""

    blocks = {**MixtralForCausalLM.blocks, tracing.KDA: KDAMixer}
