"""Laguna (``model_type`` ``laguna``): sliding-window and full attention
layers mixed, each kind with a head count and a rotation of its own, over a
sigmoid-routed mixture of experts with a shared expert.

The model is llama.py's decoder body and every mixer is llama.py's
``Attention``, bound under two flax names: ``attn`` in the full-attention
layers and ``swa`` in the sliding ones. What a layer is it learns from the
config by that name (``LagunaConfig.attention``): the kind's head count over
the K/V heads all layers share; its rotation (the full layers': YaRN's table
over the leading ``partial_rotary_factor`` of a head, cos and sin times
``attention_factor``; the sliding layers': the plain table over the whole
head); the sliding layers' window; and one sigmoid gate a head and token on
the attention's output (``gating``). The FFN is llama.py's dense ``MLP`` or
mixtral.py's ``MoELayer`` by the source's ``mlp_layer_types``, the expert
layer told to score by sigmoid, to renormalise and scale its gates, to add
the shared expert and to hold a range of the router's experts. What the
source's ``config.json`` leaves open is listed in the benchmark's
configuration file under ``assumed``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp

from ..util import tracing
from .llama import AttentionKind, rope_frequencies
from .mixtral import MixtralConfig, MixtralForCausalLM
from .mla import yarn_frequencies

# The source's names of a layer's kind -> the mixer's flax name.
MIXER_OF = {"full_attention": tracing.ATTN, "sliding_attention": tracing.SWA}


@dataclass(frozen=True)
class LayerAttention:
    """One kind of layer's attention by the source's keys: its entry of
    ``num_attention_heads_per_layer`` and of ``rope_parameters`` (for
    ``rope_type`` ``yarn`` the keys ``mla.yarn_frequencies`` reads), and the
    window where the kind has one."""
    num_heads: int
    rope_theta: float
    rope_type: str = "default"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    window: Optional[int] = None


@dataclass(frozen=True)
class LagunaConfig(MixtralConfig):
    # Each layer's (mixer, ffn): "attn" or "swa", "mlp" or "moe".
    layer_kinds: Tuple[Tuple[str, str], ...] = ()
    # (mixer name, what its layers' attention is), one entry a kind.
    attentions: Tuple[Tuple[str, LayerAttention], ...] = ()
    gating: bool = True
    router_score: str = "sigmoid"
    moe_dispatch: str = "gmm"
    remat_policy: str = "nothing"
    # Without the barrier XLA merges each layer's replay with its forward
    # twin and keeps all seven expert layers' laid-out rows (536 MB each at
    # 16,384 tokens, top-8): 15.75 of 15.75 GiB at the published widths, and
    # the compile fails (AOT compile, PERF.md §4).
    remat_prevent_cse: bool = True
    router_aux_loss_coef: float = 0.0

    @property
    def layers(self):
        return self.layer_kinds

    def attention(self, name: Optional[str]) -> AttentionKind:
        kind = dict(self.attentions)[name]
        turning = int(self.head_dim_ * kind.partial_rotary_factor)
        if kind.rope_type == "yarn":
            freqs = jnp.asarray(yarn_frequencies(turning, kind.rope_theta, kind))
        else:
            freqs = rope_frequencies(turning, kind.rope_theta)
        return AttentionKind(
            kind.num_heads, freqs, kind.attention_factor, kind.window, self.gating
        )

    def num_params(self) -> int:
        """The parameters held, layer by layer: the kinds' head counts and
        the gate, a dense or an expert FFN with the experts held here."""
        h, hd = self.hidden_size, self.head_dim_
        first, past = self.experts_held or (0, self.num_experts)
        expert = 3 * h * self.expert_width
        ffn = {
            tracing.MLP: 3 * h * self.intermediate_size,
            # the router's weight and its selection bias
            tracing.MOE: (h + 1) * self.num_experts
            + (past - first + self.num_shared_experts) * expert,
        }
        total = self.vocab_size * h * (1 if self.tie_embeddings else 2) + h
        for mixer, kind in self.layer_kinds:
            heads = dict(self.attentions)[mixer].num_heads
            total += 2 * h * hd * (heads + self.num_kv_heads) + 2 * h + ffn[kind]
            total += h * heads if self.gating else 0
        return total


def laguna_config(
    *, layer_types, mlp_layer_types, num_attention_heads_per_layer,
    rope_parameters: dict, sliding_window: int,
    shared_expert_intermediate_size: int, num_experts_held: int,
    expert_rank: int = 0, **fields,
) -> LagunaConfig:
    """The program's config from the source's keys (each layer's kind, FFN
    kind and head count; the nested ``rope_parameters`` with one entry a
    kind; the window; the shared expert's width) and the deployment's: how
    many of the router's experts a rank holds, and which rank this is."""
    n = fields["num_layers"]
    attentions = {}
    for kind, heads in zip(layer_types[:n], num_attention_heads_per_layer[:n]):
        rope = dict(rope_parameters[kind])
        if rope.get("rope_type", "default") not in ("default", "yarn"):
            raise ValueError(f"rope_type {rope['rope_type']!r} is not supported")
        spec = LayerAttention(
            num_heads=heads,
            window=sliding_window if kind == "sliding_attention" else None,
            **rope,
        )
        if attentions.setdefault(MIXER_OF[kind], spec) != spec:
            raise ValueError(
                f"{kind} layers with different head counts: "
                f"{num_attention_heads_per_layer[:n]}"
            )
    shared, rest = divmod(
        shared_expert_intermediate_size, fields["moe_intermediate_size"]
    )
    if rest:
        raise ValueError(
            "the shared expert is whole multiples of a routed expert's width"
        )
    first = expert_rank * num_experts_held
    return LagunaConfig(
        layer_kinds=tuple(
            (MIXER_OF[kind], {"dense": tracing.MLP, "sparse": tracing.MOE}[ffn])
            for kind, ffn in zip(layer_types[:n], mlp_layer_types[:n])
        ),
        attentions=tuple(attentions.items()), num_shared_experts=shared,
        experts_held=(first, first + num_experts_held), **fields,
    )


class LagunaForCausalLM(MixtralForCausalLM):
    """The decoder body of llama.py with ``Attention`` as every layer's
    mixer, under the flax name of the layer's kind, and a dense or an expert
    FFN by the layer (``LagunaConfig.layers``)."""

    blocks = {
        **MixtralForCausalLM.blocks,
        tracing.SWA: MixtralForCausalLM.blocks[tracing.ATTN],
    }
