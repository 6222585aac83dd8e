"""dots3-note-prev (``model_type`` ``dots3_note``): latent attention of two
kinds, full layers under a learned token-level top-k selection and sliding
layers of other widths under a window, a gate a head in both, over a
sigmoid-routed mixture of experts with a shared expert.

The model is llama.py's decoder body and every mixer is ``mla.py``'s
``MLAMixer``, bound under two flax names: ``mla`` in the full-attention
layers and ``swa_mla`` in the sliding ones. What a layer is it learns from
the config by that name (``Dots3Config.latent``): the kind's head count, its
latents' ranks and its heads' widths (the ``swa_*`` keys are the sliding
kind's), its rotation's theta, the sliding kind's window, the full kind's
lightning indexer (``index_*``), the gate (``*attention_gate_type``
``headwise``) and the latents' rescale (``apply_mla_qkv_lora_rescale``),
and the heads of the kind that this rank holds. The first
``first_k_dense_replace`` layers have llama.py's dense ``MLP``; the others
mixtral.py's ``MoELayer`` told to score by sigmoid, to renormalise and scale
its gates, to add the shared expert and to hold a range of the router's
experts. What the source's ``config.json`` leaves open is listed in the
benchmark's configuration file under ``assumed``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

from ..util import tracing
from .mixtral import MixtralForCausalLM
from .mla import Indexer, LatentKind, MLAConfig, MLAMixer

# The source's names of a layer's kind -> the mixer's flax name.
MIXER_OF = {"full_attention": tracing.MLA, "sliding_attention": tracing.SWA_MLA}


@dataclass(frozen=True)
class Dots3Config(MLAConfig):
    # Each layer's (mixer, ffn): "mla" or "swa_mla", "mlp" or "moe".
    layer_kinds: Tuple[Tuple[str, str], ...] = ()
    # (mixer name, what its layers' latent attention is), one entry a kind.
    latents: Tuple[Tuple[str, LatentKind], ...] = ()
    router_score: str = "sigmoid"
    moe_dispatch: str = "gmm"
    remat_policy: str = "nothing"
    # As the sibling families: without the barrier XLA merges each layer's
    # replay with its forward twin and keeps every layer's residuals.
    remat_prevent_cse: bool = True
    router_aux_loss_coef: float = 0.0

    @property
    def layers(self):
        return self.layer_kinds

    def latent(self, name: Optional[str]) -> LatentKind:
        return dict(self.latents)[name]

    def num_params(self) -> int:
        """The parameters held, layer by layer: each kind's mixer at the
        heads held (the latents, the gate and the indexer whole), a dense or
        an expert FFN with the experts held here."""
        h = self.hidden_size
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
            k = self.latent(mixer)
            heads, qk = k.heads_here, k.qk_nope_head_dim + k.qk_rope_head_dim
            total += h * k.q_lora_rank + k.q_lora_rank + k.q_lora_rank * heads * qk
            total += h * (k.kv_lora_rank + k.qk_rope_head_dim) + k.kv_lora_rank
            total += k.kv_lora_rank * heads * (k.qk_nope_head_dim + k.v_head_dim)
            total += heads * k.v_head_dim * h + (h * heads if k.gate else 0)
            if k.indexer is not None:
                i = k.indexer
                total += (k.q_lora_rank * i.num_heads * i.head_dim
                          + h * i.head_dim + 2 * i.head_dim + h * i.num_heads)
            total += 2 * h + ffn[kind]
        return total


def _held(count: int, held: Optional[int], rank: int) -> Optional[Tuple[int, int]]:
    if held is None or held == count:
        return None
    if count % held or not 0 <= rank < count // held:
        raise ValueError(f"rank {rank} of {held} held of {count}")
    return rank * held, (rank + 1) * held


def dots3_config(
    *, layer_types, first_k_dense_replace: int, num_heads_published: int,
    swa_num_heads_published: int, swa_num_heads: int, q_lora_rank: int,
    swa_q_lora_rank: int, swa_kv_lora_rank: int, swa_qk_nope_head_dim: int,
    swa_qk_rope_head_dim: int, swa_v_head_dim: int, swa_rope_theta: float,
    sliding_window_size: int, index_n_heads: int, index_head_dim: int,
    index_topk: int, attention_gate_type: Optional[str],
    swa_attention_gate_type: Optional[str], apply_mla_qkv_lora_rescale: bool,
    num_experts_held: int, expert_rank: int = 0, head_rank: int = 0,
    index_norm_eps: float = 1e-6, **fields,
) -> Dots3Config:
    """The program's config from the source's keys (each layer's kind, the
    full kind's widths under the plain keys and the sliding kind's under
    ``swa_*``, the indexer's, the window, the gates' types, the rescale) and
    the deployment's: how many of the router's experts and of each kind's
    heads a rank holds (``num_heads``, ``swa_num_heads`` of the published
    counts), and which rank this is of each."""
    for gate in (attention_gate_type, swa_attention_gate_type):
        if gate not in (None, "headwise"):
            raise ValueError(f"attention gate of type {gate!r} is not supported")
    n = fields["num_layers"]
    common = dict(mla_rope=True, rescale=apply_mla_qkv_lora_rescale)
    latents = {
        tracing.MLA: LatentKind(
            num_heads_published, fields["kv_lora_rank"],
            fields["qk_nope_head_dim"], fields["qk_rope_head_dim"],
            fields["v_head_dim"], fields["rope_theta"],
            q_lora_rank=q_lora_rank, gate=attention_gate_type is not None,
            indexer=Indexer(index_n_heads, index_head_dim, index_topk,
                            index_norm_eps),
            heads_held=_held(num_heads_published, fields["num_heads"], head_rank),
            **common),
        tracing.SWA_MLA: LatentKind(
            swa_num_heads_published, swa_kv_lora_rank, swa_qk_nope_head_dim,
            swa_qk_rope_head_dim, swa_v_head_dim, swa_rope_theta,
            q_lora_rank=swa_q_lora_rank, window=sliding_window_size,
            gate=swa_attention_gate_type is not None,
            heads_held=_held(swa_num_heads_published, swa_num_heads, head_rank),
            **common),
    }
    first = expert_rank * num_experts_held
    return Dots3Config(
        layer_kinds=tuple(
            (MIXER_OF[kind],
             tracing.MLP if i < first_k_dense_replace else tracing.MOE)
            for i, kind in enumerate(layer_types[:n])
        ),
        latents=tuple(latents.items()),
        experts_held=(first, first + num_experts_held), **fields,
    )


class Dots3ForCausalLM(MixtralForCausalLM):
    """The decoder body of llama.py with ``MLAMixer`` as every layer's
    mixer, under the flax name of the layer's kind, and a dense or an expert
    FFN by the layer (``Dots3Config.layers``)."""

    blocks = {
        **MixtralForCausalLM.blocks,
        tracing.MLA: MLAMixer, tracing.SWA_MLA: MLAMixer,
    }
