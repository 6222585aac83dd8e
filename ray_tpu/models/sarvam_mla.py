"""sarvam-105b (``model_type`` ``sarvam_mla``): latent attention in every
layer over a sigmoid-routed mixture of experts.

The model is llama.py's decoder body. Every layer's mixer is ``mla.py``'s
``MLAMixer`` with what Kimi-Linear's runs without: the 64-wide parts of q
and of the one shared key rotated under ``deepseek_yarn`` scaling, the
softmax scale times YaRN's ``mscale`` squared, and an RMSNorm over each
head's channels of q and of k (``use_qk_norm``). The first
``first_k_dense_replace`` layers have llama.py's dense ``MLP``; the others
mixtral.py's ``MoELayer`` told to score by sigmoid, to choose by score +
bias, to renormalise and scale its gates, to add a shared expert and to hold
a range of the router's experts. What the source's ``config.json`` leaves
open is listed in the benchmark's configuration file under ``assumed``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..util import tracing
from .mixtral import MixtralForCausalLM
from .mla import MLAConfig, MLAMixer, yarn_scaling


@dataclass(frozen=True)
class SarvamMLAConfig(MLAConfig):
    first_k_dense_replace: int = 1
    mla_rope: bool = True
    qk_head_norm: bool = True
    router_score: str = "sigmoid"
    moe_dispatch: str = "gmm"
    remat_policy: str = "nothing"
    # Without the barrier XLA merges each layer's replay with its forward
    # twin and keeps all five layers' residuals: 16.56 of 15.75 GiB at 4,096
    # tokens and the published widths (AOT compile, PERF.md §4).
    remat_prevent_cse: bool = True
    router_aux_loss_coef: float = 0.0

    @property
    def layers(self):
        return tuple(
            (tracing.MLA, tracing.MLP if i < self.first_k_dense_replace else tracing.MOE)
            for i in range(self.num_layers)
        )


def sarvam_mla_config(
    *, num_experts_held: int, expert_rank: int = 0,
    rope_scaling: Optional[dict] = None, use_qk_norm: bool = True, **fields,
) -> SarvamMLAConfig:
    """The program's config from the source's keys (its nested
    ``rope_scaling``, of a type ``mla.YARN_TYPES`` lists, and ``use_qk_norm``)
    and the deployment's: how many of the router's experts a rank holds, and
    which rank this is."""
    first = expert_rank * num_experts_held
    return SarvamMLAConfig(
        rope_scaling=yarn_scaling(rope_scaling), qk_head_norm=use_qk_norm,
        experts_held=(first, first + num_experts_held), **fields,
    )


class SarvamMLAForCausalLM(MixtralForCausalLM):
    """The decoder body of llama.py with ``MLAMixer`` in every layer and a
    dense or an expert FFN by the layer's index (``SarvamMLAConfig.layers``)."""

    blocks = {**MixtralForCausalLM.blocks, tracing.MLA: MLAMixer}
