"""Xing4.0-29B-A4B (``model_type`` ``xing4_0``): latent attention with a q
latent in every layer over a sigmoid-routed mixture of experts, on a residual
path of four hyper-connected streams, with one multi-token-prediction module.

The model is llama.py's decoder body. Every layer's mixer is ``mla.py``'s
``MLAMixer`` with a q latent (``q_lora_rank``) and its 64-wide parts rotated
under YaRN (``rope_scaling`` of type ``yarn``, DeepSeek's keys); no QK norm.
The first ``first_k_dense_replace`` layers have the dense ``MLP``, the others
mixtral.py's ``MoELayer`` as ``sarvam_mla.py`` tells it (sigmoid scores, the
top k of score + bias, gates renormalised and scaled, a shared expert, a held
range of the router's experts). The residual path is
``hyper_connections.py``'s (``hc_mult`` streams, Sinkhorn-projected): the
embedding is copied to the streams and their sum goes to the final norm.

The multi-token-prediction module is DeepSeek-V3's (arXiv:2412.19437 §2.2),
one deep: at position i, h' = M [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] with
h_i the main model's hidden state before its final norm; one more expert
layer on h', hyper-connected like the others (h' copied to the streams, their
sum taken after); an RMSNorm; the main model's head predicts t_{i+2}.
Embedding and head are the main model's. ``model.apply(params, ids)`` gives
the main head's logits; given the next tokens too, with ``return_hidden``, it
gives both heads' normed hidden states, which ``mtp_chunked_lm_loss`` takes
through the shared head a chunk at a time. What the source's ``config.json``
leaves open is listed in the benchmark's configuration file under ``assumed``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ..util import tracing
from .hyper_connections import HyperConnections
from .llama import (
    RMSNorm, _embedding, _logits, _lookup, _positions, _through,
    chunked_head_loss, lm_head_weight, weight_init,
)
from .mla import yarn_scaling
from .sarvam_mla import SarvamMLAConfig, SarvamMLAForCausalLM


@dataclass(frozen=True)
class Xing4Config(SarvamMLAConfig):
    first_k_dense_replace: int = 2
    qk_head_norm: bool = False
    q_lora_rank: Optional[int] = 768
    hyper_connections: Optional[HyperConnections] = HyperConnections()
    num_nextn_predict_layers: int = 1

    def __post_init__(self):
        if self.num_nextn_predict_layers != 1:
            raise ValueError("one multi-token-prediction module, no more and no fewer")


def xing4_config(
    *, num_experts_held: int, expert_rank: int = 0,
    rope_scaling: Optional[dict] = None, hc_mult: int = 4,
    hc_sinkhorn_iters: int = 20, hc_eps: float = 1e-6,
    hc_clamp_min: float = -30.0, hc_clamp_max: float = 30.0,
    hc_alpha_init: float = 1.0, hc_res_diagonal_init: float = 2.0, **fields,
) -> Xing4Config:
    """The program's config from the source's keys (its nested
    ``rope_scaling``, its ``hc_*`` and ``mhc_h_res_clamp_*``), the initial
    values the benchmark's file assumes, and the deployment's: how many of
    the router's experts a rank holds, and which rank this is."""
    first = expert_rank * num_experts_held
    return Xing4Config(
        rope_scaling=yarn_scaling(rope_scaling),
        experts_held=(first, first + num_experts_held),
        hyper_connections=HyperConnections(
            mult=hc_mult, sinkhorn_iters=hc_sinkhorn_iters, eps=hc_eps,
            clamp=(hc_clamp_min, hc_clamp_max), alpha_init=hc_alpha_init,
            res_diagonal_init=hc_res_diagonal_init,
        ),
        **fields,
    )


class Xing4ForCausalLM(SarvamMLAForCausalLM):
    """llama.py's decoder body on hyper-connected streams, and the
    multi-token-prediction module beside its final norm."""

    @nn.compact
    def __call__(self, input_ids, positions=None, return_hidden=False,
                 next_ids=None):
        """``next_ids`` [B, T], the token after each of ``input_ids``: with
        ``return_hidden`` the result is then (the main head's hidden states,
        the module's), both normed. Initialisation runs the module too, so
        that its parameters exist."""
        cfg = self.cfg
        if positions is None:
            positions = _positions(input_ids)
        emb = _embedding(cfg)
        h = _through(
            self, [(f"{tracing.LAYER}{i}", *kinds) for i, kinds in enumerate(cfg.layers)],
            _lookup(cfg, emb, input_ids), positions,
        )
        x = RMSNorm(cfg.rms_eps, cfg.param_dtype, name=tracing.FINAL_NORM)(h)
        if next_ids is None and self.is_initializing():
            next_ids = jnp.roll(input_ids, -1, axis=1)
        if next_ids is not None:
            predicted = _predict_further(self, emb, h, next_ids, positions)
            if return_hidden:
                return x, predicted
        return x if return_hidden else _logits(cfg, emb, x)


def _predict_further(model: Xing4ForCausalLM, emb, h, next_ids, positions):
    """The module's normed hidden states [B, T, C], inside the model's
    ``__call__``: position i's stands for the token after ``next_ids[i]``."""
    cfg = model.cfg
    norm = lambda name: RMSNorm(cfg.rms_eps, cfg.param_dtype, name=name)  # noqa: E731
    with tracing.scope(tracing.MTP):
        joined = jnp.concatenate([
            norm(tracing.MTP_HIDDEN_NORM)(h),
            norm(tracing.MTP_EMBED_NORM)(_lookup(cfg, emb, next_ids)),
        ], axis=-1)
        x = nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name=tracing.MTP_PROJ,
        )(joined)
        x = _through(model, [(tracing.MTP_LAYER, tracing.MLA, tracing.MOE)], x, positions)
        return norm(tracing.MTP_NORM)(x)


def mtp_chunked_lm_loss(model: Xing4ForCausalLM, params, input_ids, targets,
                        chunk_size: int = 2048, mtp_weight: float = 0.3):
    """Next-token cross-entropy plus ``mtp_weight`` times the module's, the
    cross-entropy of the token after the next over the positions that have
    one (every one but a sequence's last), each the mean over its positions
    and each through the shared head a chunk at a time, one after the other:
    no [T, V] logits array is alive beside another. ``targets`` are the ids
    shifted by one."""
    hidden, predicted = model.apply(
        params, input_ids, return_hidden=True, next_ids=targets
    )
    head = lm_head_weight(params)
    loss = chunked_head_loss(hidden, head, targets, None, chunk_size)
    with tracing.scope(tracing.MTP):
        has_target = jnp.arange(targets.shape[1]) < targets.shape[1] - 1
        further = chunked_head_loss(
            predicted, head, jnp.roll(targets, -1, axis=1), has_target[None],
            chunk_size,
        )
    with tracing.scope(tracing.LOSS):
        return loss + mtp_weight * further
