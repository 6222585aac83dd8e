"""MiniCPM-SALA's wrong programs, for ``reference_readings_of.py --wrong
benchmarks.tools.wrong_minicpm_sala``: each is another function than the
model, and the readings say which of them ``correct`` refuses on the chip
(``tests/test_minicpm_sala_model.py`` holds every one of them far from the
model in float32 on the CPU).

- ``system_topk_less_one``: the program choosing topk - 1 blocks;
  ``system_window_less_one``: its forced window one key (so one whole block)
  shorter; ``system_no_init_block``: the first block not forced;
  ``system_sparse_rotated``: q and k of the sparse layer turned (the plain
  table over the whole head), where the model turns nothing;
  ``system_lightning_unrotated``: the Lightning layers' q and k not turned;
  ``system_residual_of_held_layers``: the residual scale from the layers held
  (1.4 / sqrt(4)) and not the 32 published; ``system_logits_undivided``: the
  final norm's output not divided by hidden / dim_model_base;
- ``reference_mean_pool``: the reference with a block's score the mean of the
  compressed keys' that overlap it, not the largest;
  ``reference_per_head_scores``: each head choosing by its own scores, not a
  K/V group by their sum; ``reference_slopes_no_layer_factor``: the slopes
  without 1 - l / (L - 1); ``reference_state_bf16``: the recurrence's state
  rounded to bfloat16 after every token; ``reference_scores_bf16``: the
  selection's probabilities and their sum over a group rounded to bfloat16."""
from __future__ import annotations

import dataclasses
import math


def programs(cfg) -> dict:
    """name -> (the program's config,)."""
    sel = cfg.sparse
    sparse = lambda **change: (  # noqa: E731
        dataclasses.replace(cfg, sparse=dataclasses.replace(sel, **change)),)
    return {
        "system_topk_less_one": sparse(topk=sel.topk - 1),
        "system_window_less_one": sparse(window_size=sel.window_size - 1),
        "system_no_init_block": sparse(init_blocks=0),
        "system_sparse_rotated": (dataclasses.replace(cfg, attn_use_rope=True),),
        "system_lightning_unrotated": (
            dataclasses.replace(cfg, lightning_use_rope=False),),
        "system_residual_of_held_layers": (dataclasses.replace(
            cfg, residual_scale=cfg.residual_scale
            * math.sqrt(cfg.published_layers / cfg.num_layers)),),
        "system_logits_undivided": (dataclasses.replace(cfg, logit_divisor=1.0),),
    }


def references(bf16) -> dict:
    """name -> (one of the reference's functions, what replaces it given the
    plain one). ``bf16`` rounds an array to bfloat16's values."""
    import jax.numpy as jnp

    def mean_pool(_):
        def pool(scores, overlaps):
            total = jnp.where(overlaps, scores[..., None], 0.0).sum(-2)
            return total / jnp.maximum(overlaps.sum(0), 1)

        return pool

    def per_head(_):
        return lambda p: p

    def no_layer_factor(plain):
        return lambda cfg, layer: plain(cfg, 0) / (1.0 + 1e-5)

    def state_bf16(_):
        return bf16

    def scores_bf16(plain):
        return lambda p: bf16(plain(bf16(p)))

    return {
        "reference_mean_pool": ("pool", mean_pool),
        "reference_per_head_scores": ("over_group", per_head),
        "reference_slopes_no_layer_factor": ("lightning_slopes", no_layer_factor),
        "reference_state_bf16": ("state", state_bf16),
        "reference_scores_bf16": ("over_group", scores_bf16),
    }
