"""dots3-note-prev's wrong programs, for ``reference_readings_of.py --wrong
benchmarks.tools.wrong_dots3``: each has to read far from the reference, or
PERF.md §7 says that the comparison cannot see it and which CPU test does.

- ``system_no_rescale``: the program with neither latent times (hidden /
  rank) ** 0.5 after its norm; ``system_no_gate``: the attention's output not
  gated, in both kinds; ``system_window_512`` and ``system_window_514``: the
  sliding layers' band one key narrower and one wider; ``system_top_2047``:
  each row of a full layer keeping one key fewer; ``system_no_selection``: the
  full layers attending every key up to the row's own, no indexer;
- ``reference_index_bf16``: the reference with the indexer's products, their
  ReLUs and the scores that the threshold is taken over rounded to bfloat16
  (the precision below the float32 the configuration states for them: rows
  then tie in heaps and keep every key of a heap); ``reference_router_bf16``:
  the reference with the router's matmul and sigmoid rounded to bfloat16."""
from __future__ import annotations

import dataclasses


def programs(cfg) -> dict:
    """name -> (the program's config, the mixer's parameters it lacks)."""
    def kinds(only=None, **changes):
        return dataclasses.replace(cfg, latents=tuple(
            (name, dataclasses.replace(kind, **changes)
             if only in (None, name) else kind)
            for name, kind in cfg.latents))

    full = dict(cfg.latents)["mla"]
    window = dict(cfg.latents)["swa_mla"].window
    fewer = dataclasses.replace(full.indexer, topk=full.indexer.topk - 1)
    index = ("index_q_proj", "index_k_proj", "index_k_norm", "index_w_proj")
    return {
        "system_no_rescale": (kinds(rescale=False),),
        "system_no_gate": (kinds(gate=False), ("g_proj",)),
        "system_window_512": (kinds("swa_mla", window=window - 1),),
        "system_window_514": (kinds("swa_mla", window=window + 1),),
        "system_top_2047": (kinds("mla", indexer=fewer),),
        "system_no_selection": (kinds("mla", indexer=None), index),
    }


def references(bf16) -> dict:
    """name -> (one of the reference's functions, what replaces it given the
    plain one). ``bf16`` rounds an array to bfloat16's values."""
    import jax
    import jax.numpy as jnp

    def index_bf16(plain):
        def chosen_keys(q_i, k_i, w, rows, topk):
            t = k_i.shape[0]
            products = bf16(jnp.einsum("qhd,kd->hqk", bf16(q_i), bf16(k_i)))
            scores = bf16(jnp.einsum("qh,hqk->qk", w, jax.nn.relu(products)))
            visible = jnp.arange(t)[None, :] <= rows[:, None]
            scores = jnp.where(visible, scores, -jnp.inf)
            if t <= topk:
                return visible
            return visible & (scores >= jax.lax.top_k(scores, topk)[0][:, -1:])

        return chosen_keys

    def router_bf16(plain):
        def router_gates(p, x, c):
            sigmoid = jax.nn.sigmoid
            jax.nn.sigmoid = lambda logits: bf16(sigmoid(bf16(logits)))
            try:
                rounded = {**p, "router": {"kernel": bf16(p["router"]["kernel"].astype(jnp.float32))}}
                return plain(rounded, bf16(x), c)
            finally:
                jax.nn.sigmoid = sigmoid

        return router_gates

    return {
        "reference_index_bf16": ("chosen_keys", index_bf16),
        "reference_router_bf16": ("router_gates", router_bf16),
    }
