"""Solar-Open2's architecture through the program's models, on the CPU: the five
ranks' shares of an expert layer add up to the uncut layer, and the models
that share the mixers are bit for bit what they were
(``tests/test_solar_open2_model.py`` has the model against its reference and
says what the reference is; ``tests/solar_open2_cases.py`` what the files
share).
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.reference import solar_open2_decoder as reference
from ray_tpu.models.mixtral import MoELayer
from ray_tpu.models.solar_open2 import SolarOpen2Config

from solar_open2_cases import interpret  # noqa: F401 - fixtures


# ------------------------------------------------- the expert layer alone


def expert_layer(held):
    """One expert layer at Solar-Open2's routing: 20 experts scored, top-4,
    sigmoid, renormalised, x 1, one shared expert; ``held`` of them here."""
    cfg = SolarOpen2Config(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_experts=20, num_experts_per_tok=4, num_shared_experts=1,
        experts_held=held, initializer_range=0.5, held_rows="gather",
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return MoELayer(cfg)


def layer_config(held) -> dict:
    """The reference's keys for that layer."""
    lo, hi = held or (0, 20)
    return {"n_routed_experts_published": 20, "n_routed_experts": hi - lo,
            "expert_rank": lo // (hi - lo), "num_experts_per_tok": 4,
            "norm_topk_prob": True, "routed_scaling_factor": 1,
            "n_shared_experts": 1}


@pytest.mark.parametrize("taker", [None, 2], ids=["as-scored", "one-rank-takes-all"])
def test_the_five_ranks_shares_add_up_to_the_uncut_layer(taker):
    """Five ranks of four experts each, a rank count that is no power of two
    under a router whose width is no multiple of 128: the routed parts they
    give, with the shared expert (which every rank computes alike) counted
    once, are the uncut reference's expert layer; as the router scores at its
    initial values, and with a router that sends every pair to one rank's four
    experts and none to the sixteen others (the layout's bound, and a rank
    whose tiles hold padding alone)."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 48, 32)), jnp.float32)
    params = expert_layer(None).init(jax.random.PRNGKey(1), x)["params"]
    if taker is not None:
        # The reference reads no selection bias: one feature that every token
        # holds, and a router that scores it for one rank's experts alone.
        x = x.at[..., 0].set(4.0)
        scores = np.full(20, -5.0, np.float32)
        scores[4 * taker:4 * taker + 4] = 5.0
        kernel = params["router"]["kernel"].at[0].set(scores)
        params = {**params, "router": {"kernel": kernel}}
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.moe(params, tokens, layer_config(None))
        shared = reference.shared_expert(params, tokens)
        gates = np.asarray(reference.router_gates(params, tokens, layer_config(None)))
    total, pairs = 0.0, 0
    for rank in range(5):
        held = (4 * rank, 4 * rank + 4)
        mine = {**params, **{k: params[k][held[0]:held[1]]
                             for k in ("w_gate", "w_up", "w_down")}}
        out = expert_layer(held).apply({"params": mine}, x).reshape(-1, 32)
        with jax.default_matmul_precision("highest"):
            want = reference.moe(mine, tokens, layer_config(held))
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        mine_pairs = int((gates[:, held[0]:held[1]] > 0).sum())
        if taker is not None:
            assert mine_pairs == (96 * 4 if rank == taker else 0)
        pairs += mine_pairs
        total = total + (out - shared)
    assert pairs == 96 * 4  # every pair is held by exactly one rank
    np.testing.assert_allclose(total + shared, uncut, rtol=1e-4, atol=2e-5)
    # gates: four a token, renormalised, times 1
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-5)
    assert ((gates > 0).sum(-1) == 4).all()
    # and the uncut layer through the program is the reference's too
    whole = expert_layer(None).apply({"params": params}, x).reshape(-1, 32)
    np.testing.assert_allclose(whole, uncut, rtol=1e-4, atol=1e-5)


# ------------------- the models that share Attention, KDAMixer and MoELayer


def tree_digest(tree) -> tuple:
    """Names, shapes and dtypes of a parameter tree in one digest, and the
    number of leaves (tests/test_hybrid_layers.py's)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    text = ";".join(f"{jax.tree_util.keystr(p)}:{x.shape}:{x.dtype}" for p, x in flat)
    return hashlib.sha1(text.encode()).hexdigest()[:16], len(flat)


# Read by this same code at the parent of the PR that gave ``AttentionKind``
# "no rotation" and a gate of q's width and took ``KDAMixer`` off the
# latent-attention config (commit 7b31701), at each file's rehearsal size:
# the parameter tree's digest and leaves, and the sha1 of the lowered forward
# pass's text over [1, 128] ids, kernels interpreted. One text, one function:
# the same outputs bit for bit. A later change to one of these models on
# purpose reads its new values the same way: Kimi-Linear's text since
# ``KDAMixer`` convolves through ``ops/kda.py`` ``conv_silu``, whose two
# Pallas passes are interpreted here with the others ("6740a415a90863fc"
# before, with ``silu(short_conv)`` as XLA has it; tests/test_conv_silu_op.py holds
# the two to each other). The text is read without the counters JAX gives its
# private functions (``@silu_158``): a ``checkpoint_name`` lowers to nothing
# and moves them (models/llama.py REPLAY_KEEPS; these four digests read the
# same at that change's parent and after it). Laguna's text since
# ``ops/attention.py`` walks every mask by one forward (PR 56): its windowed
# forward kernel, interpreted here, finds its band's first and last block
# after the start and not before it, adds the step to the first once and not
# twice, and takes q's K/V head first in K's and V's index maps; the tile's
# operations are where they were ("cc97cf680f1bedbe" before; the logits and
# every gradient at this size are the parent's bit for bit, PERF.md §6,
# PR 56). The three others, which run the causal kernels alone, read the same.
# Laguna's again since its held eighth's rows, ``held_rows`` "gather", reach
# their slots over the used tiles alone (``_held_ffn`` with a gather back to
# tokens; "0e16e4b782b6a5a6" before, with every pair laid out): Kimi-Linear's
# and sarvam's, which walk, read what they read. Kimi-Linear's again since the
# scan's kernels are called through ``ops/attention.py`` ``kernel_entry``
# (PR 68): the four KDA layers share one trace of ``_forward_pallas``, so the
# matrix of running sums it builds (``_sum_matrix``) is one constant of the
# text where each layer's trace wrote its own ("c16ef491925e5adb" before: the
# same text but for three ``stablehlo.constant`` lines and the numbering after
# them). The three others hold no constant of a kernel's wrapper.
# Xing4's line was read the same way at the parent of the PR that made
# ``MLAMixer`` learn its widths, head count, window, gate, rescale and indexer
# from the layer's kind (``MLAConfig.latent``, PR 69), where Kimi-Linear's and
# sarvam's read what they read: the three models whose mixer it is lower to
# the text they lowered to. Laguna's and Xing4's again since a held share's
# rows come back to tokens by a kernel over tokens (``ops/gmm.py``
# ``pairs_summed``, PR 70; "ec64c261a184e22c" and "8f712a609e700b41" before,
# with a gather over every pair): the two of the five that gather. Kimi-Linear's
# and sarvam's, which walk, and Mistral's read what they read.
BEFORE = {
    "kimi-linear-48b-a3b-l5": ("4ed2711bc2778250", 117, "59821762e752310d"),
    "laguna-xs2-33b-a3b-l8": ("304ffe861753dc5a", 118, "a67063925f4a1684"),
    "mistral-7b-l4": ("06a35641bbb39a58", 21, "6ac84cd0523ca00f"),
    "sarvam-105b-l5": ("c710f6841e29dd3a", 83, "2a9ffca6aec4a4c6"),
    "xing4-29b-a4b-l5": ("f8dffe54740580c8", 164, "858b93108f6bfbdd"),
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_the_models_that_share_the_mixers_are_bit_for_bit_what_they_were(name):
    config = cells.load_json(f"{cells.BENCH_DIR}/configs/{name}.json")
    config = {**config, **config["rehearsal"]}
    model = cells.resolve(config["program"]["model"])(cells.program_config(config))
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    text = jax.jit(model.apply).lower(
        shapes, jax.ShapeDtypeStruct((1, 128), np.int32)).as_text()
    text = re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
    digest = hashlib.sha1(text.encode()).hexdigest()[:16]
    assert (*tree_digest(shapes), digest) == BEFORE[name]
