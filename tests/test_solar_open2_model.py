"""Solar-Open2's architecture through the program's models, on the CPU.

``SolarOpen2ForCausalLM`` (one period: a softmax layer of grouped-query
attention without rotation under an element-wise output gate, then three KDA
layers that write with beta in (0, 2), each over an expert layer with
sigmoid routing, a shared expert and one expert-parallel rank's share of the
routed experts through the ``gmm`` dispatch, all kernels interpreted) against
the benchmark's plain reference
(``benchmarks/reference/solar_open2_decoder.py``) at the configuration file's
rehearsal size on seeded random weights in float32: logits, loss and every
gradient. The programs and references of ``benchmarks/tools/wrong_solar.py``,
each another function; the five ranks' shares of one expert layer against the
uncut reference; the configuration file against the catalog's row; and the
models that share ``Attention`` and ``KDAMixer``, whose parameter trees and
lowered forward passes are what they were.
"""
import dataclasses
import hashlib
import importlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import solar_open2_decoder as reference
from benchmarks.tools import wrong_solar
from ray_tpu.models.llama import chunked_causal_lm_loss
from ray_tpu.models.mixtral import MoELayer
from ray_tpu.models.solar_open2 import SolarOpen2Config, SolarOpen2ForCausalLM
from ray_tpu.util import tracing

SEQ = 128
CONFIG = f"{cells.BENCH_DIR}/configs/solar-open2-250b-l4.json"
# Past these a float32 program is another function than the reference.
FAR = {"per_position_rel_err": 1e-3, "min_share_within": 0.5}


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted, and
    # with them the scan kernels of ops/kda.py and, at 128 rows, the flash
    # kernels.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


@pytest.fixture(scope="module")
def solar():
    """(configuration dict at its rehearsal size, model, params, ids): 4 q
    heads over 2 K/V heads of 32, 4 KDA heads of 32, 20 experts top-4 of
    which 4 are held, float32."""
    config = cells.load_json(CONFIG)
    config = {**config, **config["rehearsal"]}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": "float32",
                "param_dtype": "float32"},
    }
    model = SolarOpen2ForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    # b_proj's draw of 0.02 leaves beta within 0.25 of 1: widen it, so that
    # beta runs over (0, 2) and a doubling left out is far from the model.
    p = dict(params["params"])
    for i in (1, 2, 3):
        kda = dict(p[f"layers_{i}"]["kda"])
        kda["b_proj"] = {"kernel": kda["b_proj"]["kernel"] * 12.0}
        p[f"layers_{i}"] = {**p[f"layers_{i}"], "kda": kda}
    return config, model, {"params": p}, ids


@pytest.fixture(scope="module")
def expected(solar):
    config, _, params, ids = solar
    return reference.forward(params, ids, config, SEQ)


def test_the_configuration_builds_solar_open2s_program(solar):
    config, model, params, _ = solar
    cfg = model.cfg
    # the full layer first in a period: the source counts gqa_layers from 0
    assert cfg.layers == ((tracing.ATTN, "moe"),) + ((tracing.KDA, "moe"),) * 3
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.num_shared_experts, cfg.moe_dispatch, cfg.held_rows) == (
        "sigmoid", True, 1, 1, "gmm", "gather")
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (20, (0, 4), 4)
    assert (cfg.use_rope, cfg.use_gqa_gate, cfg.kda_allow_neg_eigval) == (False, True, True)
    p = params["params"]
    assert set(p["layers_0"]) == {"input_norm", "attn", "post_attn_norm", "moe"}
    assert set(p["layers_1"]) == {"input_norm", "kda", "post_attn_norm", "moe"}
    attn = p["layers_0"]["attn"]
    assert set(attn) == {"q_proj", "k_proj", "v_proj", "g_proj", "o_proj"}
    assert attn["q_proj"]["kernel"].shape == (128, 4, 32)
    assert attn["k_proj"]["kernel"].shape == (128, 2, 32)
    assert attn["g_proj"]["kernel"].shape == (128, 4, 32)  # a gate of q's width
    assert set(p["layers_1"]["kda"]) == {
        "q_proj", "k_proj", "v_proj", "q_conv", "k_conv", "v_conv", "f_a_proj",
        "f_b_proj", "b_proj", "A_log", "dt_bias", "g_a_proj", "g_b_proj",
        "o_norm", "o_proj"}
    assert p["layers_1"]["moe"]["router"]["kernel"].shape == (128, 20)
    assert p["layers_1"]["moe"]["w_gate"].shape == (4, 128, 64)  # the experts held
    # and at the published sizes the file gives the published architecture
    full = cells.program_config(cells.load_json(CONFIG))
    assert full.layers == cfg.layers
    assert (full.hidden_size, full.expert_width, full.num_heads, full.num_kv_heads,
            full.head_dim_, full.kda_num_heads, full.kda_head_dim,
            full.short_conv_kernel_size, full.rms_eps, full.vocab_size,
            full.num_layers, full.tie_embeddings) == (
        4096, 1280, 64, 8, 128, 64, 128, 4, 1e-5, 24576, 4, False)
    assert (full.num_experts, full.experts_held, full.num_experts_per_tok,
            full.num_shared_experts, full.routed_scaling_factor) == (
        320, (0, 8), 8, 1, 1)
    kind = full.attention(tracing.ATTN)
    assert (kind.num_heads, kind.freqs, kind.window, kind.gate, kind.gate_channels) == (
        64, None, None, True, True)
    rotated = dataclasses.replace(full, use_rope=True).attention(tracing.ATTN)
    assert rotated.freqs.shape == (64,)


@pytest.mark.parametrize("published", [False, True], ids=["tiny", "published"])
def test_num_params_counts_layer_by_layer(solar, published):
    model = solar[1]
    if published:
        model = SolarOpen2ForCausalLM(cells.program_config(cells.load_json(CONFIG)))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    held = sum(math.prod(leaf.shape) for leaf in jax.tree_util.tree_leaves(shapes))
    assert model.cfg.num_params() == held
    if published:  # the issue's 1,295 M: 9.65 GiB at 8 bytes a parameter
        assert 1.2950e9 < held < 1.2953e9
        p = shapes["params"]
        count = lambda tree: sum(  # noqa: E731
            math.prod(leaf.shape) for leaf in jax.tree_util.tree_leaves(tree))
        assert round(count(p["layers_1"]["kda"]) / 1e5) == 1377  # 137.7 M
        assert round(count(p["layers_0"]["attn"]) / 1e5) == 1091  # 109.1 M


@pytest.mark.parametrize("change,message", [
    ({"kda_use_full_proj": True}, "low-rank"),
    ({"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                             "num_heads": 64, "num_kv_heads": 8}}, "head count"),
], ids=["full-rank gate maps", "fewer value heads"])
def test_what_the_mixer_cannot_build_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        cells.program_config({**cells.load_json(CONFIG), **change})


# -------------------------------------------- the model against the reference


def test_logits_agree_with_the_reference_in_float32(solar, expected):
    _, model, params, ids = solar
    system = jax.jit(model.apply)(params, ids[None])[0]
    assert system.dtype == jnp.float32
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 2e-5, "min_share_within": 1.0})
    assert result["ok"], result


def test_beta_runs_over_zero_to_two(solar):
    """The fixture's b_proj reaches both ends: the comparisons above and
    below are of a model that writes with beta > 1.9 and < 0.1 somewhere."""
    config, _, params, ids = solar
    p = params["params"]
    x = p["embed_tokens"]["embedding"][ids]
    h = reference.rms_norm(x, p["layers_1"]["input_norm"]["scale"], 1e-5)
    beta = np.asarray(reference.write_strength(p["layers_1"]["kda"], h, config))
    assert beta.max() > 1.9 and beta.min() < 0.1 and (beta > 1).mean() > 0.25


def without(params, names):
    """The tree less the mixers' parameters ``names``."""
    return {"params": {
        layer: {k: {n: w for n, w in v.items() if n not in names}
                if k in tracing.MIXERS else v for k, v in sub.items()}
        if layer.startswith("layers_") else sub
        for layer, sub in params["params"].items()}}


@pytest.mark.parametrize("name", [
    "system_beta_undoubled", "system_no_gate", "system_rotated"])
def test_a_wrong_program_is_far_from_the_reference(solar, expected, name):
    _, model, params, ids = solar
    cfg, *drop = wrong_solar.programs(model.cfg)[name]
    if drop:
        params = without(params, drop[0])
    system = jax.jit(SolarOpen2ForCausalLM(cfg).apply)(params, ids[None])[0]
    result = logits_agreement(system, expected, FAR)
    assert not result["ok"], result


def bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def beta_capped(plain):
    return lambda p, x, c: jnp.minimum(plain(p, x, c), 1.0)


@pytest.mark.parametrize("function,replacement", [
    *wrong_solar.references(bf16).values(),
    ("write_strength", beta_capped),
], ids=[*wrong_solar.references(bf16), "reference_beta_capped_at_one"])
def test_a_wrong_reference_is_far_from_the_program(
        solar, expected, monkeypatch, function, replacement):
    """One gate value a head, a router over the held experts alone, a
    bfloat16 state, beta capped at 1: each moves the logits past what float32
    leaves between the program and the reference."""
    config, _, params, ids = solar
    monkeypatch.setattr(
        reference, function, replacement(getattr(reference, function)))
    other = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(other, expected, FAR)
    assert not result["ok"], result


@pytest.fixture(scope="module")
def both_gradients(solar):
    config, model, params, ids = solar
    targets = np.roll(ids, -1)
    system = jax.jit(jax.value_and_grad(
        lambda p: chunked_causal_lm_loss(
            model, p, ids[None], targets[None], chunk_size=64)
    ))(params)
    wanted = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, ids, targets, config)
    ))(params)
    return system, wanted


def test_the_chunked_loss_agrees_with_the_reference(both_gradients):
    (loss, _), (wanted, _) = both_gradients
    assert float(loss) == pytest.approx(float(wanted), rel=1e-5)


def test_every_gradient_agrees_with_the_references(both_gradients):
    (_, grads), (_, wanted) = both_gradients
    flat = dict(jax.tree_util.tree_leaves_with_path(grads["params"]))
    checked = 0
    for path, want in jax.tree_util.tree_leaves_with_path(wanted["params"]):
        got, want = np.asarray(flat[path]), np.asarray(want)
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            assert not got.any() and not want.any()  # no gradient reaches it
            continue
        assert got.shape == want.shape and np.abs(want).max() > 0, name
        np.testing.assert_allclose(
            got, want, rtol=5e-3, atol=5e-5 * np.abs(want).max(), err_msg=name)
        checked += 1
    # a layer: 2 norms and 7 expert-layer weights; the GQA mixer's 5 weights,
    # a KDA mixer's 16 (g_b_proj has a bias); embedding, final norm, head
    assert checked == 4 * 9 + 5 + 3 * 16 + 3


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


# --------------------------------------------------- the configuration file


def test_the_file_holds_every_published_key_and_lists_exactly_what_it_cut():
    """Against the catalog's row where the catalog is installed, else the
    sizes the issue names."""
    cfg = cells.load_json(CONFIG)
    bench = cells.load_json(f"{cells.ROOT}/BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    cut = {"num_hidden_layers": (48, 4), "n_routed_experts": (320, 8),
           "vocab_size": (196608, 24576)}
    assert set(cfg["reduced"]) == set(entry["reduced"]) == set(cut)
    assert entry["source"] == cfg["source"] and len(cfg["source"]) < 200
    for key, (source, here) in cut.items():
        assert (cfg["reduced"][key]["source"], cfg["reduced"][key]["here"],
                cfg[key], cfg[key + "_published"]) == (source, here, here, source)
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["routed_scaling_factor"], cfg["rms_norm_eps"]) == (
        4096, 128, 64, 8, 1280, 8, 1, 1, 1e-5)
    assert cfg["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert cfg["gqa_layers"] == list(range(0, 48, 4))
    for key in ("gqa_gate_width", "router_score", "selection_bias_and_groups",
                "gate_low_rank_width", "kda_initialisers", "initializer_range",
                "router_dtype", "intermediate_size", "hidden_act"):
        assert key in cfg["assumed"], key
    gate = cfg["assumed"]["gqa_gate_width"]  # the count both ways
    assert "250.29 B" in gate and "14.74 B" in gate and "249.89 B" in gate
    assert "over 40 chips" in cfg["deployment"] and "Layers 0-3" in cfg["deployment"]
    assert cfg["program"]["set"] == {
        "param_dtype": "bfloat16", "dtype": "bfloat16", "remat": True,
        "remat_policy": "nothing", "moe_dispatch": "gmm",
        "router_score": "sigmoid", "held_rows": "gather"}
    cell = next(w for w in bench["workloads"] if w["config"] == cfg["name"])
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b-l4.pretrain-4k", "pretrain-4k", 1)
    assert sum(w["config"] == cfg["name"] for w in bench["workloads"]) == 1
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [cells.json.loads(line) for line in open(catalog)]
    except OSError:
        return
    published = next(r for r in rows if r["name"] == "Solar-Open2-250B")
    assert published["source_url"] == cfg["source"]
    differs = {k for k, v in published["config"].items() if cfg.get(k, "absent") != v}
    assert differs == set(cut)


def test_the_cells_flops_and_kernels_follow_the_layers():
    cell = cells.load_cell("solar-open2-250b-l4.pretrain-4k")
    flops = importlib.import_module("benchmarks.lib.flops_solar")
    config, seq = cell["config"], 4096
    assert flops.layer_kinds(config) == [("attn", "moe")] + [("kda", "moe")] * 3
    per_token = flops.solar_open2_decoder(config, seq)
    assert 18.3e12 < per_token * seq < 18.5e12  # 18.4 TFLOP a step
    # by hand: matmul parameters a token passes through, attention, the scan
    h, d, w = 4096, 128, 1280
    kda = 4 * h * 64 * d + 2 * (h * d + d * 64 * d) + h * 64
    gqa = 3 * h * 64 * d + 2 * h * 8 * d
    sparse = h * 320 + 3 * h * w + 8 * 8 / 320 * 3 * h * w
    params = 3 * kda + gqa + 4 * sparse + h * 24576
    assert per_token == pytest.approx(
        6 * params + 6.0 * seq * 64 * d + 3 * 64 * 21 * d * d, rel=1e-12)
    assert 0.55 < (18 * kda + 3 * 64 * 21 * d * d) / per_token < 0.58  # KDA's share
    stated = cells.stated_kernels(cell)
    assert {k: s["least"] for k, s in stated.items()} == {
        "_fwd_kernel": 1, "_bwd_dkv_kernel": 1, "_bwd_dq_kernel": 1,
        "_kda_fwd_kernel": 3, "_kda_bwd_kernel": 3,
        "_gmm_kernel": 24, "_tgmm_kernel": 12}
    # a causal call at 64 q heads (K and V repeated to them), a scan at 64
    assert stated["_fwd_kernel"]["call"][0] == 2.0 * 64 * seq * seq / 2 * 2 * d
    kda_flops = importlib.import_module("benchmarks.lib.flops_kda")
    assert stated["_kda_fwd_kernel"]["call"] == kda_flops.kda_call(
        "_kda_fwd_kernel", 64, seq, d, d)
    # the cell reports the mixers' readers and they find nothing in an empty run
    names = {m["name"] for m in cell["per_layer"]}
    assert {"model.gqa_share", "model.attn_gate_share", "model.kda_share",
            "kernel.kda_share", "kernel.kda_roofline", "kernel.flash_roofline",
            "model.moe_share", "kernel.gmm_share",
            "trainer.step_ms_p95_over_p50"} <= names
    assert not {"kernel.gmm_roofline", "model.mla_share", "model.swa_share"} & names
    for name in ("model.gqa_share", "model.attn_gate_share"):
        reader = cells.load_reader(f"{cells.BENCH_DIR}/layer_metrics", name)
        assert reader.read({"trace_data": None}) is None


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
# before, with ``silu(short_conv)`` as XLA has it; tests/test_kda_op.py holds
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
