"""dots3-note-prev's architecture through the program's models, on the CPU.

``Dots3ForCausalLM`` (a leading dense layer and one period of one full layer
to three sliding ones, both kinds latent attention at widths of their own, the
full kind under the lightning indexer's token-level top-k, the sliding kind
under a window, a gate a head, expert layers with sigmoid routing, a shared
expert and one rank's share of the routed experts and of the heads, all
kernels interpreted) against the benchmark's plain reference
(``benchmarks/reference/dots3_note_decoder.py``) at a tiny size on seeded
random weights in float32: logits, loss and gradients. A hand-written line
each for the rescale, the headwise gate, the band and the threshold with
ties; the selection where a row has no more keys than ``index_topk``; the
fourth mask's kernels and the indexer kernel against explicit masks; every
rank's share of a full layer and of an expert layer against the uncut
reference; the wrong programs and references of
``benchmarks/tools/wrong_dots3.py``; the configuration file against the
catalog's row.
"""
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import dots3_note_decoder as reference
from benchmarks.tools import wrong_dots3
from ray_tpu.models.dots3 import Dots3Config, Dots3ForCausalLM
from ray_tpu.models.llama import chunked_causal_lm_loss
from ray_tpu.models.mixtral import MoELayer
from ray_tpu.models.mla import Indexer, LatentKind, MLAMixer
from ray_tpu.ops import attention
from ray_tpu.util import tracing

SEQ = 128
CONFIG = f"{cells.BENCH_DIR}/configs/dots3-note-prev-l5.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Past these a float32 program is another function than the reference.
FAR = {"per_position_rel_err": 1e-3, "min_share_within": 0.5}
INDEX = ("index_q_proj", "index_k_proj", "index_k_norm", "index_w_proj")


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted, and
    # at 128 rows so are the indexer, the selection's and the band's kernels.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def tiny(**changes) -> dict:
    """The file at its rehearsal widths in float32: 128 positions choose 48
    keys of up to 128 in the full layers and see 40 in the sliding ones."""
    config = cells.load_json(CONFIG)
    config = {**config, **config["rehearsal"], "index_topk": 48,
              "sliding_window_size": 40, "num_experts_per_tok": 2, **changes}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": "float32",
                "param_dtype": "float32"},
    }
    return config


@pytest.fixture(scope="module")
def dots3():
    config = tiny()
    model = Dots3ForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    return config, model, params, ids


@pytest.fixture(scope="module")
def expected(dots3):
    config, _, params, ids = dots3
    return reference.forward(params, ids, config, SEQ)


def test_the_configuration_builds_dots3s_program(dots3):
    _, model, params, _ = dots3
    cfg = model.cfg
    assert cfg.layers == ((tracing.MLA, "mlp"), (tracing.MLA, "moe")) + (
        (tracing.SWA_MLA, "moe"),) * 3
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.num_shared_experts, cfg.moe_dispatch) == ("sigmoid", True, 1, 1, "gmm")
    assert (cfg.num_experts, cfg.experts_held) == (16, (0, 4))
    p = params["params"]
    assert set(p["layers_0"]) == {"input_norm", "mla", "post_attn_norm", "mlp"}
    assert set(p["layers_1"]) == {"input_norm", "mla", "post_attn_norm", "moe"}
    assert set(p["layers_4"]) == {"input_norm", "swa_mla", "post_attn_norm", "moe"}
    latent = {"q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj", "kv_a_norm",
              "kv_b_proj", "g_proj", "o_proj"}
    assert set(p["layers_1"]["mla"]) == latent | set(INDEX)
    assert set(p["layers_2"]["swa_mla"]) == latent
    full, sliding = p["layers_1"]["mla"], p["layers_2"]["swa_mla"]
    # the heads held (2 of 8) in the up-projections, the gate and o_proj; the
    # latents and the indexer whole
    assert full["q_b_proj"]["kernel"].shape == (32, 2, 32)
    assert full["kv_b_proj"]["kernel"].shape == (32, 2, 32)
    assert full["g_proj"]["kernel"].shape == (128, 2)
    assert full["o_proj"]["kernel"].shape == (2, 16, 128)
    assert full["index_q_proj"]["kernel"].shape == (32, 4, 32)
    assert full["index_k_proj"]["kernel"].shape == (128, 32)
    assert full["index_w_proj"]["kernel"].shape == (128, 4)
    assert sliding["kv_a_proj"]["kernel"].shape == (128, 48 + 16)
    assert sliding["q_b_proj"]["kernel"].shape == (32, 2, 48)
    assert sliding["kv_b_proj"]["kernel"].shape == (48, 2, 32 + 16)
    # and at the published sizes the file gives the published architecture
    published = cells.program_config(cells.load_json(CONFIG))
    assert published.layers == cfg.layers
    assert (published.hidden_size, published.intermediate_size,
            published.expert_width, published.rms_eps, published.vocab_size,
            published.num_layers, published.tie_embeddings) == (
        5120, 13824, 1536, 1e-5, 19008, 5, False)
    assert (published.num_experts, published.experts_held,
            published.num_experts_per_tok, published.num_shared_experts) == (
        256, (0, 8), 8, 1)
    assert dict(published.latents) == {
        tracing.MLA: LatentKind(
            128, 512, 128, 64, 128, 80000000, mla_rope=True, q_lora_rank=1024,
            rescale=True, gate=True, indexer=Indexer(64, 128, 2048, 1e-6),
            heads_held=(0, 32)),
        tracing.SWA_MLA: LatentKind(
            64, 1024, 192, 64, 128, 50000, mla_rope=True, q_lora_rank=1024,
            rescale=True, window=513, gate=True, heads_held=(0, 16)),
    }


@pytest.mark.parametrize("published", [False, True], ids=["tiny", "published"])
def test_num_params_counts_layer_by_layer(dots3, published):
    model = dots3[1]
    if published:
        model = Dots3ForCausalLM(cells.program_config(cells.load_json(CONFIG)))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    held = sum(math.prod(leaf.shape) for leaf in jax.tree_util.tree_leaves(shapes))
    assert model.cfg.num_params() == held
    if published:  # 1,452 M: 8.12 GiB of weights and moments
        assert 1.452e9 < held < 1.453e9


def test_a_gate_of_another_type_is_refused():
    with pytest.raises(ValueError, match="elementwise"):
        cells.program_config(tiny(attention_gate_type="elementwise"))


def test_logits_agree_with_the_reference_in_float32(dots3, expected):
    _, model, params, ids = dots3
    logits = model.apply(params, ids[None])[0]
    found = logits_agreement(logits, expected, {
        "per_position_rel_err": 2e-5, "min_share_within": 1.0})
    assert found["ok"], found


def without(params, names):
    return {"params": {
        layer: {mixer: {k: v for k, v in sub.items() if k not in names}
                if mixer in tracing.MIXERS else sub for mixer, sub in held.items()}
        if layer.startswith("layers_") else held
        for layer, held in params["params"].items()}}


@pytest.mark.parametrize("name", [
    "system_no_rescale", "system_no_gate", "system_window_512",
    "system_window_514", "system_top_2047", "system_no_selection"])
def test_a_wrong_program_is_far_from_the_reference(dots3, expected, name):
    _, model, params, ids = dots3
    cfg, *drop = wrong_dots3.programs(model.cfg)[name]
    logits = Dots3ForCausalLM(cfg).apply(
        without(params, drop[0]) if drop else params, ids[None])[0]
    found = logits_agreement(logits, expected, FAR)
    assert not found["ok"], found


@pytest.mark.parametrize("name,far", [
    # bfloat16 scores move the rows whose threshold they cross, and at 128
    # positions those are few: none may be far where the float32 program lies
    # within 2e-5 at every one
    ("reference_index_bf16", {**FAR, "min_share_within": 1.0}),
    ("reference_router_bf16", FAR)])
def test_a_wrong_reference_is_far_from_the_program(dots3, name, far, monkeypatch):
    config, model, params, ids = dots3
    function, replacement = wrong_dots3.references(
        lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7))[name]
    monkeypatch.setattr(reference, function, replacement(getattr(reference, function)))
    wrong = reference.forward(params, ids, config, SEQ)
    found = logits_agreement(model.apply(params, ids[None])[0], wrong, far)
    assert not found["ok"], found


@pytest.fixture(scope="module")
def both_gradients(dots3):
    config, model, params, ids = dots3
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
    checked = frozen = 0
    for path, want in jax.tree_util.tree_leaves_with_path(wanted["params"]):
        got, want = np.asarray(flat[path]), np.asarray(want)
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']") or "['index_" in name:
            # no gradient reaches the selection bias, and none the indexer:
            # what it chooses is a set, and no alignment loss trains it
            assert not got.any() and not want.any(), name
            frozen += 1
            continue
        assert got.shape == want.shape and np.abs(want).max() > 0, name
        np.testing.assert_allclose(
            got, want, rtol=5e-3, atol=5e-5 * np.abs(want).max(), err_msg=name)
        checked += 1
    # 2 norms a layer, 8 latent weights, 3 dense or 7 expert-layer weights
    # (the bias apart); embedding, final norm, head
    assert checked == 5 * 10 + 3 + 4 * 7 + 3
    assert frozen == 4 + 2 * 5  # a bias an expert layer; 5 leaves an indexer


# ------------------------------------------------ a hand-written line each


def one_mixer(kind: LatentKind, seq=SEQ, hidden=64, seed=0):
    cfg = Dots3Config(
        hidden_size=hidden, latents=((tracing.MLA, kind),),
        initializer_range=0.3, dtype=jnp.float32, param_dtype=jnp.float32)
    x = jnp.asarray(
        np.random.default_rng(seed).normal(size=(1, seq, hidden)), jnp.float32)
    positions = jnp.arange(seq)[None]
    mixer = MLAMixer(cfg, name=tracing.MLA)
    params = mixer.init(jax.random.PRNGKey(seed), x, positions)
    return mixer, params, x, positions


KIND = LatentKind(4, 32, 16, 16, 16, 1e4, mla_rope=True, q_lora_rank=24)


def test_the_rescale_is_each_latent_times_the_root_of_hidden_over_its_rank():
    """c_q x (64 / 24)^1/2 and c x (64 / 32)^1/2: the same as the plain layer
    with the factors in the up-projections that read the latents."""
    plain, params, x, positions = one_mixer(KIND)
    rescaled = one_mixer(dataclasses.replace(KIND, rescale=True))[0]
    p = params["params"]
    folded = {"params": {
        **p,
        "q_b_proj": {"kernel": p["q_b_proj"]["kernel"] * (64 / 24) ** 0.5},
        "kv_b_proj": {"kernel": p["kv_b_proj"]["kernel"] * (64 / 32) ** 0.5},
    }}
    np.testing.assert_allclose(
        rescaled.apply(params, x, positions), plain.apply(folded, x, positions),
        rtol=2e-5, atol=3e-5)


def test_the_gate_is_one_sigmoid_a_head_and_token_before_o_proj():
    """out = sum_n (o_n sigmoid(x W_g)_n) W_o[n]: with W_g = 0 half the plain
    layer's; with head 0's column far below zero, the plain layer's without
    head 0."""
    plain, params, x, positions = one_mixer(KIND)
    gated = one_mixer(dataclasses.replace(KIND, gate=True))[0]
    out = plain.apply(params, x, positions)
    p = params["params"]
    zero = {"params": {**p, "g_proj": {"kernel": jnp.zeros((64, 4))}}}
    np.testing.assert_allclose(
        gated.apply(zero, x, positions), 0.5 * out, rtol=2e-5, atol=1e-6)
    # x has a constant channel: its column of W_g is a bias a head
    x1 = x.at[..., 0].set(1.0)
    shut = jnp.zeros((64, 4)).at[0].set(jnp.asarray([-40.0, 40.0, 40.0, 40.0]))
    headless = {"params": {
        **p, "o_proj": {"kernel": p["o_proj"]["kernel"].at[0].set(0.0)}}}
    np.testing.assert_allclose(
        gated.apply({"params": {**p, "g_proj": {"kernel": shut}}}, x1, positions),
        plain.apply(headless, x1, positions), rtol=2e-5, atol=1e-6)


def test_a_window_of_513_is_the_row_and_the_512_before_it():
    """Row t sees keys t - 512 .. t: the reference's band, by hand, and the
    program's ``flash_attention(window=)`` under it at 1,100 rows."""
    t, window = 1100, 513
    ahead = np.arange(t)[:, None] - np.arange(t)[None, :]
    band = (ahead >= 0) & (ahead <= 512)
    assert band.sum(1).tolist() == [min(i + 1, 513) for i in range(t)]
    assert band[1000].nonzero()[0][[0, -1]].tolist() == [488, 1000]
    np.testing.assert_array_equal(attention._visible(t, t, window), band)
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1, t, 8)), jnp.float32)
               for _ in range(3))
    s = np.einsum("td,sd->ts", q[0, 0], k[0, 0]) * 8 ** -0.5
    p = np.where(band, np.exp(s - s.max(1, keepdims=True)), 0.0)
    want = (p / p.sum(1, keepdims=True)) @ np.asarray(v[0, 0])
    got = attention.flash_attention(q, k, v, window=window)[0, 0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def chosen_by_hand(scores: np.ndarray, topk: int) -> np.ndarray:
    """Row t keeps {s <= t : I[t, s] >= the topk-th largest of I[t, :t+1]}."""
    t = scores.shape[0]
    seen = np.zeros((t, t), bool)
    for row in range(t):
        mine = scores[row, :row + 1]
        least = np.sort(mine)[::-1][min(topk, row + 1) - 1]
        seen[row, :row + 1] = mine >= least
    return seen


@pytest.mark.parametrize("t", [96, 256], ids=["xla", "kernel"])
def test_the_threshold_keeps_ties(t):
    """One index head of one channel, w = 1: I[t, s] = ReLU(q_t k_s), and with
    q and k from a few small integers whole heaps of keys score alike (and
    half score 0): a row keeps every key at its threshold, so more than
    ``topk`` where the threshold's heap is cut."""
    rng = np.random.default_rng(5)
    q = rng.integers(1, 3, size=(1, 1, t, 8)).astype(np.float32) * (np.arange(8) == 0)
    k = rng.integers(-2, 4, size=(1, t, 8)).astype(np.float32) * (np.arange(8) == 0)
    w = np.ones((1, t, 1), np.float32)
    topk = 24
    words = attention.index_keys(jnp.asarray(q), jnp.asarray(k), jnp.asarray(w), topk=topk)
    scores = np.maximum(q[0, 0, :, :1] * k[0, :, 0][None, :], 0.0)
    want = chosen_by_hand(scores, topk)
    np.testing.assert_array_equal(attention._unpack_keys(words, t)[0], want)
    kept = want.sum(1)
    assert (kept[:topk] == np.arange(1, topk + 1)).all()  # every key while few
    assert (kept >= np.minimum(np.arange(t) + 1, topk)).all() and kept.max() > topk


@pytest.mark.parametrize("t", [64, 160], ids=["xla", "kernel"])
def test_the_selection_of_a_short_sequence_is_the_causal_mask(t):
    rng = np.random.default_rng(6)
    q, k = rng.normal(size=(1, 2, t, 16)), rng.normal(size=(1, t, 16))
    w = rng.normal(size=(1, t, 2))
    words = attention.index_keys(
        *(jnp.asarray(a, jnp.float32) for a in (q, k, w)), topk=t)
    np.testing.assert_array_equal(
        attention._unpack_keys(words, t)[0], np.tril(np.ones((t, t), bool)))


# ------------------------------------- the fourth mask's kernels, interpreted


def explicit(q, k, v, seen, scale):
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * scale
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


@pytest.mark.parametrize("t,topk", [(256, 100), (300, 64), (1280, 200)])
def test_the_selection_kernels_agree_with_an_explicit_mask(t, topk):
    """The indexer kernel's words against the scores' own threshold, and the
    three flash kernels under them (forward, dK/dV, dQ) against a masked
    soft-max and its autodiff: 1,280 rows are two tiles of 1,024 with a dead
    one above the diagonal, 300 a padded tile of 256."""
    rng = np.random.default_rng(t)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q, k, v = f32(1, 2, t, 24), f32(1, 2, t, 24), f32(1, 2, t, 16)
    q_i, k_i, w = f32(1, 3, t, 16), f32(1, t, 16), f32(1, t, 3)
    words = attention.index_keys(q_i, k_i, w, topk=topk)
    scores = np.asarray(attention.index_scores(q_i, k_i, w))[0]
    seen = chosen_by_hand(scores, topk)
    np.testing.assert_array_equal(attention._unpack_keys(words, t)[0], seen)
    seen, scale = jnp.asarray(seen)[None], 24 ** -0.5
    weight = jnp.cos(jnp.arange(16.0))
    got, back = jax.value_and_grad(
        lambda *qkv: (attention.flash_attention(
            *qkv, keys=words, sm_scale=scale) * weight).sum(), (0, 1, 2))(q, k, v)
    want, wanted = jax.value_and_grad(
        lambda *qkv: (explicit(*qkv, seen, scale) * weight).sum(), (0, 1, 2))(q, k, v)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(back, wanted):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


def test_keys_are_refused_where_they_have_no_meaning():
    q = jnp.zeros((1, 2, 64, 8))
    words = attention._pack_keys(jnp.ones((1, 64, 64), bool), 128)
    with pytest.raises(ValueError, match="keys= is causal"):
        attention.flash_attention(q, q[:, :1], q[:, :1], keys=words)
    with pytest.raises(ValueError, match="keys= is causal"):
        attention.flash_attention(q, q, q, keys=words, window=8)


# ------------------------------------------------------- the shares add up


def test_the_head_ranks_shares_add_up_to_the_uncut_full_layer():
    """Four ranks of two heads each of a full layer: what each gives of
    o_proj's sum, from the same latents and the same selection (every rank
    computes those alike), adds up to the reference's layer at all 8 heads."""
    kind = LatentKind(
        8, 32, 16, 16, 16, 1e4, mla_rope=True, q_lora_rank=24, rescale=True,
        gate=True, indexer=Indexer(2, 16, 40))
    _, params, x, positions = one_mixer(kind, seq=SEQ)
    p = params["params"]
    config = {
        "hidden_size": 64, "rms_norm_eps": 1e-5, "q_lora_rank": 24,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
        "v_head_dim": 16, "rope_theta": 1e4, "attention_gate_type": "headwise",
        "apply_mla_qkv_lora_rescale": True, "index_n_heads": 2,
        "index_head_dim": 16, "index_topk": 40, "index_norm_eps": 1e-6}
    with jax.default_matmul_precision("highest"):
        uncut = reference.latent_attention(p, x[0], "full_attention", config)
    total = 0.0
    for rank in range(4):
        held = slice(2 * rank, 2 * rank + 2)
        mine = {"params": {
            **p,
            "q_b_proj": {"kernel": p["q_b_proj"]["kernel"][:, held]},
            "kv_b_proj": {"kernel": p["kv_b_proj"]["kernel"][:, held]},
            "g_proj": {"kernel": p["g_proj"]["kernel"][:, held]},
            "o_proj": {"kernel": p["o_proj"]["kernel"][held]},
        }}
        share = one_mixer(dataclasses.replace(
            kind, heads_held=(2 * rank, 2 * rank + 2)))[0]
        out = share.apply(mine, x, positions)[0]
        with jax.default_matmul_precision("highest"):
            want = reference.latent_attention(
                mine["params"], x[0], "full_attention", config)
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        total = total + out
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=2e-5)


def expert_layer(held):
    """One expert layer at dots3's routing: 32 experts scored, top-2,
    sigmoid, renormalised, x 1, one shared expert; ``held`` of them here."""
    cfg = Dots3Config(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_experts=32, num_experts_per_tok=2, num_shared_experts=1,
        experts_held=held, initializer_range=0.5,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return MoELayer(cfg)


def layer_config(held) -> dict:
    """The reference's keys for that layer."""
    lo, hi = held or (0, 32)
    return {"n_routed_experts_published": 32, "n_routed_experts": hi - lo,
            "expert_rank": lo // (hi - lo), "num_experts_per_tok": 2,
            "routed_scaling_factor": 1, "norm_topk_prob": True,
            "n_shared_experts": 1}


def test_the_32_expert_ranks_shares_add_up_to_the_uncut_layer():
    """32 ranks of one expert each: the routed parts they give, with the
    shared expert (which every rank computes alike) counted once, are the
    uncut reference's expert layer. With the head ranks' sum above, the parts
    of all 4 x 32 ranks are the uncut layer's two sublayers."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 64, 32)), jnp.float32)
    params = expert_layer(None).init(jax.random.PRNGKey(1), x)["params"]
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.moe(params, tokens, layer_config(None))
        shared = reference.swiglu(params["shared"], tokens)
        gates = np.asarray(reference.router_gates(params, tokens, layer_config(None)))
    total, pairs = 0.0, 0
    for rank in range(32):
        held = (rank, rank + 1)
        mine = {**params, **{k: params[k][held[0]:held[1]]
                             for k in ("w_gate", "w_up", "w_down")}}
        out = expert_layer(held).apply({"params": mine}, x).reshape(-1, 32)
        if rank % 8 == 0:
            with jax.default_matmul_precision("highest"):
                want = reference.moe(mine, tokens, layer_config(held))
            np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        pairs += int((gates[:, rank] > 0).sum())
        total = total + (out - shared)
    assert pairs == 64 * 2  # every pair is held by exactly one rank
    np.testing.assert_allclose(total + shared, uncut, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-5)  # renormalised, x 1


# --------------------------------------------------- the configuration file


def test_the_file_holds_every_published_key_and_lists_exactly_what_it_cut():
    config = cells.load_json(CONFIG)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "dots3-note-prev")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_attention_heads", "swa_num_attention_heads"}
    for key, cut in config["reduced"].items():
        assert cut["source"] == row["config"][key] == config[key + "_published"]
        assert cut["here"] == config[key]
    bench = cells.load_json(f"{cells.ROOT}/BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert set(entry["reduced"]) == differs and entry["source"] == config["source"]
    # the floors: the dense layer and a whole period, 8 experts, an eighth of
    # the vocabulary; a tensor-parallel rank's quarter of each kind's heads
    assert config["layer_types"][:5] == ["full_attention"] * 2 + ["sliding_attention"] * 3
    assert config["n_routed_experts"] == 8 and 8 * config["vocab_size"] == 152064
    assert 4 * config["num_attention_heads"] == 128
    assert 4 * config["swa_num_attention_heads"] == 64


def test_the_cells_flops_and_kernels_follow_the_layers():
    cell = cells.load_cell("dots3-note-prev-l5.sparsectx-8k")
    config, traffic = cell["config"], cell["traffic"]
    assert (traffic["batch"], traffic["seq"], traffic["batches"],
            traffic["compare_last"]) == (1, 8192, 16, 256)
    assert traffic["optimizer"] == {
        "name": "adamw", "learning_rate": 3e-7, "b1": 0.9, "b2": 0.95,
        "mu_dtype": "bfloat16"}
    stated = cells.stated_kernels(cell)
    assert {k: s["least"] for k, s in stated.items()} == {
        "_index_kernel": 2, "_fwd_select_kernel": 2, "_bwd_dkv_select_kernel": 2,
        "_bwd_dq_select_kernel": 2, "_fwd_window_kernel": 3,
        "_bwd_dkv_window_kernel": 3, "_bwd_dq_window_kernel": 3,
        "_gmm_kernel": 24, "_tgmm_kernel": 12}
    # a full layer's forward: 32 heads x the chosen pairs x (192 + 128) x 2
    chosen = 2048 * 2049 / 2 + (8192 - 2048) * 2048
    assert stated["_fwd_select_kernel"]["call"][0] == 2.0 * 32 * chosen * 320
    assert stated["_index_kernel"]["call"][0] == 2.0 * 64 * 128 * 8192 * 8193 / 2
    band = 513 * 514 / 2 + (8192 - 513) * 513
    assert stated["_fwd_window_kernel"]["call"][0] == 2.0 * 16 * band * (256 + 128)
    flops = cells.resolve(config["required_flops"])(config, traffic["seq"])
    # 6 x 1.16 B matmul parameters a token passes, the selection's and the
    # band's pairs, the indexer's scores once
    assert 3.9e9 < flops < 4.2e9
    # the chosen pairs only: every key a row sees would be 2.3x the full
    # layers' attention
    every = dict(config, index_topk=8192)
    more = cells.resolve(config["required_flops"])(every, traffic["seq"]) - flops
    assert more == pytest.approx(6.0 * 2 * 32 * 320 * (8192 * 8193 / 2 - chosen) / 8192)
