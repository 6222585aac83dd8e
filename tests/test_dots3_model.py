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

This file holds the model against its reference (logits, wrong references,
loss and gradients) and the configuration file's cases. The wrong programs
(``tests/test_dots3_wrong_programs.py``), the hand-written lines and the
selection (``test_dots3_layers.py``) and the ranks' shares
(``test_dots3_shares.py``) are beside it, over ``tests/dots3_cases.py``.
"""
import json
import math

import jax
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import dots3_note_decoder as reference
from benchmarks.tools import wrong_dots3
from ray_tpu.models.dots3 import Dots3ForCausalLM
from ray_tpu.models.llama import chunked_causal_lm_loss
from ray_tpu.models.mla import Indexer, LatentKind
from ray_tpu.util import tracing

from dots3_cases import (  # noqa: F401 - fixtures
    CONFIG, FAR, SEQ, dots3, expected, interpret, tiny,
)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
INDEX = ("index_q_proj", "index_k_proj", "index_k_norm", "index_w_proj")


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


@pytest.fixture(scope="module")
def logits(dots3):
    """The unchanged program's float32 logits: one jitted program, one value."""
    _, model, params, ids = dots3
    return jax.jit(model.apply)(params, ids[None])[0]


def test_logits_agree_with_the_reference_in_float32(logits, expected):
    found = logits_agreement(logits, expected, {
        "per_position_rel_err": 2e-5, "min_share_within": 1.0})
    assert found["ok"], found


@pytest.mark.parametrize("name,far", [
    # bfloat16 scores move the rows whose threshold they cross, and at 128
    # positions those are few: none may be far where the float32 program lies
    # within 2e-5 at every one
    ("reference_index_bf16", {**FAR, "min_share_within": 1.0}),
    ("reference_router_bf16", FAR)])
def test_a_wrong_reference_is_far_from_the_program(
        dots3, logits, name, far, monkeypatch):
    config, _, params, ids = dots3
    function, replacement = wrong_dots3.references(
        lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7))[name]
    monkeypatch.setattr(reference, function, replacement(getattr(reference, function)))
    wrong = reference.forward(params, ids, config, SEQ)
    found = logits_agreement(logits, wrong, far)
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
