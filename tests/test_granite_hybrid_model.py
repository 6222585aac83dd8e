"""Granite 4.0-H on the CPU at the configuration's rehearsal size (hidden 128,
4 state-space heads of 32 over a state of 32, 4 query heads of 32 over 2 K/V
heads) and at one odd size (3 state-space heads of 24 over a state of 40, 6
query heads over 3 K/V heads, a sequence that is no whole number of chunks),
seeded float32 weights: the config builder on the source's own keys;
``num_params()`` against the tree at both sizes and whole; logits, the chunked
loss and every gradient leaf against the plain reference
(``benchmarks/reference/granite_hybrid_decoder``: the recurrence token by
token), the kernels interpreted; each wrong program and wrong reference of
``benchmarks/tools/wrong_granite_hybrid.py`` far from it; the share tied to
the model (the cut's layers and its quarter of the tied table against the
whole); and what the benchmark states of the cell.

This file holds the model's logits against its reference, the wrong programs
and references, and what the benchmark states of the cell. The loss and
gradients, the quarter's logits, the odd size and the parameter counts are in
``tests/test_granite_hybrid_gradients.py`` beside it, over
``tests/granite_hybrid_cases.py``.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import granite_hybrid_decoder as reference
from benchmarks.tools import wrong_granite_hybrid
from ray_tpu.models.granite_hybrid import (
    GraniteHybridConfig, GraniteHybridForCausalLM,
)
from ray_tpu.util import tracing

from granite_hybrid_cases import (  # noqa: F401 - fixtures
    CONFIG, NEAR, SEQ, granite, in_float32, interpret, leaves,
)


CELL = "granite-4-h-micro-l10.pretrain-8k"
# Past these a float32 program is another function than the reference.
FAR = {"per_position_rel_err": 1e-3, "min_share_within": 0.5}


@pytest.fixture(scope="module")
def expected(granite):
    config, _, params, ids = granite
    return reference.forward(params, ids, config, SEQ)


# ---------------------------------------------------------------- the config


def test_the_builder_reads_the_sources_own_keys():
    config = cells.load_json(CONFIG)
    assert len(config["layer_types"]) == 40 == config["num_hidden_layers_published"]
    assert [i for i, t in enumerate(config["layer_types"]) if t == "attention"] == [
        5, 15, 25, 35]
    cfg = cells.program_config(config)
    assert isinstance(cfg, GraniteHybridConfig)
    mamba, attn = (tracing.MAMBA, tracing.MLP), (tracing.ATTN, tracing.MLP)
    assert cfg.layers == (mamba,) * 5 + (attn,) + (mamba,) * 4
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
            cfg.intermediate_size, cfg.vocab_size, cfg.max_seq_len) == (
        2048, 32, 8, 64, 8192, 25088, 131072)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_conv_bias, cfg.mamba_channels) == (64, 64, 128, 4, True, (4096, 4352))
    assert (cfg.embed_scale, cfg.residual_scale, cfg.logit_divisor,
            cfg.attention_scale) == (12.0, 0.22, 8.0, 0.015625)
    assert (cfg.tie_embeddings, cfg.rms_eps, cfg.initializer_range) == (True, 1e-5, 0.02)
    assert (cfg.remat, cfg.remat_policy, cfg.remat_prevent_cse) == (True, "nothing", True)
    kind = cfg.attention(tracing.ATTN)
    assert (kind.num_heads, kind.freqs, kind.scale, kind.window, kind.gate) == (
        32, None, 0.015625, None, False)


def test_the_files_numbers_are_the_catalogs_but_for_the_two_it_reduces():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of public architectures is not on this machine")
    config = cells.load_json(CONFIG)
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "granite-4.0-h-micro"]
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {"num_hidden_layers", "vocab_size"}
    for key in ("head_dim", "gate_and_norm", "time_step_limit", "mamba_chunk_size",
                "mamba_initialisers", "initializer_range", "in_proj", "attention",
                "multipliers", "ffn"):
        assert key in config["assumed"], key
    assert len(config["departures"]) == 2 and "four pipeline stages" in config["deployment"]
    assert "vocabulary rank 0 of four" in config["deployment"]


@pytest.mark.parametrize("change,message", [
    ({"mamba_n_groups": 8}, "one B/C group"),
    ({"mamba_proj_bias": True}, "no projection"),
    ({"num_local_experts": 8}, "no routed expert"),
    ({"position_embedding_type": "rope"}, "nope"),
    ({"mamba_expand": 4}, "mamba_expand"),
    ({"layer_types": ["mamba", "lightning-attn"] * 5}, "lightning-attn"),
    ({"layer_types": ["mamba"] * 9}, "short of 10"),
], ids=["eight groups", "a projection bias", "routed experts", "rotation",
        "another expansion", "an unknown layer type", "too few layer types"])
def test_what_the_builder_cannot_build_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        cells.program_config({**cells.load_json(CONFIG), **change})


def test_num_params_is_the_tree_at_the_published_widths_and_whole():
    config = cells.load_json(CONFIG)
    cfg = cells.program_config(config)
    shapes = jax.eval_shape(GraniteHybridForCausalLM(cfg).init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    assert leaves(shapes) == cfg.num_params() == config["parameters_held"] == 797_850_560
    p = shapes["params"]
    assert leaves(p["layers_0"]["mamba"]) == 25_847_232
    assert leaves(p["layers_5"]["attn"]) == 10_485_760
    assert leaves(p["layers_0"]["mlp"]) == 50_331_648
    assert leaves(p["layers_0"]) == 76_182_976 and leaves(p["layers_5"]) == 60_821_504
    mamba, attn = p["layers_0"]["mamba"], p["layers_5"]["attn"]
    # the source's one in_proj [2048, 8512] as its three column ranges
    assert [mamba[n]["kernel"].shape for n in ("z_proj", "xbc_proj", "dt_proj")] == [
        (2048, 4096), (2048, 4352), (2048, 64)]
    assert mamba["conv"].shape == (4, 4352) and mamba["conv_bias"].shape == (4352,)
    assert mamba["A_log"].shape == mamba["D"].shape == mamba["dt_bias"].shape == (64,)
    assert mamba["norm"]["scale"].shape == (4096,)
    assert mamba["out_proj"]["kernel"].shape == (4096, 2048)
    assert attn["q_proj"]["kernel"].shape == (2048, 32, 64)
    assert attn["k_proj"]["kernel"].shape == attn["v_proj"]["kernel"].shape == (2048, 8, 64)
    assert set(attn) == {"q_proj", "k_proj", "v_proj", "o_proj"}  # no bias, no gate, no norm
    assert "lm_head" not in p and p["embed_tokens"]["embedding"].shape == (25088, 2048)
    whole = cells.program_config(
        {**config, "num_hidden_layers": 40, "vocab_size": 100352})
    assert [m for m, _ in whole.layers].count(tracing.ATTN) == 4
    assert whole.num_params() == config["parameters_whole_model"] == 3_191_396_096


# -------------------------------------------- the model against the reference


def test_logits_agree_with_the_reference_in_float32(granite, expected):
    _, model, params, ids = granite
    system = jax.jit(model.apply)(params, ids[None])[0]
    assert system.dtype == jnp.float32
    result = logits_agreement(system, expected, NEAR)
    assert result["ok"], result


WRONG = {
    **{name: ("program", entry)
       for name, entry in wrong_granite_hybrid.programs(
           cells.program_config(in_float32(
               {**cells.load_json(CONFIG), **cells.load_json(CONFIG)["rehearsal"]}))).items()},
    **{name: ("reference", entry)
       for name, entry in wrong_granite_hybrid.references(
           lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)).items()},
}


@pytest.mark.parametrize("name", list(WRONG))
def test_a_wrong_program_or_reference_is_refused(granite, expected, monkeypatch, name):
    """The default scale of 1/8, no filter bias, the three multipliers at 1.0;
    no softplus, the decay without A, the input not scaled by the step, no
    skip, the norm before the gate, a norm a head, B and C a head, rotation in
    the attention layer, an untied head: each moves the logits past what
    float32 leaves between program and reference. (The rounded state moves
    them too at this size, where a head's state is 32 x 32.)"""
    config, _, params, ids = granite
    kind, entry = WRONG[name]
    if kind == "program":
        cfg, *drop = entry
        p = params
        if drop:
            p = {"params": {
                layer: {m: {k: v for k, v in sub.items() if k not in drop[0]}
                        if m in tracing.MIXERS else sub for m, sub in tree.items()}
                if layer.startswith(tracing.LAYER) else tree
                for layer, tree in params["params"].items()}}
        other = jax.jit(GraniteHybridForCausalLM(cfg).apply)(p, ids[None])[0]
    else:
        function, replacement = entry
        monkeypatch.setattr(
            reference, function, replacement(getattr(reference, function)))
        other = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(other, expected, FAR)
    assert not result["ok"], result


# --------------------------------------------- the share tied to the model


def test_the_cuts_layers_are_layers_0_to_9_of_the_whole_pattern():
    config = cells.load_json(CONFIG)
    whole = cells.program_config({**config, "num_hidden_layers": 40})
    cut = cells.program_config(config)
    assert cut.layers == whole.layers[:10]
    # one whole period: every later stage of ten layers has the same pattern
    assert all(whole.layers[i:i + 10] == cut.layers for i in (10, 20, 30))


# ------------------------------------------------- what the benchmark states


def test_the_required_flops_a_token_are_the_issues_arithmetic():
    cell = cells.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    per_token = cells.resolve(config["required_flops"])(config, traffic["seq"])
    mamba, attn, mlp, head = 25_821_184, 10_485_760, 50_331_648, 2048 * 25088
    matmul_params = 9 * mamba + attn + 10 * mlp + head
    assert matmul_params == 797_573_120
    recurrence = 9 * 64 * 3 * 5 * 128 * 64
    attention = 6.0 * 8192 * 32 * 64
    assert per_token == pytest.approx(6.0 * matmul_params + attention + recurrence)
    assert per_token == pytest.approx(4.957e9, rel=1e-3)
    shares = {"mamba": (6 * 9 * mamba + recurrence) / per_token,
              "mlp": 6 * 10 * mlp / per_token,
              "attn": (6 * attn + attention) / per_token, "head": 6 * head / per_token}
    assert {k: round(100 * v, 1) for k, v in shares.items()} == {
        "mamba": 29.6, "mlp": 60.9, "attn": 3.3, "head": 6.2}


def test_the_stated_kernels_are_the_steps_and_share_one_score_matrix():
    from benchmarks.lib.flops import flash_call
    from benchmarks.lib.flops_granite_hybrid import ssd_call

    cell = cells.load_cell(CELL)
    stated = cells.stated_kernels(cell)
    assert {k: s["least"] for k, s in stated.items()} == {
        "_fwd_kernel": 1, "_bwd_dkv_kernel": 1, "_bwd_dq_kernel": 1,
        "_ssd_fwd_kernel": 9, "_ssd_bwd_kernel": 9,
        "_conv_fwd_kernel": 1, "_conv_bwd_kernel": 1}
    flops, nbytes = stated["_ssd_fwd_kernel"]["call"]
    chunks, c, h, p, n = 32, 256, 64, 64, 128
    # C B^T once a chunk, not once a head; a head's masked product, the
    # state's two products and its decay
    assert flops == chunks * (c * c * n + h * (c * c * p + 4 * c * n * p + n * p))
    # u and y at two bytes, B and C once, the step four bytes a head and token
    assert nbytes == chunks * c * (2 * h * p * 2 + 2 * n * 2 + h * 4)
    assert stated["_ssd_bwd_kernel"]["call"][0] == 3 * flops
    assert stated["_ssd_bwd_kernel"]["call"][1] == chunks * c * (
        3 * h * p * 2 + 2 * (2 * n * 2 + h * 4))
    with pytest.raises(KeyError):
        ssd_call("_lightning_fwd_kernel", 1, 8192, 64, 64, 128)
    assert stated["_fwd_kernel"]["call"] == flash_call(
        "_fwd_kernel", 32, 8192, 8192, 64, causal=True)
    flops, nbytes = stated["_conv_fwd_kernel"]["call"]
    assert (flops, nbytes) == (2.0 * 4 * 8192 * 4352, 8192 * 4352 * 6.0)


def test_the_cell_reads_the_metrics_of_its_layers():
    cell = cells.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    ours = {"model.mamba_share", "model.mamba_conv_share", "kernel.ssd_share",
            "kernel.ssd_roofline"}
    assert ours | {"model.mlp_share", "model.gqa_share", "kernel.flash_share",
                   "kernel.flash_roofline", "trainer.step_ms_p95_over_p50",
                   "step.unnamed_share", "model.head_loss_share",
                   "device.peak_hbm_gib"} <= names
    assert not {"kernel.gdn_share", "kernel.lightning_share", "model.moe_share",
                "model.kda_share"} & names
    assert (cell["chips"], cell["traffic_name"], cell["traffic"]["batch"],
            cell["traffic"]["seq"], cell["traffic"]["loss"]["args"]["chunk_size"]) == (
        1, "pretrain-8k", 1, 8192, 2048)
    bench = cells.load_json(f"{cells.ROOT}/BENCHMARK.json")
    for metric in bench["per_layer"]:
        if metric["name"] in ours:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "tokens_per_s_per_chip"
            reader = cells.load_reader(f"{cells.BENCH_DIR}/layer_metrics", metric["name"])
            assert reader.read({"trace_data": None}) is None
