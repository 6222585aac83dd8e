"""MiniCPM-SALA on the CPU at the configuration's rehearsal size (hidden 128,
4 heads of 32 over 2 K/V heads, 4 Lightning heads of 32, a selection of
block 16 / top-4 / window 32 that runs sparse from 65 tokens on) and at one
odd size (3 Lightning heads of 24, 6 query heads over 3 K/V heads, a sequence
that is no whole number of tiles), seeded float32 weights: the config builder
on the source's own keys; ``num_params()`` against the tree at both sizes and
whole; logits, the chunked loss and every gradient leaf against the plain
reference (``benchmarks/reference/minicpm_sala_decoder``: the recurrence token
by token, the selection row by row), the kernels interpreted; the chosen sets
equal to the reference's to the index; each wrong program and wrong reference
of ``benchmarks/tools/wrong_minicpm_sala.py`` far from it; the dense road
against ``causal_gqa``; and what the benchmark states of the cell."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import minicpm_sala_decoder as reference
from benchmarks.tools import wrong_minicpm_sala
from ray_tpu.models.llama import chunked_causal_lm_loss
from ray_tpu.models.minicpm_sala import (
    MiniCPMSalaConfig, MiniCPMSalaForCausalLM, SparseSelection,
)
from ray_tpu.ops.attention import select_blocks
from ray_tpu.util import tracing
from remat_jaxpr import forward_matmuls

SEQ = 256
CELL = "minicpm-sala-9b-l4.long16k"
CONFIG = f"{cells.BENCH_DIR}/configs/minicpm-sala-9b-l4.json"
# 3 Lightning heads of 24, 6 query heads of 24 over 3 K/V heads (groups of 2),
# blocks of 8 keys: nothing a power of two but the block.
ODD = {"hidden_size": 72, "intermediate_size": 160, "num_attention_heads": 6,
       "num_key_value_heads": 3, "head_dim": 24, "vocab_size": 384,
       "lightning_nh": 3, "lightning_nkv": 3, "lightning_head_dim": 24,
       "dim_model_base": 24,
       "sparse_config": {"kernel_size": 6, "kernel_stride": 3, "init_blocks": 2,
                         "block_size": 8, "window_size": 24, "topk": 7,
                         "dense_len": 32}}
ODD_SEQ = 200
# Past these a float32 program is another function than the reference.
FAR = {"per_position_rel_err": 1e-3, "min_share_within": 0.5}
NEAR = {"per_position_rel_err": 5e-5, "min_share_within": 1.0}


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # The scan's kernels and, from 128 rows, the sparse and the flash kernels.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def in_float32(config):
    return {**config, "program": {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": "float32",
                "param_dtype": "float32"}}}


def build(sizes, seq, seed):
    config = in_float32({**cells.load_json(CONFIG), **sizes})
    model = MiniCPMSalaForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(seed).integers(0, config["vocab_size"], seq)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), ids[None, :8])
    # The draws of 0.02 leave every projection of 128 channels near zero: q.k
    # of normed heads is then all weight and no data, and the selection's
    # scores nearly flat. Projections of unit size give it something to choose.
    p = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 8.0 if path[-1].key == "kernel"
        and path[-2].key in ("q_proj", "k_proj", "v_proj") else w,
        params["params"])
    return config, model, {"params": p}, ids


@pytest.fixture(scope="module")
def sala():
    """(configuration dict at the rehearsal size, model, params, ids), float32."""
    return build(cells.load_json(CONFIG)["rehearsal"], SEQ, 0)


@pytest.fixture(scope="module")
def odd():
    return build(ODD, ODD_SEQ, 1)


@pytest.fixture(scope="module")
def expected(sala):
    config, _, params, ids = sala
    return reference.forward(params, ids, config, SEQ)


# ---------------------------------------------------------------- the config


def test_the_builder_reads_the_sources_own_keys():
    config = cells.load_json(CONFIG)
    assert len(config["mixer_types"]) == 32 == config["num_hidden_layers_published"]
    assert [i for i, t in enumerate(config["mixer_types"]) if t == "minicpm4"] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    cfg = cells.program_config(config)
    assert isinstance(cfg, MiniCPMSalaConfig)
    assert cfg.layers == ((tracing.SPARSE, tracing.MLP),) + (
        (tracing.LIGHTNING, tracing.MLP),) * 3
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
            cfg.intermediate_size, cfg.vocab_size, cfg.max_seq_len) == (
        4096, 32, 2, 128, 16384, 9181, 524288)
    assert (cfg.lightning_nh, cfg.lightning_head_dim, cfg.lightning_use_rope,
            cfg.attn_use_rope, cfg.rope_theta, cfg.rms_eps) == (32, 128, True, False, 10000, 1e-6)
    assert cfg.sparse == SparseSelection(32, 16, 64, 64, 1, 2048, 8192)
    assert (cfg.embed_scale, cfg.logit_divisor) == (12.0, 16.0)
    assert cfg.residual_scale == pytest.approx(1.4 / math.sqrt(32))
    assert (cfg.remat, cfg.remat_policy, cfg.remat_prevent_cse) == (True, "kernels", True)
    # the slopes: 2^(-8 (h + 1) / 32) times the layer's factor of the 32
    assert cfg.lightning_slopes(0)[0] == pytest.approx(2 ** -0.25 * (1 + 1e-5))
    assert cfg.lightning_slopes(3)[31] == pytest.approx(2 ** -8 * (1 - 3 / 31 + 1e-5))
    assert cfg.lightning_slopes(31)[0] == pytest.approx(2 ** -0.25 * 1e-5)


def test_under_its_own_remat_fields_no_replay_runs_a_swiglu_matmul(sala):
    """The file's "kernels" is "nothing" since PR 55: ahead of the backward
    pass the gradient holds each of the SwiGLU's three matmuls once a layer."""
    _, model, params, ids = sala
    grad = jax.grad(lambda p: chunked_causal_lm_loss(
        model, p, ids[None], np.roll(ids, -1)[None], chunk_size=64))
    dots = forward_matmuls(jax.make_jaxpr(grad)(params).jaxpr)
    layers = len(model.cfg.layers)
    assert [dots[f"mlp/{name}"] for name in ("gate_proj", "up_proj", "down_proj")] == [layers] * 3


def test_the_files_numbers_are_the_catalogs_but_for_the_two_it_reduces():
    import json
    import os

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of public architectures is not on this machine")
    config = cells.load_json(CONFIG)
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "MiniCPM-SALA"]
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {"num_hidden_layers", "vocab_size"}
    for key in ("sparse_config", "forced_window", "selection", "qk_norm",
                "lightning_slopes", "use_output_norm", "use_output_gate",
                "mup", "initializer_range"):
        assert key in config["assumed"], key
    assert config["departures"] and config["deployment"]


@pytest.mark.parametrize("change,message", [
    ({"lightning_nkv": 8}, "head count"),
    ({"mixer_types": ["minicpm4", "mamba"] * 2}, "mamba"),
    ({"mixer_types": ["minicpm4"] * 3}, "short of 4"),
    ({"use_output_norm": False}, "output norm"),
], ids=["grouped lightning", "an unknown mixer", "too few mixer types", "no output norm"])
def test_what_the_builder_cannot_build_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        cells.program_config({**cells.load_json(CONFIG), **change})


def leaves(tree):
    return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))


def test_num_params_is_the_tree_at_the_published_widths_and_whole():
    config = cells.load_json(CONFIG)
    cfg = cells.program_config(config)
    shapes = jax.eval_shape(MiniCPMSalaForCausalLM(cfg).init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    assert leaves(shapes) == cfg.num_params() == config["parameters_held"] == 1_184_642_432
    p = shapes["params"]
    assert leaves(p["layers_0"]["sparse"]) == 52_429_056  # 52.4 M
    assert leaves(p["layers_1"]["lightning"]) == 83_886_464  # 83.9 M
    assert leaves(p["layers_0"]["mlp"]) == 201_326_592
    sparse, lightning = p["layers_0"]["sparse"], p["layers_1"]["lightning"]
    assert sparse["q_proj"]["kernel"].shape == (4096, 32, 128)
    assert sparse["k_proj"]["kernel"].shape == sparse["v_proj"]["kernel"].shape == (4096, 2, 128)
    assert sparse["g_proj"]["kernel"].shape == (4096, 32, 128)
    assert sparse["q_norm"]["scale"].shape == sparse["k_norm"]["scale"].shape == (128,)
    assert lightning["g_proj"]["kernel"].shape == (4096, 4096)
    assert lightning["o_norm"]["scale"].shape == lightning["q_norm"]["scale"].shape == (128,)
    assert set(p["layers_1"]) == {"lightning", "mlp", "input_norm", "post_attn_norm"}
    whole = cells.program_config(
        {**config, "num_hidden_layers": 32, "vocab_size": 73448})
    assert [m for m, _ in whole.layers].count(tracing.SPARSE) == 8
    assert whole.num_params() == config["parameters_whole_model"] == 9_477_110_784


@pytest.mark.parametrize("which", ["sala", "odd"])
def test_num_params_is_the_tree_at_the_small_sizes(request, which):
    _, model, params, _ = request.getfixturevalue(which)
    assert leaves(params) == model.cfg.num_params()


# -------------------------------------------- the model against the reference


def test_logits_agree_with_the_reference_in_float32(sala, expected):
    _, model, params, ids = sala
    assert SEQ > model.cfg.sparse.dense_len  # the sparse road
    system = jax.jit(model.apply)(params, ids[None])[0]
    assert system.dtype == jnp.float32
    result = logits_agreement(system, expected, NEAR)
    assert result["ok"], result


def test_logits_agree_at_an_odd_size(odd):
    config, model, params, ids = odd
    system = jax.jit(model.apply)(params, ids[None])[0]
    result = logits_agreement(
        system, reference.forward(params, ids, config, ODD_SEQ), NEAR)
    assert result["ok"], result


def _qk_of_the_sparse_layer(config, params, ids):
    """q [T, heads, d] and k [T, kv, d] as layer 0's mixer takes them."""
    p = params["params"]
    x = config["scale_emb"] * p["embed_tokens"]["embedding"][ids]
    layer = p["layers_0"]
    x = reference.rms_norm(x, layer["input_norm"]["scale"], config["rms_norm_eps"])
    return reference.sparse_qkv(layer["sparse"], x, config)[:2]


@pytest.mark.parametrize("which", ["sala", "odd"])
def test_the_chosen_sets_are_the_references_to_the_index(request, which):
    config, model, params, ids = request.getfixturevalue(which)
    sel = model.cfg.sparse
    with jax.default_matmul_precision("highest"):
        q, k = _qk_of_the_sparse_layer(config, params, ids)
        wanted = np.asarray(reference.chosen_blocks(q, k, config["sparse_config"]))[:, :, 0]
        got = select_blocks(
            q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
            block_size=sel.block_size, topk=sel.topk, window=sel.window_size,
            init_blocks=sel.init_blocks, kernel_size=sel.kernel_size,
            kernel_stride=sel.kernel_stride, rows=64)
    got = np.asarray(got)[0].transpose(1, 0, 2)  # [T, kv, blocks]
    assert got.shape == wanted.shape
    np.testing.assert_array_equal(got, wanted)
    own = np.arange(len(ids)) // sel.block_size
    counts = got.sum(-1)
    # topk blocks a row and group, every visible block where there are fewer
    np.testing.assert_array_equal(
        counts, np.minimum(own + 1, sel.topk)[:, None].repeat(got.shape[1], 1))
    rows = np.arange(len(ids))
    assert got[rows, :, own].all()  # a row's own block
    for b in range(sel.init_blocks):  # the initial blocks, from the row that sees them
        assert got[own >= b, :, b].all()
    # the two groups choose differently somewhere, and a row not only its window
    assert (got[:, 0] != got[:, 1]).any()
    local = sel.window_size // sel.block_size
    far = np.arange(got.shape[2])[None, :] <= (own - local)[:, None]
    far[:, :sel.init_blocks] = False
    assert (got[:, 0] & far).any()


def replaced(cfg, **change):
    return dataclasses.replace(cfg, **change)


def gradient_through_the_selection(plain):
    """The attention's output times 1 + R - stop_gradient(R), R the sum of the
    blocks' scores: the same value, and a gradient that reaches q and k
    through the scores the selection is made from."""
    def block_sparse_gqa(q, k, v, chosen, block):
        sel = {"kernel_size": 8, "kernel_stride": 4, "block_size": block}
        r = reference.block_scores(q, k, sel, 0, q.shape[0]).sum()
        return plain(q, k, v, chosen, block) * (1.0 + r - jax.lax.stop_gradient(r))

    return block_sparse_gqa


WRONG = {
    **{name: ("program", entry)
       for name, entry in wrong_minicpm_sala.programs(
           cells.program_config(in_float32(
               {**cells.load_json(CONFIG), **cells.load_json(CONFIG)["rehearsal"]}))).items()},
    **{name: ("reference", entry)
       for name, entry in wrong_minicpm_sala.references(
           lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)).items()},
}


@pytest.mark.parametrize("name", list(WRONG))
def test_a_wrong_program_or_reference_is_refused(sala, expected, monkeypatch, name):
    """topk - 1, window - 1, no init block, rotation in the sparse layer, none
    in the Lightning layers, the residual scale of 4 layers, logits not
    divided; mean-pool for max-pool, per-head for group-summed scores, slopes
    without the layer's factor, a bfloat16 state, bfloat16 scores: each moves
    the logits past what float32 leaves between program and reference."""
    config, _, params, ids = sala
    kind, entry = WRONG[name]
    if name == "reference_scores_bf16":
        # What bfloat16 scores move is which blocks are chosen, at a few rows:
        # the logits of those rows move, fewer than half of all. The sets say it.
        q, k = _qk_of_the_sparse_layer(config, params, ids)
        plain = np.asarray(reference.chosen_blocks(q, k, config["sparse_config"]))
        monkeypatch.setattr(reference, entry[0], entry[1](getattr(reference, entry[0])))
        other = np.asarray(reference.chosen_blocks(q, k, config["sparse_config"]))
        assert (plain != other).any(-1).mean() > 0.01
        return
    if kind == "program":
        other = jax.jit(MiniCPMSalaForCausalLM(entry[0]).apply)(params, ids[None])[0]
    else:
        function, replacement = entry
        monkeypatch.setattr(
            reference, function, replacement(getattr(reference, function)))
        other = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(other, expected, FAR)
    assert not result["ok"], result


@pytest.fixture(scope="module")
def both_gradients(sala):
    config, model, params, ids = sala
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


def worst(grads, wanted):
    """The largest |got - want| / max |want| over the leaves, and their number."""
    flat = dict(jax.tree_util.tree_leaves_with_path(grads["params"]))
    errors = {}
    for path, want in jax.tree_util.tree_leaves_with_path(wanted["params"]):
        got, want = np.asarray(flat[path]), np.asarray(want)
        assert got.shape == want.shape and np.abs(want).max() > 0, path
        errors[jax.tree_util.keystr(path)] = np.abs(got - want).max() / np.abs(want).max()
    return errors


def test_every_gradient_agrees_with_the_references(both_gradients):
    (_, grads), (_, wanted) = both_gradients
    errors = worst(grads, wanted)
    assert max(errors.values()) < 1e-4, max(errors.items(), key=lambda e: e[1])
    # a layer: 2 norms and the MLP's 3 weights; the sparse mixer's 7 leaves, a
    # Lightning mixer's 8; embedding, final norm, head
    assert len(errors) == 4 * 5 + 7 + 3 * 8 + 3


def test_a_gradient_through_the_selection_is_refused(sala, both_gradients, monkeypatch):
    config, _, params, ids = sala
    (_, grads), (loss, _) = both_gradients
    monkeypatch.setattr(reference, "block_sparse_gqa",
                        gradient_through_the_selection(reference.block_sparse_gqa))
    targets = np.roll(ids, -1)
    other_loss, other = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, ids, targets, config)))(params)
    assert float(other_loss) == pytest.approx(float(loss), rel=1e-5)  # the same value
    errors = worst(grads, other)
    assert errors["['layers_0']['sparse']['q_proj']['kernel']"] > 1e-2
    assert errors["['layers_0']['sparse']['k_proj']['kernel']"] > 1e-2


def test_at_or_under_dense_len_the_layer_is_causal_attention(sala):
    """The dense road: T <= dense_len takes no selection, and is the dense
    references' ``causal_gqa``."""
    config, model, params, ids = sala
    short = ids[:64]  # dense_len of the rehearsal size
    assert len(short) == model.cfg.sparse.dense_len
    system = jax.jit(model.apply)(params, short[None])[0]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(reference, "chosen_blocks", None)  # not called
        wanted = reference.forward(params, short, config, 64)
    result = logits_agreement(system, wanted, NEAR)
    assert result["ok"], result
    text = jax.jit(model.apply).lower(params, short[None]).as_text("hlo")
    assert "top_k" not in text.lower() and "topk" not in text.lower()


# ------------------------------------------------- what the benchmark states


def test_the_required_flops_a_token_are_the_issues_arithmetic():
    from benchmarks.lib.flops_minicpm_sala import attended_pairs, scored_pairs

    cell = cells.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    seq = traffic["seq"]
    per_token = cells.resolve(config["required_flops"])(config, seq)
    sparse, lightning, mlp = 52_428_800, 83_886_080, 201_326_592
    matmul_params = sparse + 3 * lightning + 4 * mlp + 4096 * 9181
    # 64 of up to 256 blocks: a row sees 4,096 keys at most, 44% of the causal
    # pairs over the sequence and 25% at its end
    pairs = attended_pairs(seq, 64, 64)
    assert pairs == sum((min(i // 64 + 1, 64) - 1) * 64 + i % 64 + 1 for i in range(seq))
    assert 0.43 < pairs / (seq * (seq + 1) / 2) < 0.45
    scored = scored_pairs(seq, 32, 16)
    assert scored == sum(max((i + 1 - 32) // 16 + 1, 0) for i in range(seq))
    recurrence = 3 * 32 * 15 * 128 * 128
    assert per_token == pytest.approx(
        6.0 * matmul_params + 32 * 128 * (12.0 * pairs + 2.0 * scored) / seq + recurrence)
    assert 0.02 < 6 * 4096 * 9181 / per_token < 0.04  # the head's share
    # under dense_len the layer is causal attention whole
    dense = cells.resolve(config["required_flops"])(config, 8192)
    assert dense == pytest.approx(6.0 * matmul_params + 6.0 * 8192 * 4096 + recurrence)


def test_the_stated_kernels_are_the_steps_and_count_the_attended_pairs():
    from benchmarks.lib.flops_minicpm_sala import (
        attended_pairs, lightning_call, sparse_call,
    )

    cell = cells.load_cell(CELL)
    stated = cells.stated_kernels(cell)
    assert {k: s["least"] for k, s in stated.items()} == {
        "_sparse_fwd_kernel": 1, "_bwd_dkv_sparse_kernel": 1,
        "_bwd_dq_sparse_kernel": 1, "_lightning_fwd_kernel": 3,
        "_lightning_bwd_kernel": 3}
    pairs = attended_pairs(16384, 64, 64)
    flops, nbytes = stated["_sparse_fwd_kernel"]["call"]
    assert flops == 2.0 * 32 * pairs * 2 * 128
    rows = 16384 * 128 * 2
    # q and o at 32 heads, K and V at 2, the log-sum-exp, a bit a row, group
    # and block
    assert nbytes == (32 * 2 + 2 * 2) * rows + 32 * 16384 * 4 + 2 * 16384 * 256 / 8
    assert stated["_bwd_dkv_sparse_kernel"]["call"][0] == 2 * flops
    assert stated["_bwd_dq_sparse_kernel"]["call"][0] == 1.5 * flops
    flops, nbytes = stated["_lightning_fwd_kernel"]["call"]
    assert (flops, nbytes) == lightning_call("_lightning_fwd_kernel", 32, 16384, 128, 128)
    assert flops == 32 * 16384 * 5 * 128 * 128 and nbytes == 32 * 16384 * 5 * 128 * 2
    assert stated["_lightning_bwd_kernel"]["call"][0] == 3 * flops
    with pytest.raises(KeyError):
        lightning_call("_gdn_fwd_kernel", 32, 16384, 128, 128)
    with pytest.raises(KeyError):
        sparse_call("_fwd_kernel", 32, 2, 16384, 64, 64, 128)
    # under dense_len the step holds the causal kernels
    short = cells.stated_kernels({**cell, "traffic": {**cell["traffic"], "seq": 8192}})
    assert set(short) == {"_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel",
                          "_lightning_fwd_kernel", "_lightning_bwd_kernel"}


def test_the_cell_reads_the_metrics_of_its_layers_and_not_the_flash_kernels():
    cell = cells.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    ours = {"model.lightning_share", "kernel.lightning_share",
            "kernel.lightning_roofline", "model.sparse_share",
            "model.sparse_select_share", "kernel.sparse_share",
            "kernel.sparse_roofline"}
    assert ours | {"model.mlp_share", "step.unnamed_share",
                   "model.head_loss_share", "device.peak_hbm_gib"} <= names
    assert not {"kernel.flash_share", "kernel.flash_roofline", "kernel.gdn_share",
                "model.moe_share", "trainer.step_ms_p95_over_p50"} & names
    assert (cell["chips"], cell["traffic"]["batch"], cell["traffic"]["seq"],
            cell["traffic"]["loss"]["args"]["chunk_size"]) == (1, 1, 16384, 2048)
    for name in ours:
        reader = cells.load_reader(f"{cells.BENCH_DIR}/layer_metrics", name)
        assert reader.read({"trace_data": None}) is None
    # the cells that run a causal flash kernel still read the flash kernels'
    # two: all but this one and the dots3 cell, whose full layers run the
    # selection's kernels and whose sliding layers the band's (PR 69)
    bench = cells.load_json(f"{cells.ROOT}/BENCHMARK.json")
    without = (CELL, "dots3-note-prev-l5.sparsectx-8k")
    for metric in bench["per_layer"]:
        if metric["name"] in ("kernel.flash_share", "kernel.flash_roofline"):
            assert metric["workloads"] == [
                w["name"] for w in bench["workloads"] if w["name"] not in without]
