"""OLMoE-1B-7B's architecture through the program's models, on the CPU.

``MixtralForCausalLM`` with QK-norm, unnormalised top-k gates and an untied
head, through the drop-free ``gmm`` dispatch (the Pallas kernels in interpret
mode), against the benchmark's plain reference
(``benchmarks/reference/olmoe_decoder.py``) at the configuration file's own
``rehearsal`` size on seeded random weights: logits, loss and gradients. And
each of the four facts of the architecture (the fourth is its draw of the
weights) leaves the models that lack it as they were.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import dense_decoder, olmoe_decoder
from ray_tpu.models import CONFIGS, LlamaForCausalLM
from ray_tpu.models.llama import lm_head_weight
from ray_tpu.models.mixtral import CONFIGS as MOE_CONFIGS
from ray_tpu.models.mixtral import (
    MixtralConfig, MixtralForCausalLM, MoELayer, moe_lm_loss,
)

SEQ = 128


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def olmoe(dtype: str):
    """(configuration dict at its rehearsal size, model, params, ids)."""
    config = cells.load_json(f"{cells.BENCH_DIR}/configs/olmoe-1b-7b-1chip.json")
    config = {**config, **config["rehearsal"]}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": dtype, "param_dtype": dtype},
    }
    model = MixtralForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    return config, model, params, ids


@pytest.fixture(scope="module")
def olmoe_f32():
    return olmoe("float32")


@pytest.fixture(scope="module")
def olmoe_bf16():
    return olmoe("bfloat16")


def test_the_configuration_builds_olmoes_program(olmoe_f32):
    config, model, params, _ = olmoe_f32
    cfg = model.cfg
    assert (cfg.qk_norm, cfg.norm_topk_prob, cfg.tie_embeddings) == (
        True, False, False)
    assert cfg.moe_dispatch == "gmm" and cfg.router_aux_loss_coef == 0.01
    assert cfg.initializer_range == 0.02
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (8, 2)
    attn = params["params"]["layers_0"]["attn"]
    width = cfg.num_heads * cfg.head_dim_
    assert attn["q_norm"]["scale"].shape == (width,)
    assert attn["k_norm"]["scale"].shape == (cfg.num_kv_heads * cfg.head_dim_,)
    # and at the published sizes the file gives the published architecture
    full = cells.program_config(
        cells.load_json(f"{cells.BENCH_DIR}/configs/olmoe-1b-7b-1chip.json")
    )
    assert (full.hidden_size, full.num_heads, full.num_kv_heads,
            full.head_dim_, full.intermediate_size, full.num_experts,
            full.num_experts_per_tok, full.vocab_size, full.max_seq_len) == (
        2048, 16, 16, 128, 1024, 64, 8, 50304, 4096)
    assert (full.qk_norm, full.norm_topk_prob, full.tie_embeddings) == (
        True, False, False)


def test_logits_agree_with_the_reference_in_float32(olmoe_f32):
    config, model, params, ids = olmoe_f32
    system = model.apply(params, ids[None])[0]
    expected = olmoe_decoder.forward(params, ids, config, SEQ)
    assert system.dtype == jnp.float32
    # No routing flip is expected in 128 positions: a flip needs two router
    # probabilities within float32 rounding of each other.
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 1e-4, "min_share_within": 1.0}
    )
    assert result["ok"], result


def test_logits_agree_in_bfloat16_within_the_references_tolerance(olmoe_bf16):
    config, model, params, ids = olmoe_bf16
    system = model.apply(params, ids[None])[0]
    expected = olmoe_decoder.forward(params, ids, config, SEQ)
    result = logits_agreement(system, expected, olmoe_decoder.TOLERANCE)
    assert result["ok"], result
    assert result["rel_err_median"] > 1e-4  # the system is not the reference


@pytest.mark.parametrize("wrong", [
    {"norm_topk_prob": True},  # Mixtral's gates
    {"moe_dispatch": "capacity", "capacity_factor": 1.25},  # drops pairs
    {"qk_norm": False},  # the tree keeps q_norm and k_norm; nothing reads them
], ids=lambda w: "-".join(w))
def test_a_program_of_another_function_is_refused(olmoe_bf16, wrong):
    """The tolerance the chip runs are held to refuses them here as it does
    there (the reference's file has the chip's readings)."""
    config, model, params, ids = olmoe_bf16
    other = MixtralForCausalLM(dataclasses.replace(model.cfg, **wrong))
    expected = olmoe_decoder.forward(params, ids, config, SEQ)
    result = logits_agreement(
        other.apply(params, ids[None])[0], expected, olmoe_decoder.TOLERANCE,
    )
    assert not result["ok"], result


@pytest.fixture(scope="module")
def both_gradients(olmoe_f32):
    config, model, params, ids = olmoe_f32
    targets = np.roll(ids, -1)
    system = jax.jit(jax.value_and_grad(
        lambda p: moe_lm_loss(model, p, ids[None], targets[None])
    ))(params)
    expected = jax.jit(jax.value_and_grad(
        lambda p: olmoe_decoder.loss(p, ids, targets, config)
    ))(params)
    return system, expected


def test_loss_agrees_with_the_reference(both_gradients):
    (loss, _), (expected, _) = both_gradients
    assert float(loss) == pytest.approx(float(expected), rel=1e-5)
    # the auxiliary term is in both: the cross-entropy alone is smaller
    assert float(loss) > np.log(512) * 0.9


def leaf(tree, path):
    for key in path:
        tree = tree[key]
    return np.asarray(tree)


@pytest.mark.parametrize("path", [
    ("layers_0", "moe", "router", "kernel"),
    ("layers_1", "moe", "router", "kernel"),
    ("layers_0", "moe", "w_gate"),
    ("layers_0", "moe", "w_up"),
    ("layers_0", "moe", "w_down"),
    ("layers_0", "attn", "q_norm", "scale"),
    ("layers_0", "attn", "k_norm", "scale"),
    ("layers_1", "attn", "q_norm", "scale"),
    ("lm_head", "kernel"),
    ("embed_tokens", "embedding"),
], ids="/".join)
def test_gradients_agree_with_the_references(both_gradients, path):
    (_, grads), (_, expected) = both_gradients
    got, want = leaf(grads["params"], path), leaf(expected["params"], path)
    assert got.shape == want.shape and np.abs(want).max() > 0
    if path[-1] in ("w_gate", "w_up", "w_down"):
        # one expert's matrices, the busiest's
        e = int(np.argmax(np.abs(want).reshape(want.shape[0], -1).sum(1)))
        got, want = got[e], want[e]
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-5 * np.abs(want).max())


def test_an_expert_that_no_token_chose_has_a_zero_gradient():
    """The weight-gradient kernel writes an expert's block when it leaves
    the expert's tiles: an empty expert keeps a tile of padding so that it
    is written, with zeros."""
    cfg = dataclasses.replace(
        MOE_CONFIGS["mixtral-tiny"], dtype=jnp.float32, moe_dispatch="gmm",
        num_experts=8, num_experts_per_tok=2,
    )
    layer = MoELayer(cfg)
    # Positive inputs and a router that prefers expert 0, then 1, for all.
    x = jnp.asarray(
        np.abs(np.random.RandomState(0).randn(1, 16, cfg.hidden_size)) + 1.0,
        jnp.float32,
    )
    params = layer.init(jax.random.PRNGKey(0), x)
    router = np.full((cfg.hidden_size, 8), -1.0, np.float32)
    router[:, 0], router[:, 1] = 1.0, 0.5
    params = {"params": {**params["params"],
                         "router": {"kernel": jnp.asarray(router)}}}
    grads = jax.grad(lambda p: (layer.apply(p, x) ** 2).sum())(params)["params"]
    for name in ("w_gate", "w_up", "w_down"):
        g = np.asarray(grads[name])
        assert np.isfinite(g).all(), name
        assert np.abs(g[:2]).max() > 0 and not g[2:].any(), name


@pytest.mark.parametrize("dispatch", ["gmm", "capacity", "ragged"])
def test_unnormalised_gates_scale_each_token_by_its_top_k_mass(dispatch):
    """norm_topk_prob false: a token's gates are its top-k probabilities as
    they are, so its output is the renormalised one times their sum, which
    is under one. True is the default and Mixtral's."""
    assert MixtralConfig().norm_topk_prob is True
    base = dataclasses.replace(
        MOE_CONFIGS["mixtral-tiny"], dtype=jnp.float32, moe_dispatch=dispatch,
        capacity_factor=2.0,  # experts / top-k: no pair is dropped
    )
    assert base.norm_topk_prob is True
    x = jnp.asarray(np.random.RandomState(1).randn(2, 32, base.hidden_size),
                    jnp.float32)
    renormalised = MoELayer(base)
    params = renormalised.init(jax.random.PRNGKey(2), x)
    as_they_are = MoELayer(dataclasses.replace(base, norm_topk_prob=False))
    probs = jax.nn.softmax(x @ params["params"]["router"]["kernel"], axis=-1)
    mass = jax.lax.top_k(probs, base.num_experts_per_tok)[0].sum(-1)
    assert float(mass.max()) < 0.999  # the gates do not sum to one
    np.testing.assert_allclose(
        np.asarray(as_they_are.apply(params, x)),
        np.asarray(renormalised.apply(params, x) * mass[..., None]),
        rtol=1e-5, atol=1e-6,
    )


def test_without_qk_norm_llama_is_what_it_was():
    """qk_norm false (the default): the parameter tree has no norm in the
    attention, and the logits are the dense reference's, which has none."""
    cfg = dataclasses.replace(
        CONFIGS["llama-tiny"], dtype=jnp.float32, remat=False)
    assert cfg.qk_norm is False
    ids = np.random.default_rng(3).integers(0, cfg.vocab_size, 64).astype(np.int32)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), ids[None, :8])
    assert sorted(params["params"]["layers_0"]["attn"]) == [
        "k_proj", "o_proj", "q_proj", "v_proj"]
    assert sorted(params["params"]) == [
        "embed_tokens", "final_norm", "layers_0", "layers_1", "lm_head"]
    config = {"num_hidden_layers": cfg.num_layers, "rms_norm_eps": cfg.rms_eps,
              "rope_theta": cfg.rope_theta}
    result = logits_agreement(
        model.apply(params, ids[None])[0],
        dense_decoder.forward(params, ids, config, 64),
        {"per_position_rel_err": 1e-4, "min_share_within": 1.0},
    )
    assert result["ok"], result
    # with it, the tree gains the two scales and the function changes
    normed = LlamaForCausalLM(dataclasses.replace(cfg, qk_norm=True))
    with_norms = normed.init(jax.random.PRNGKey(0), ids[None, :8])
    attn = with_norms["params"]["layers_0"]["attn"]
    assert sorted(attn) == ["k_norm", "k_proj", "o_proj", "q_norm", "q_proj",
                            "v_proj"]
    assert attn["q_norm"]["scale"].shape == (cfg.num_heads * cfg.head_dim_,)
    assert attn["k_norm"]["scale"].shape == (cfg.num_kv_heads * cfg.head_dim_,)
    assert cfg.num_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    assert dataclasses.replace(cfg, qk_norm=True).num_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(with_norms))
    assert not np.allclose(
        normed.apply(with_norms, ids[None]), model.apply(params, ids[None]),
        atol=1e-3,
    )


@pytest.mark.parametrize("tied", [False, True])
def test_mixtral_honours_tie_embeddings(tied):
    cfg = dataclasses.replace(
        MOE_CONFIGS["mixtral-tiny"], dtype=jnp.float32, remat=False,
        moe_dispatch="capacity", tie_embeddings=tied,
    )
    model = MixtralForCausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    table = params["params"]["embed_tokens"]["embedding"]
    head = lm_head_weight(params)
    assert head.shape == (cfg.vocab_size, cfg.hidden_size)
    assert ("lm_head" in params["params"]) is (not tied)
    if tied:
        assert head is table
    else:
        np.testing.assert_array_equal(
            head, params["params"]["lm_head"]["kernel"].T)
        assert not np.allclose(head, table)
    assert model.apply(params, ids).shape == (1, 8, cfg.vocab_size)


def matrices(params):
    """{path: std} of every weight matrix and the embedding."""
    return {
        "/".join(k.key for k in path): float(np.asarray(a, np.float32).std())
        for path, a in jax.tree_util.tree_leaves_with_path(params["params"])
        if a.ndim >= 2
    }


@pytest.mark.parametrize("model_cls, cfg", [
    (LlamaForCausalLM, CONFIGS["llama-tiny"]),
    (MixtralForCausalLM, dataclasses.replace(
        MOE_CONFIGS["mixtral-tiny"], moe_dispatch="capacity")),
], ids=["llama", "mixtral"])
def test_initializer_range_draws_every_matrix_as_the_architecture_does(model_cls, cfg):
    """A float: every weight matrix and the embedding normal(0, that), each
    expert's own among them (the published ``_init_weights``). None, the
    default: flax's draws, a matrix's 1 / sqrt(fan-in)."""
    assert cfg.initializer_range is None
    cfg = dataclasses.replace(cfg, param_dtype=jnp.float32, tie_embeddings=False)
    ids = jnp.zeros((1, 8), jnp.int32)
    drawn = matrices(model_cls(
        dataclasses.replace(cfg, initializer_range=0.02)
    ).init(jax.random.PRNGKey(0), ids))
    assert {"embed_tokens/embedding", "lm_head/kernel",
            "layers_0/attn/q_proj/kernel", "layers_0/attn/o_proj/kernel"} <= set(drawn)
    for path, std in drawn.items():
        assert std == pytest.approx(0.02, rel=0.1), path
    default = matrices(model_cls(cfg).init(jax.random.PRNGKey(0), ids))
    assert default["layers_0/attn/q_proj/kernel"] == pytest.approx(
        cfg.hidden_size ** -0.5, rel=0.1)
    assert default["lm_head/kernel"] == pytest.approx(cfg.hidden_size ** -0.5, rel=0.1)
    if model_cls is MixtralForCausalLM:
        assert "layers_1/moe/router/kernel" in drawn
        # flax reads the stacked [E, in, out] as one matrix of fan-in E x in:
        # the Mixtral cell's draw, sqrt(E) small (PERF.md, Open questions)
        assert default["layers_0/moe/w_gate"] == pytest.approx(
            (cfg.num_experts * cfg.hidden_size) ** -0.5, rel=0.1)
        assert default["layers_0/moe/w_down"] == pytest.approx(
            (cfg.num_experts * cfg.intermediate_size) ** -0.5, rel=0.1)
