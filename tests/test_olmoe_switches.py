"""The switches OLMoE's architecture added to the models, each off where a
configuration has none: Llama without a QK norm is what it was, Mixtral
honours tied embeddings, and ``initializer_range`` draws every matrix as the
architecture does (``tests/test_olmoe_model.py`` has the model against its
reference).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import dense_decoder
from ray_tpu.models import CONFIGS, LlamaForCausalLM
from ray_tpu.models.llama import lm_head_weight
from ray_tpu.models.mixtral import CONFIGS as MOE_CONFIGS, MixtralForCausalLM


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


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
    logits = jax.jit(model.apply)(params, ids[None])
    result = logits_agreement(
        logits[0],
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
        jax.jit(normed.apply)(with_norms, ids[None]), logits,
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
