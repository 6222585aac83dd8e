"""Xing4.0-29B-A4B's architecture through the program's models, on the CPU.

``Xing4ForCausalLM`` (a leading dense layer and expert layers, each under
latent attention with a q latent, on four hyper-connected residual streams
whose stream-to-stream map is Sinkhorn-projected; a multi-token-prediction
module beside the final norm; one expert-parallel rank's share of the routed
experts through the ``gmm`` dispatch in interpret mode) against the
benchmark's plain reference (``benchmarks/reference/xing4_decoder.py``) at the
configuration file's own ``rehearsal`` size on seeded random weights: both
heads' logits, both loss terms and gradients. Sinkhorn's projection and its
hand-written backward against the plain loop, the four ranks' shares of one
expert layer against the uncut reference, and ``MLAMixer`` with the q latent
off against what it was.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import sarvam_mla_decoder as sarvam_reference
from benchmarks.reference import xing4_decoder as reference
from benchmarks.tools import wrong_xing4
from ray_tpu.models.hyper_connections import (
    HyperConnection, HyperConnections, collapse_streams, expand_streams,
    sinkhorn, write_streams,
)
from ray_tpu.models.mixtral import MoELayer
from ray_tpu.models.mla import MLAConfig, MLAMixer, YarnScaling, yarn_scaling
from ray_tpu.models.xing4 import (
    Xing4Config, Xing4ForCausalLM, mtp_chunked_lm_loss, xing4_config,
)

SEQ = 128
CONFIG = f"{cells.BENCH_DIR}/configs/xing4-29b-a4b-l5.json"
PUBLISHED_YARN = YarnScaling(
    factor=64, original_max_position_embeddings=4096, beta_fast=32, beta_slow=1,
    mscale=1, mscale_all_dim=1,
)
LOOSE = {"per_position_rel_err": 1e-3, "min_share_within": 0.5}


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def xing4(dtype: str):
    """(configuration dict at its rehearsal size, model, params, ids)."""
    config = cells.load_json(CONFIG)
    config = {**config, **config["rehearsal"]}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": dtype, "param_dtype": dtype},
    }
    model = Xing4ForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    # Norm weights, gating factors and biases away from their initial values,
    # so that a norm left out, a map's term or an entry of b read at the wrong
    # place shows.
    rng = np.random.default_rng(3)
    p = jax.tree_util.tree_map(lambda a: a, params)["params"]
    layers = [p[f"layers_{i}"] for i in range(config["num_hidden_layers"])]
    for layer in layers + [p["mtp_layer"]]:
        scale = layer["mla"]["q_a_norm"]["scale"]
        layer["mla"]["q_a_norm"]["scale"] = jnp.asarray(
            rng.uniform(0.5, 1.5, scale.shape), scale.dtype)
        for hc in (layer["mixer_hc"], layer["ffn_hc"]):
            hc["alpha"] = jnp.asarray(rng.uniform(0.6, 1.4, 3), scale.dtype)
            for name in ("b_pre", "b_post", "b_res"):
                hc[name] = hc[name] + jnp.asarray(
                    rng.normal(size=hc[name].shape) * 0.5, scale.dtype)
    for name in ("mtp_hidden_norm", "mtp_embed_norm", "mtp_norm"):
        p[name]["scale"] = jnp.asarray(
            rng.uniform(0.5, 1.5, p[name]["scale"].shape), scale.dtype)
    return config, model, {"params": p}, ids


@pytest.fixture(scope="module")
def xing4_f32():
    return xing4("float32")


@pytest.fixture(scope="module")
def xing4_bf16():
    return xing4("bfloat16")


def test_the_configuration_builds_xing4s_program(xing4_f32):
    config, model, params, _ = xing4_f32
    cfg = model.cfg
    assert cfg.layers == (("mla", "mlp"),) + (("mla", "moe"),) * 4
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.num_shared_experts, cfg.moe_dispatch) == ("sigmoid", True, 2, 1, "gmm")
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (16, (0, 4), 4)
    # a held quarter moves its rows by gathers; the siblings' sixteenths walk
    assert cfg.held_rows == "gather" and Xing4Config().held_rows == "walk"
    assert (cfg.mla_rope, cfg.qk_head_norm, cfg.rope_scaling, cfg.q_lora_rank) == (
        True, False, PUBLISHED_YARN, 48)
    assert cfg.hyper_connections == HyperConnections(
        mult=4, sinkhorn_iters=20, eps=1e-6, clamp=(-30, 30), alpha_init=1.0,
        res_diagonal_init=2.0)
    assert cfg.remat and cfg.remat_prevent_cse and cfg.remat_policy == "nothing"
    p = params["params"]
    assert set(p["layers_0"]) == {"input_norm", "mla", "post_attn_norm", "mlp",
                                  "mixer_hc", "ffn_hc"}
    assert set(p["layers_4"]) == set(p["mtp_layer"]) == {
        "input_norm", "mla", "post_attn_norm", "moe", "mixer_hc", "ffn_hc"}
    assert {k for k in p if k.startswith("mtp")} == {
        "mtp_hidden_norm", "mtp_embed_norm", "mtp_proj", "mtp_layer", "mtp_norm"}
    assert p["mtp_proj"]["kernel"].shape == (256, 128)
    mla = p["layers_0"]["mla"]
    assert set(mla) == {"q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj",
                        "kv_a_norm", "kv_b_proj", "o_proj"}
    assert mla["q_a_proj"]["kernel"].shape == (128, 48)
    assert mla["q_b_proj"]["kernel"].shape == (48, 4, 32)
    hc = p["layers_2"]["ffn_hc"]
    assert {k: v.shape for k, v in hc.items()} == {
        "phi": (512, 24), "alpha": (3,), "b_pre": (4,), "b_post": (4,), "b_res": (4, 4)}
    moe = p["layers_1"]["moe"]
    assert moe["router"]["kernel"].shape == (128, 16)  # the router's width
    assert moe["w_gate"].shape == (4, 128, 64)  # the experts held
    # and at the published sizes the file gives the published architecture
    full = cells.program_config(cells.load_json(CONFIG))
    assert (full.hidden_size, full.intermediate_size, full.expert_width,
            full.num_heads, full.kv_lora_rank, full.q_lora_rank,
            full.qk_nope_head_dim, full.qk_rope_head_dim, full.v_head_dim,
            full.rms_eps, full.rope_theta) == (
        3584, 9216, 1024, 32, 512, 768, 128, 64, 128, 1e-6, 10000)
    assert (full.num_experts, full.experts_held, full.num_experts_per_tok,
            full.vocab_size, full.num_layers, full.tie_embeddings,
            full.num_nextn_predict_layers) == (64, (0, 16), 4, 32768, 5, False, 1)
    assert full.layers == cfg.layers and full.rope_scaling == PUBLISHED_YARN
    assert full.hyper_connections == cfg.hyper_connections
    assert full.param_dtype == full.dtype == jnp.bfloat16


def test_the_initial_values_are_the_files_assumed_ones():
    config = cells.load_json(CONFIG)
    config = {**config, **config["rehearsal"]}
    model = Xing4ForCausalLM(cells.program_config(config))
    p = jax.jit(model.init)(jax.random.PRNGKey(1), np.zeros((1, 8), np.int32))["params"]
    hc = p["mtp_layer"]["mixer_hc"]
    assert np.asarray(hc["alpha"], np.float32).tolist() == [config["hc_alpha_init"]] * 3
    assert not np.asarray(hc["b_pre"], np.float32).any()
    assert not np.asarray(hc["b_post"], np.float32).any()
    np.testing.assert_array_equal(
        np.asarray(hc["b_res"], np.float32), config["hc_res_diagonal_init"] * np.eye(4))
    assert np.asarray(hc["phi"], np.float32).std() == pytest.approx(
        config["initializer_range"], rel=0.05)


@pytest.mark.parametrize("kind", ["yarn", "deepseek_yarn"])
def test_yarn_by_either_name_is_the_same_scaling(kind):
    scaling = {**cells.load_json(CONFIG)["rope_scaling"], "type": kind}
    assert yarn_scaling(scaling) == PUBLISHED_YARN
    assert yarn_scaling(None) is None
    with pytest.raises(ValueError, match="linear"):
        yarn_scaling({"type": "linear", "factor": 2})


# ----------------------------------------------- the model against the reference


def test_logits_agree_with_the_reference_in_float32(xing4_f32):
    config, model, params, ids = xing4_f32
    system = model.apply(params, ids[None])[0]
    expected = reference.forward(params, ids, config, SEQ)
    assert system.dtype == jnp.float32
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 2e-4, "min_share_within": 1.0})
    assert result["ok"], result


def further_logits(model, params, ids, targets):
    _, predicted = model.apply(params, ids[None], return_hidden=True, next_ids=targets[None])
    return predicted[0].astype(jnp.float32) @ params["params"]["lm_head"]["kernel"].astype(jnp.float32)


def test_the_modules_logits_agree_with_the_reference_in_float32(xing4_f32):
    config, model, params, ids = xing4_f32
    targets = np.roll(ids, -1)
    with jax.default_matmul_precision("highest"):
        system = further_logits(model, params, ids, targets)
    expected = reference.mtp_logits(params, ids, targets, config, SEQ)
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 2e-4, "min_share_within": 1.0})
    assert result["ok"], result
    # and they are not the main head's
    main = reference.forward(params, ids, config, SEQ)
    assert not logits_agreement(system, main, LOOSE)["ok"]


def test_logits_in_bfloat16_are_near_the_reference_and_not_it(xing4_bf16):
    config, model, params, ids = xing4_bf16
    system = model.apply(params, ids[None])[0]
    expected = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 0.1, "min_share_within": 0.9})
    assert result["ok"], result
    assert result["rel_err_median"] > 1e-4  # the system is not the reference


@pytest.mark.parametrize("wrong", sorted(wrong_xing4.programs(Xing4Config(
    rope_scaling=PUBLISHED_YARN))))
def test_a_wrong_program_is_far_from_the_reference(xing4_f32, wrong):
    """``wrong_xing4.py``'s programs of another function, in float32."""
    config, model, params, ids = xing4_f32
    (other,) = wrong_xing4.programs(model.cfg)[wrong]
    result = logits_agreement(
        Xing4ForCausalLM(other).apply(params, ids[None])[0],
        reference.forward(params, ids, config, SEQ), LOOSE)
    assert not result["ok"], result


@pytest.mark.parametrize("wrong", sorted(wrong_xing4.references(None)))
def test_a_wrong_reference_is_far_from_the_program(xing4_f32, wrong, monkeypatch):
    """``wrong_xing4.py``'s references of another function: Sinkhorn on rows
    alone, H_post without its 2, constant maps, an un-normed q latent."""
    config, model, params, ids = xing4_f32
    name, replacement = wrong_xing4.references(None)[wrong]
    monkeypatch.setattr(reference, name, replacement(getattr(reference, name)))
    result = logits_agreement(
        model.apply(params, ids[None])[0],
        reference.forward(params, ids, config, SEQ), LOOSE)
    assert not result["ok"], result


@pytest.fixture(scope="module")
def both_gradients(xing4_f32):
    config, model, params, ids = xing4_f32
    targets = np.roll(ids, -1)
    system = jax.jit(jax.value_and_grad(
        lambda p: mtp_chunked_lm_loss(
            model, p, ids[None], targets[None], chunk_size=64, mtp_weight=0.3)
    ))(params)
    expected = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, ids, targets, config)
    ))(params)
    return system, expected


@pytest.mark.parametrize("term", ["main", "mtp", "sum"])
def test_each_loss_term_agrees_with_the_references(xing4_f32, both_gradients, term):
    config, model, params, ids = xing4_f32
    targets = np.roll(ids, -1)
    main, mtp = (float(v) for v in reference.loss_terms(params, ids, targets, config))
    assert config["mtp_loss_weight"] == 0.3 and abs(main - mtp) > 1e-3
    loss = lambda weight: float(mtp_chunked_lm_loss(  # noqa: E731
        model, params, ids[None], targets[None], chunk_size=64, mtp_weight=weight))
    if term == "main":
        assert loss(0.0) == pytest.approx(main, rel=1e-5)
    elif term == "mtp":
        assert loss(1.0) - loss(0.0) == pytest.approx(mtp, rel=1e-4)
    else:
        (value, _), (expected, _) = both_gradients
        assert float(value) == pytest.approx(main + 0.3 * mtp, rel=1e-5)
        assert float(expected) == pytest.approx(main + 0.3 * mtp, rel=1e-6)


def leaf(tree, path):
    for key in path:
        tree = tree[key]
    return np.asarray(tree)


@pytest.mark.parametrize("path", [
    ("layers_0", "mixer_hc", "phi"),
    ("layers_0", "mixer_hc", "alpha"),
    ("layers_0", "mixer_hc", "b_pre"),
    ("layers_0", "mixer_hc", "b_post"),
    ("layers_0", "mixer_hc", "b_res"),
    ("layers_0", "ffn_hc", "phi"),
    ("layers_0", "ffn_hc", "b_res"),
    ("layers_0", "mla", "q_a_proj", "kernel"),
    ("layers_0", "mla", "q_a_norm", "scale"),
    ("layers_0", "mla", "q_b_proj", "kernel"),
    ("layers_0", "mla", "kv_a_proj", "kernel"),
    ("layers_0", "mla", "kv_b_proj", "kernel"),
    ("layers_0", "mla", "o_proj", "kernel"),
    ("layers_0", "mlp", "down_proj", "kernel"),
    ("layers_0", "input_norm", "scale"),
    ("layers_1", "mixer_hc", "alpha"),
    ("layers_1", "ffn_hc", "phi"),
    ("layers_1", "moe", "w_gate"),
    ("layers_1", "moe", "shared", "up_proj", "kernel"),
    ("layers_1", "moe", "router", "kernel"),
    ("layers_3", "mla", "q_b_proj", "kernel"),
    ("layers_3", "ffn_hc", "b_post"),
    ("layers_4", "mixer_hc", "b_res"),
    ("layers_4", "moe", "w_down"),
    ("mtp_hidden_norm", "scale"),
    ("mtp_embed_norm", "scale"),
    ("mtp_proj", "kernel"),
    ("mtp_layer", "mixer_hc", "phi"),
    ("mtp_layer", "mla", "q_a_proj", "kernel"),
    ("mtp_layer", "moe", "w_up"),
    ("mtp_layer", "ffn_hc", "alpha"),
    ("mtp_norm", "scale"),
    ("final_norm", "scale"),
    ("lm_head", "kernel"),
    ("embed_tokens", "embedding"),
], ids="/".join)
def test_gradients_agree_with_the_references(both_gradients, path):
    (_, grads), (_, expected) = both_gradients
    got, want = leaf(grads["params"], path), leaf(expected["params"], path)
    assert got.shape == want.shape and np.abs(want).max() > 0
    # b_res's gradient is what is left after Sinkhorn has projected the shifts
    # of whole rows and columns away: differences of nearly equal numbers
    loose = 2e-2 if path[-1] == "b_res" else 5e-5
    np.testing.assert_allclose(got, want, rtol=5e-3, atol=loose * np.abs(want).max())


def test_no_gradient_reaches_the_selection_bias_and_the_router_learns(both_gradients):
    (_, grads), (_, expected) = both_gradients
    for name in ("layers_1", "layers_4", "mtp_layer"):
        for tree in (grads, expected):
            moe = tree["params"][name]["moe"]
            assert not np.asarray(moe["router_bias"]).any()
            assert np.asarray(moe["router"]["kernel"]).any()


# ---------------------------------------------------------------- Sinkhorn


def plain_sinkhorn(logits, iters=20, eps=1e-6, clamp=(-30.0, 30.0)):
    """The loop as it is written down, on [..., n, n]."""
    m = jnp.exp(jnp.clip(logits, *clamp))
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def token_minor(a):
    """[T, n, n] as the program lays it: [n, n, T]."""
    return jnp.moveaxis(a, 0, -1)


@pytest.fixture(scope="module")
def logits():
    rng = np.random.default_rng(5)
    # a standard deviation of 2.4, the cell's, and a few entries past the clamp
    out = rng.normal(size=(96, 4, 4)) * 2.4 + 2.0 * np.eye(4)
    out[3, 1, 2], out[7, 0, 0] = 41.0, -35.0
    return jnp.asarray(out, jnp.float32)


@pytest.mark.parametrize("iters", [1, 5, 20])
def test_sinkhorn_is_the_plain_loop(logits, iters):
    got = sinkhorn(token_minor(logits), iters, 1e-6, (-30.0, 30.0))
    want = plain_sinkhorn(logits, iters)
    np.testing.assert_allclose(got, token_minor(want), rtol=1e-5, atol=1e-7)


def test_sinkhorns_rows_and_columns_sum_to_one_after_20_iterations(logits):
    """Within 1e-4 where the logits are moderate (a standard deviation of
    0.8). At the cell's initial values (2.4, and entries at the clamp) the
    columns, normalised last, still are, and twenty rounds leave some tokens'
    rows a few per cent off: the source's 20 is kept, not run to convergence."""
    mild = token_minor(logits[8:] / 3.0)  # without the entries past the clamp
    m = np.asarray(sinkhorn(mild, 20, 1e-6, (-30.0, 30.0)))
    assert (m > 0).all()
    np.testing.assert_allclose(m.sum(axis=0), 1.0, atol=1e-4)  # columns
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-4)  # rows
    wide = np.asarray(sinkhorn(token_minor(logits), 20, 1e-6, (-30.0, 30.0)))
    np.testing.assert_allclose(wide.sum(axis=0), 1.0, atol=1e-4)
    off = np.abs(wide.sum(axis=1) - 1.0)
    assert 1e-3 < off.max() < 0.1 and np.median(off) < 1e-3
    # one iteration leaves the rows far from it: what the wrong program reads
    once = np.asarray(sinkhorn(mild, 1, 1e-6, (-30.0, 30.0)))
    assert np.abs(once.sum(axis=1) - 1.0).max() > 0.05


@pytest.mark.parametrize("iters", [1, 20])
def test_sinkhorns_backward_is_jax_grad_of_the_plain_loop(logits, iters):
    weights = jnp.asarray(np.random.default_rng(6).normal(size=logits.shape), jnp.float32)
    got = jax.grad(lambda l: jnp.sum(
        sinkhorn(token_minor(l), iters, 1e-6, (-30.0, 30.0)) * token_minor(weights)))(logits)
    want = jax.grad(lambda l: jnp.sum(plain_sinkhorn(l, iters) * weights))(logits)
    assert np.abs(np.asarray(want)).max() > 1e-3
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6)
    # an entry past the clamp takes no gradient, in both
    assert got[3, 1, 2] == want[3, 1, 2] == 0 and got[7, 0, 0] == want[7, 0, 0] == 0


def test_sinkhorns_lowered_loop_holds_no_reduction(logits):
    """Forward and backward are written-out sums: nothing for XLA to split
    the loop's one fusion at."""
    text = jax.jit(jax.grad(lambda l: jnp.sum(
        sinkhorn(l, 20, 1e-6, (-30.0, 30.0)) ** 2))).lower(token_minor(logits)).as_text()
    assert text.count("stablehlo.reduce") <= 1  # the test's own sum


# ------------------------------------------------- one hyper-connection alone


def connection(x, seed=0):
    hc = HyperConnections()
    module = HyperConnection(hc, 1e-6, jax.nn.initializers.normal(0.3), jnp.float32)
    params = module.init(jax.random.PRNGKey(seed), x)["params"]
    rng = np.random.default_rng(seed)
    params = {**params, "alpha": jnp.asarray(rng.uniform(0.5, 1.5, 3), jnp.float32),
              **{k: params[k] + jnp.asarray(rng.normal(size=params[k].shape) * 0.5, jnp.float32)
                 for k in ("b_pre", "b_post", "b_res")}}
    return module, params


def test_one_hyper_connection_is_the_references():
    rng = np.random.default_rng(2)
    streams = jnp.asarray(rng.normal(size=(4, 2, 24, 16)), jnp.float32)  # [n, B, T, C]
    y = jnp.asarray(rng.normal(size=(2, 24, 16)), jnp.float32)
    module, params = connection(streams)
    u, (post, res) = module.apply({"params": params}, streams)
    out = write_streams(streams, y, post, res)
    cfg = {"rms_norm_eps": 1e-6, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
           "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30}
    tokens = jnp.moveaxis(streams, 0, 2).reshape(48, 4, 16)  # [T, n, C]
    with jax.default_matmul_precision("highest"):
        want_pre, want_post, want_res = reference.connection_maps(params, tokens, cfg)
        want = reference.hyper_connected(
            params, tokens, lambda v: y.reshape(48, 16) + 0 * v, cfg)
    np.testing.assert_allclose(post.reshape(4, 48).T, want_post, rtol=1e-4)
    np.testing.assert_allclose(res.reshape(4, 4, 48).transpose(2, 0, 1), want_res,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        u.reshape(48, 16), jnp.einsum("tn,tnc->tc", want_pre, tokens), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        jnp.moveaxis(out, 0, 2).reshape(48, 4, 16), want, rtol=1e-4, atol=1e-5)


def test_the_streams_start_as_copies_and_end_as_their_sum():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 16)), jnp.bfloat16)
    streams = expand_streams(x, 4)
    assert streams.shape == (4, 2, 8, 16) and streams.dtype == jnp.bfloat16
    assert all((streams[i] == x).all() for i in range(4))
    np.testing.assert_array_equal(
        np.asarray(collapse_streams(streams), np.float32),
        np.asarray((4 * x.astype(jnp.float32)).astype(jnp.bfloat16), np.float32))


# ------------------------------------------------- the expert layer alone


def expert_layer(held, **over):
    """One expert layer at Xing4's routing: 64 experts scored, top-4,
    sigmoid, renormalised, x 2, one shared expert; ``held`` of them here."""
    cfg = Xing4Config(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_experts=64, num_experts_per_tok=4, num_shared_experts=1,
        routed_scaling_factor=2.0, experts_held=held, initializer_range=0.5,
        dtype=jnp.float32, param_dtype=jnp.float32, **over,
    )
    return MoELayer(cfg)


def layer_config(held) -> dict:
    """The reference's keys for that layer."""
    lo, hi = held or (0, 64)
    return {"n_routed_experts": 64, "num_experts": hi - lo,
            "expert_rank": lo // (hi - lo), "num_experts_per_tok": 4,
            "norm_topk_prob": True, "routed_scaling_factor": 2,
            "n_shared_experts": 1}


@pytest.mark.parametrize("held_rows", ["walk", "gather"])
def test_the_four_ranks_shares_add_up_to_the_uncut_layer(held_rows):
    """Four ranks of sixteen experts each, the deployment's division: the
    routed parts they give, with the shared expert (which every rank computes
    alike) counted once, are the uncut reference's expert layer, whichever
    way a rank's rows reach their slots."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 48, 32)), jnp.float32)
    params = expert_layer(None).init(jax.random.PRNGKey(1), x)["params"]
    bias = np.random.default_rng(2).normal(size=64).astype(np.float32) * 0.3
    params = {**params, "router_bias": jnp.asarray(bias)}
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.moe(params, tokens, layer_config(None))
        shared = reference.swiglu(params["shared"], tokens)
    total, pairs = 0.0, 0
    for rank in range(4):
        held = (16 * rank, 16 * rank + 16)
        mine = {**params, **{k: params[k][held[0]:held[1]]
                             for k in ("w_gate", "w_up", "w_down")}}
        layer = expert_layer(held, held_rows=held_rows)
        out = layer.apply({"params": mine}, x).reshape(-1, 32)
        with jax.default_matmul_precision("highest"):
            want = reference.moe(mine, tokens, layer_config(held))
            gates = reference.router_gates(params, tokens, layer_config(held))
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        pairs += int((np.asarray(gates)[:, held[0]:held[1]] > 0).sum())
        total = total + (out - shared)
    assert pairs == 96 * 4  # every pair is held by exactly one rank
    np.testing.assert_allclose(total + shared, uncut, rtol=1e-4, atol=2e-5)
    # gates: four a token, renormalised, times 2
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.0, rtol=1e-5)
    assert ((np.asarray(gates) > 0).sum(-1) == 4).all()


@pytest.fixture
def fresh_traces():
    """The road that gathers keeps what it traced (``mixtral._held_inlined``):
    a test that patches what a trace calls starts from none and leaves none.
    Named before ``monkeypatch``, it is torn down after the patches are."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def routed(params, rank, routing):
    """``params`` with a selection bias that sends a rank of sixteen experts
    no pair, its share as the router scores, or every pair."""
    bias = np.zeros(64, np.float32)
    if routing == "none-here":
        bias[16 * (rank ^ 1):16 * (rank ^ 1) + 16] = 10.0
    elif routing == "every-pair-here":
        bias[16 * rank:16 * rank + 16] = 10.0
    return {**params, "router_bias": jnp.asarray(bias)}


@pytest.mark.parametrize("rank, routing", [
    (0, "expected-share"), (1, "expected-share"), (3, "expected-share"),
    (1, "none-here"), (3, "every-pair-here"),
])
def test_gathered_rows_give_what_walked_rows_give(rank, routing, fresh_traces, monkeypatch):
    """``held_rows`` "gather" against "walk" and against the uncut layer: the
    same result and the same gradients (x, the router through the gates, the
    three expert matrices), with NaN in every row of a bounded buffer that
    the road should leave alone (past ``tiles_used`` in what the grouped
    matmuls return, everywhere in what the loops are handed to fill), so
    that a pass that read one would show. The uncut layer is the 64 experts'
    with a zero down-projection in the 48 that are elsewhere: they add
    nothing and pass no gradient."""
    from ray_tpu.ops import gmm as G

    plain, bounded = G._gmm_pallas, []

    def poisoned(lhs, rhs, tile_group, block_m, transpose_rhs=False,
                 tiles_used=None):
        out = plain(lhs, rhs, tile_group, block_m, transpose_rhs, tiles_used)
        if tiles_used is None:
            return out
        bounded.append(transpose_rhs)
        past = jnp.arange(out.shape[0])[:, None] >= tiles_used[0] * block_m
        return jnp.where(past, jnp.nan, out)

    x = jnp.asarray(np.random.default_rng(7).normal(size=(1, 192, 32)), jnp.float32)
    params = routed(
        expert_layer(None).init(jax.random.PRNGKey(2), x)["params"], rank, routing)
    held = (16 * rank, 16 * rank + 16)
    here = (np.arange(64) >= held[0]) & (np.arange(64) < held[1])
    params["w_down"] = params["w_down"] * here[:, None, None]
    w = jnp.asarray(np.random.default_rng(8).normal(size=x.shape), jnp.float32)

    def share(tree):
        return {**tree, **{k: tree[k][held[0]:held[1]]
                           for k in ("w_gate", "w_up", "w_down")}}

    mine = share(params)

    def readings(held, p, **over):
        layer = expert_layer(held, **over)
        return jax.value_and_grad(
            lambda p, x: (layer.apply({"params": p}, x) * w).sum(), (0, 1)
        )(p, x)

    want, want_grads = readings(held, mine, held_rows="walk")
    uncut, (uncut_params, uncut_x) = readings(None, params)
    uncut_grads = (share(uncut_params), uncut_x)
    monkeypatch.setattr(G, "_gmm_pallas", poisoned)
    monkeypatch.setattr(
        G, "unwritten", lambda shape, dtype, after: jnp.full(shape, jnp.nan, dtype))
    got, got_grads = readings(held, mine, held_rows="gather")
    # three grouped matmuls forward (traced as the function and again as its
    # forward rule), three back to rows: all told where to stop
    assert bounded == [False] * 6 + [True] * 3
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(got) == pytest.approx(float(uncut), rel=1e-4)
    flat = jax.tree_util.tree_leaves_with_path
    for other, rtol in ((want_grads, 1e-4), (uncut_grads, 1e-3)):
        for (path, a), (_, b) in zip(flat(got_grads), flat(other)):
            assert np.isfinite(np.asarray(a)).all(), path
            np.testing.assert_allclose(
                a, b, rtol=rtol, atol=1e-5 * max(float(np.abs(b).max()), 1e-9),
                err_msg=jax.tree_util.keystr(path))
    reached = np.abs(np.asarray(got_grads[0]["router"]["kernel"])).max() > 0
    assert reached == (routing != "none-here")


def test_held_rows_takes_one_of_two_names():
    x = jnp.zeros((1, 32, 32), jnp.float32)
    with pytest.raises(ValueError, match="held_rows"):
        expert_layer((0, 16), held_rows="scatter").init(jax.random.PRNGKey(0), x)


# ------------------------------------ the mixer with and without the q latent


MIXER = dict(
    hidden_size=32, num_heads=2, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, mla_rope=True, rope_scaling=PUBLISHED_YARN,
    initializer_range=0.3, rms_eps=1e-6, dtype=jnp.float32, param_dtype=jnp.float32,
)
MIXER_KEYS = {"kv_lora_rank": 16, "q_lora_rank": 24, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
              "use_qk_norm": False}


@pytest.mark.parametrize("q_lora_rank", [None, 24], ids=["off", "on"])
def test_the_q_latent_is_off_where_a_config_has_none(q_lora_rank):
    """Off, ``MLAMixer`` has the parameters it had and gives what the sarvam
    reference's mixer gives; on, q goes through ``q_a_proj``, a norm and
    ``q_b_proj`` and the mixer is the Xing4 reference's."""
    cfg = MLAConfig(q_lora_rank=q_lora_rank, **MIXER)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 64, 32)), jnp.float32)
    positions = jnp.arange(64)[None]
    params = MLAMixer(cfg).init(jax.random.PRNGKey(4), x, positions)["params"]
    shared = {"kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj"}
    out = MLAMixer(cfg).apply({"params": params}, x, positions)[0]
    keys = {**MIXER_KEYS, "rope_scaling": {**cells.load_json(CONFIG)["rope_scaling"]}}
    with jax.default_matmul_precision("highest"):
        if q_lora_rank is None:
            assert set(params) == shared | {"q_proj"}
            keys["rope_scaling"]["type"] = "deepseek_yarn"
            want = sarvam_reference.mla(params, x[0], keys)
        else:
            assert set(params) == shared | {"q_a_proj", "q_a_norm", "q_b_proj"}
            want = reference.mla(params, x[0], keys)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)


def test_the_sibling_models_have_neither_a_q_latent_nor_streams():
    """Both are off where a config does not say so: the two sibling models'
    lowered steps are what they were (PERF.md, PR 39, has the hashes)."""
    from ray_tpu.models.kimi_linear import KimiLinearConfig
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.models.sarvam_mla import SarvamMLAConfig

    for config in (MLAConfig(), SarvamMLAConfig(), KimiLinearConfig()):
        assert config.q_lora_rank is None and config.hyper_connections is None
    assert LlamaConfig().hyper_connections is None
    assert Xing4Config().q_lora_rank == 768
    assert Xing4Config().hyper_connections == HyperConnections()
    with pytest.raises(ValueError, match="multi-token"):
        xing4_config(num_experts_held=4, num_nextn_predict_layers=2)


def test_the_tool_that_reads_the_modules_head_walks_on_the_cpu(tmp_path, monkeypatch):
    """``wrong_xing4.py`` as a script at the rehearsal size: the module's
    logits against the reference's ``mtp_logits``, and the first loss against
    the reference's ``loss``."""
    import json
    import sys

    monkeypatch.setattr(sys, "argv", [
        "wrong_xing4.py", "--seeds", "4000000001", "--rehearse", "--out", str(tmp_path)])
    importlib.reload(wrong_xing4).main()
    (line,) = (tmp_path / f"{wrong_xing4.CELL}.mtp.jsonl").read_text().splitlines()
    line = json.loads(line)
    assert line["seed"] == 4000000001 and line["positions"] == 64
    assert line["mtp_logits"]["rel_err_median"] < 0.05
    assert line["loss_rel_err"] < 5e-3
    assert line["reference_loss"] == pytest.approx(
        line["reference_main"] + 0.3 * line["reference_mtp"])
