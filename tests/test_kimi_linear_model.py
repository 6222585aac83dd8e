"""Kimi-Linear's architecture through the program's models, on the CPU.

``KimiLinearForCausalLM`` (a leading dense layer under KDA, KDA + experts,
MLA + experts; sigmoid routing with a selection bias, a shared expert, one
expert-parallel rank's share of the routed experts through the ``gmm``
dispatch, the Pallas kernels in interpret mode) against the benchmark's
plain reference (``benchmarks/reference/kimi_linear_decoder.py``) at the
configuration file's own ``rehearsal`` size on seeded random weights:
logits, loss and gradients. And the test that ties the share to the model:
the routed parts that all the ranks give, with the shared expert counted
once, add up to the uncut reference's expert layer.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import kimi_linear_decoder as reference
from ray_tpu.models.kimi_linear import KimiLinearConfig, KimiLinearForCausalLM
from ray_tpu.models.llama import chunked_causal_lm_loss
from ray_tpu.models.mixtral import MoELayer

SEQ = 128
CONFIG = f"{cells.BENCH_DIR}/configs/kimi-linear-48b-a3b-l5.json"


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted, and
    # with them the scan kernels of ops/kda.py.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def kimi(dtype: str):
    """(configuration dict at its rehearsal size, model, params, ids)."""
    config = cells.load_json(CONFIG)
    config = {**config, **config["rehearsal"]}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": dtype, "param_dtype": dtype},
    }
    model = KimiLinearForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    return config, model, params, ids


@pytest.fixture(scope="module")
def kimi_f32():
    return kimi("float32")


@pytest.fixture(scope="module")
def kimi_bf16():
    return kimi("bfloat16")


def test_the_configuration_builds_kimi_linears_program(kimi_f32):
    config, model, params, _ = kimi_f32
    cfg = model.cfg
    # each kind of layer is present: leading dense, KDA + experts, MLA + experts
    assert cfg.layers == (("kda", "mlp"), ("kda", "moe"), ("kda", "moe"),
                          ("mla", "moe"), ("kda", "moe"))
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.num_shared_experts, cfg.moe_dispatch) == (
        "sigmoid", True, 2.446, 1, "gmm")
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (
        16, (0, 4), 4)
    p = params["params"]
    assert set(p["layers_0"]) == {"input_norm", "kda", "post_attn_norm", "mlp"}
    assert set(p["layers_3"]) == {"input_norm", "mla", "post_attn_norm", "moe"}
    moe = p["layers_1"]["moe"]
    assert moe["router"]["kernel"].shape == (128, 16)  # the router's width
    assert moe["w_gate"].shape == (4, 128, 64)  # the experts held
    assert moe["router_bias"].shape == (16,) and not np.asarray(moe["router_bias"]).any()
    # and at the published sizes the file gives the published architecture
    full = cells.program_config(cells.load_json(CONFIG))
    assert (full.hidden_size, full.intermediate_size, full.expert_width,
            full.num_heads, full.kda_num_heads, full.kda_head_dim,
            full.short_conv_kernel_size, full.kv_lora_rank,
            full.qk_nope_head_dim, full.qk_rope_head_dim, full.v_head_dim) == (
        2304, 9216, 1024, 32, 32, 128, 4, 512, 128, 64, 128)
    assert (full.num_experts, full.experts_held, full.num_experts_per_tok,
            full.vocab_size, full.num_layers) == (256, (0, 16), 8, 20480, 5)
    assert full.layers == cfg.layers


def test_logits_agree_with_the_reference_in_float32(kimi_f32):
    config, model, params, ids = kimi_f32
    system = model.apply(params, ids[None])[0]
    expected = reference.forward(params, ids, config, SEQ)
    assert system.dtype == jnp.float32
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 2e-4, "min_share_within": 1.0}
    )
    assert result["ok"], result


def test_logits_in_bfloat16_are_near_the_reference_and_not_it(kimi_bf16):
    config, model, params, ids = kimi_bf16
    system = model.apply(params, ids[None])[0]
    expected = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(
        system, expected, {"per_position_rel_err": 0.1, "min_share_within": 0.9}
    )
    assert result["ok"], result
    assert result["rel_err_median"] > 1e-4  # the system is not the reference


@pytest.mark.parametrize("wrong", [
    {"routed_scaling_factor": 1.0},  # the 2.446 left out
    {"num_shared_experts": 0},  # the shared expert left out
    {"norm_topk_prob": False},  # gates not renormalised
    {"experts_held": (4, 8)},  # another rank's experts
], ids=lambda w: "-".join(w))
def test_a_program_of_another_function_is_far_from_the_reference(kimi_f32, wrong):
    config, model, params, ids = kimi_f32
    other = KimiLinearForCausalLM(dataclasses.replace(model.cfg, **wrong))
    expected = reference.forward(params, ids, config, SEQ)
    result = logits_agreement(
        other.apply(params, ids[None])[0], expected,
        {"per_position_rel_err": 1e-3, "min_share_within": 0.5},
    )
    assert not result["ok"], result


@pytest.fixture(scope="module")
def both_gradients(kimi_f32):
    config, model, params, ids = kimi_f32
    targets = np.roll(ids, -1)
    system = jax.jit(jax.value_and_grad(
        lambda p: chunked_causal_lm_loss(
            model, p, ids[None], targets[None], chunk_size=64)
    ))(params)
    expected = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, ids, targets, config)
    ))(params)
    return system, expected


def test_the_chunked_loss_takes_the_model_and_agrees_with_the_reference(both_gradients):
    (loss, _), (expected, _) = both_gradients
    assert float(loss) == pytest.approx(float(expected), rel=1e-5)


def leaf(tree, path):
    for key in path:
        tree = tree[key]
    return np.asarray(tree)


@pytest.mark.parametrize("path", [
    ("layers_0", "kda", "q_proj", "kernel"),
    ("layers_0", "kda", "k_conv"),
    ("layers_0", "kda", "A_log"),
    ("layers_0", "kda", "dt_bias"),
    ("layers_0", "kda", "f_a_proj", "kernel"),
    ("layers_0", "kda", "b_proj", "kernel"),
    ("layers_0", "kda", "g_b_proj", "bias"),
    ("layers_0", "kda", "o_norm", "scale"),
    ("layers_0", "mlp", "down_proj", "kernel"),
    ("layers_1", "kda", "v_proj", "kernel"),
    ("layers_1", "kda", "k_proj", "kernel"),
    ("layers_2", "kda", "g_a_proj", "kernel"),
    ("layers_1", "moe", "router", "kernel"),
    ("layers_1", "moe", "w_gate"),
    ("layers_1", "moe", "w_down"),
    ("layers_1", "moe", "shared", "up_proj", "kernel"),
    ("layers_3", "mla", "q_proj", "kernel"),
    ("layers_3", "mla", "kv_a_proj", "kernel"),
    ("layers_3", "mla", "kv_a_norm", "scale"),
    ("layers_3", "mla", "kv_b_proj", "kernel"),
    ("layers_3", "moe", "w_up"),
    ("layers_4", "kda", "o_proj", "kernel"),
    ("lm_head", "kernel"),
    ("embed_tokens", "embedding"),
], ids="/".join)
def test_gradients_agree_with_the_references(both_gradients, path):
    (_, grads), (_, expected) = both_gradients
    got, want = leaf(grads["params"], path), leaf(expected["params"], path)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-5 * np.abs(want).max())


def test_no_gradient_reaches_the_selection_bias(both_gradients):
    (_, grads), _ = both_gradients
    for i in range(1, 5):
        assert not leaf(grads["params"], (f"layers_{i}", "moe", "router_bias")).any()


# ------------------------------------------------- the expert layer alone


def expert_layer(held, **over):
    cfg = KimiLinearConfig(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_experts=16, num_experts_per_tok=4, num_shared_experts=1,
        routed_scaling_factor=2.446, experts_held=held, initializer_range=0.5,
        dtype=jnp.float32, param_dtype=jnp.float32, **over,
    )
    return MoELayer(cfg), cfg


def layer_config(cfg: KimiLinearConfig) -> dict:
    """The reference's keys for one expert layer of ``cfg``."""
    lo, hi = cfg.experts_held or (0, cfg.num_experts)
    return {
        "num_experts_published": cfg.num_experts, "num_experts": hi - lo,
        "expert_rank": lo // (hi - lo),
        "num_experts_per_token": cfg.num_experts_per_tok,
        "moe_renormalize": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "num_shared_experts": cfg.num_shared_experts,
    }


@pytest.fixture(scope="module")
def whole_layer():
    """An uncut layer (every expert held), its parameters with a selection
    bias that is not zero, and tokens."""
    layer, cfg = expert_layer(None)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 48, 32)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    bias = np.random.default_rng(2).normal(size=16).astype(np.float32) * 0.3
    return cfg, {**params, "router_bias": jnp.asarray(bias)}, x


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer(whole_layer):
    """Four ranks of four experts each: the routed parts they give, with
    the shared expert (which every rank computes alike) counted once, are the
    uncut reference's expert layer."""
    cfg, params, x = whole_layer
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.moe(params, tokens, layer_config(cfg))
        shared = reference.shared_expert(params, tokens)
    total = 0.0
    for rank in range(4):
        held = (4 * rank, 4 * rank + 4)
        layer, _ = expert_layer(held)
        mine = {**params, **{k: params[k][held[0]:held[1]]
                             for k in ("w_gate", "w_up", "w_down")}}
        out = layer.apply({"params": mine}, x).reshape(-1, 32)
        # the program's share is the reference's, given the same share
        with jax.default_matmul_precision("highest"):
            want = reference.moe(mine, tokens, layer_config(layer.cfg))
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        total = total + (out - shared)
    np.testing.assert_allclose(total + shared, uncut, rtol=1e-4, atol=1e-5)
    # and the uncut layer through the program is the reference's too
    whole = expert_layer(None)[0].apply({"params": params}, x).reshape(-1, 32)
    np.testing.assert_allclose(whole, uncut, rtol=1e-4, atol=1e-5)


def test_a_routing_that_sends_every_pair_here_loses_none(whole_layer):
    """A selection bias that puts the held experts first for every token:
    all T x K pairs arrive here, the static layout holds them, and the
    share is the whole routed result."""
    cfg, params, x = whole_layer
    held = (4, 8)
    bias = np.zeros(16, np.float32)
    bias[held[0]:held[1]] = 10.0
    params = {**params, "router_bias": jnp.asarray(bias)}
    layer, _ = expert_layer(held)
    mine = {**params, **{k: params[k][held[0]:held[1]]
                         for k in ("w_gate", "w_up", "w_down")}}
    out = layer.apply({"params": mine}, x).reshape(-1, 32)
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        gates = reference.router_gates(params, tokens, layer_config(cfg))
        uncut = reference.moe(params, tokens, layer_config(cfg))
    assert (np.asarray(gates[:, held[0]:held[1]]) > 0).all()  # every pair is here
    np.testing.assert_allclose(out, uncut, rtol=1e-4, atol=1e-5)
    # gradients reach every held expert and are finite
    grads = jax.grad(lambda p: (layer.apply({"params": p}, x) ** 2).sum())(mine)
    for name in ("w_gate", "w_up", "w_down"):
        g = np.asarray(grads[name])
        assert np.isfinite(g).all() and (np.abs(g).reshape(4, -1).max(1) > 0).all()


def test_a_rank_that_no_pair_reaches_gives_the_shared_expert_alone(whole_layer):
    cfg, params, x = whole_layer
    held = (12, 16)
    bias = np.zeros(16, np.float32)
    bias[:4] = 10.0  # every token's four choices are experts 0-3
    params = {**params, "router_bias": jnp.asarray(bias)}
    layer, _ = expert_layer(held)
    mine = {**params, **{k: params[k][held[0]:held[1]]
                         for k in ("w_gate", "w_up", "w_down")}}
    out, grads = jax.value_and_grad(
        lambda p: (layer.apply({"params": p}, x) ** 2).sum())(mine)
    with jax.default_matmul_precision("highest"):
        shared = reference.shared_expert(params, x.reshape(-1, 32))
    np.testing.assert_allclose(out, (shared ** 2).sum(), rtol=1e-4)
    for name in ("w_gate", "w_up", "w_down"):
        assert not np.asarray(grads[name]).any(), name


def test_the_bias_moves_the_selection_and_not_the_gates(whole_layer):
    """Gates are the chosen experts' sigmoids renormalised, times 2.446,
    whatever the bias; which experts are chosen follows score + bias."""
    cfg, params, x = whole_layer
    tokens = x.reshape(-1, 32)
    lc = layer_config(cfg)
    gates = np.asarray(reference.router_gates(params, tokens, lc))
    np.testing.assert_allclose(gates.sum(-1), 2.446, rtol=1e-5)
    assert ((gates > 0).sum(-1) == 4).all()
    scores = np.asarray(jax.nn.sigmoid(tokens @ params["router"]["kernel"]))
    unbiased = np.asarray(reference.router_gates(
        {**params, "router_bias": jnp.zeros(16)}, tokens, lc))
    moved = (gates > 0) != (unbiased > 0)
    assert moved.any()  # the bias changed some token's experts
    chosen = gates > 0
    want = np.where(chosen, scores, 0.0)
    want = want / want.sum(-1, keepdims=True) * 2.446
    np.testing.assert_allclose(gates, want, rtol=1e-5, atol=1e-7)
    # and the program routes as the reference does
    out = expert_layer(None)[0].apply({"params": params}, x).reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            out, reference.moe(params, tokens, lc), rtol=1e-4, atol=1e-5)


# ------------------------------- the share at the cost of the pairs here


def whole_bound_ffn(x2, gates, w_gate, w_up, w_down, pair_of_slot, tile_group,
                    tiles_used, slot_of_pair=None):
    """``_held_ffn``'s result the plain way, as the layer computed it before
    its cost followed the pairs that are here: every row move a gather over
    the whole bound, every tile of the layout computed. A pair held
    elsewhere reads the layout's last slot, which is padding: a zero row."""
    from ray_tpu.models.mixtral import _rows_to_slots, _slots_to_rows
    from ray_tpu.ops.gmm import gmm

    (S, K), m_pad = gates.shape, pair_of_slot.shape[0]
    slot_of_pair = jnp.full((S * K + 1,), m_pad - 1, jnp.int32).at[
        pair_of_slot].set(jnp.arange(m_pad, dtype=jnp.int32))[:-1].reshape(S, K)
    lhs = _rows_to_slots(x2, slot_of_pair, pair_of_slot)
    h, u = gmm(lhs, w_gate, tile_group), gmm(lhs, w_up, tile_group)
    eo = gmm(jax.nn.silu(h) * u, w_down, tile_group)
    return _slots_to_rows(eo, gates, slot_of_pair, pair_of_slot)


def routing(case, cfg, params, held):
    """(parameters, tokens) that route as ``case`` says to a rank holding
    experts ``held`` of sixteen, four a token."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, 192, 32))
    bias = np.zeros(16, np.float32)
    if case == "none-here":
        bias[:4] = 10.0
    elif case == "every-pair-here":
        bias[held[0]:held[1]] = 10.0
    elif case == "zipf":
        # A frequent token sends all its copies to the same four experts.
        ids = np.minimum(rng.zipf(1.3, size=(2, 192)), 40) - 1
        x = rng.normal(size=(40, 32))[ids]
    return {**params, "router_bias": jnp.asarray(bias)}, jnp.asarray(x, jnp.float32)


@pytest.mark.parametrize("window", [1, 3])
@pytest.mark.parametrize(
    "case", ["none-here", "even-share", "zipf", "every-pair-here"])
def test_the_share_is_the_whole_bounds_at_the_cost_of_its_pairs(
        whole_layer, monkeypatch, case, window):
    """The held layer, output and every gradient (tokens, gates, the three
    expert matrices, the router), against the same layer with its row moves
    over the whole bound, at four routings and two window sizes; every row
    past ``tiles_used`` holds NaN before its consumer runs, so a reader
    that strays past the used tiles fails."""
    from ray_tpu.models import mixtral
    from ray_tpu.ops import gmm as G

    cfg, params, _ = whole_layer
    held = (4, 8)
    params, x = routing(case, cfg, params, held)
    layer, _ = expert_layer(held)
    mine = {**params, **{k: params[k][held[0]:held[1]]
                         for k in ("w_gate", "w_up", "w_down")}}

    calls = []
    held_ffn, gmm_rows = mixtral._held_ffn, G._gmm_pallas

    def poisoned(lhs, rhs, tile_group, block_m, transpose_rhs=False,
                 tiles_used=None):
        out = gmm_rows(lhs, rhs, tile_group, block_m, transpose_rhs, tiles_used)
        if tiles_used is None:
            return out
        past = jnp.arange(out.shape[0])[:, None] >= tiles_used[0] * block_m
        return jnp.where(past, jnp.nan, out)

    def watched(*args):
        calls.append(args)
        return held_ffn(*args)

    monkeypatch.setattr(mixtral, "_WINDOW", window)
    monkeypatch.setattr(mixtral, "_held_ffn", watched)
    monkeypatch.setattr(G, "_gmm_pallas", poisoned)
    monkeypatch.setattr(
        G, "unwritten", lambda shape, dtype, after: jnp.full(shape, jnp.nan, dtype))
    w = jnp.asarray(np.random.default_rng(6).normal(size=x.shape), jnp.float32)

    def loss(p, x):
        return (layer.apply({"params": p}, x) * w).sum()

    layer.apply({"params": mine}, x)
    args = calls[0]
    out, grads = jax.value_and_grad(loss, (0, 1))(mine, x)
    got, pull = jax.vjp(lambda *a: held_ffn(*a, *args[5:]), *args[:5])
    got = (got, *pull(w.reshape(got.shape)))

    # the plain form of the same layer, and of the same call
    monkeypatch.setattr(mixtral, "_held_ffn", whole_bound_ffn)
    monkeypatch.setattr(G, "_gmm_pallas", gmm_rows)
    want_out, want_grads = jax.value_and_grad(loss, (0, 1))(mine, x)
    want, pull = jax.vjp(lambda *a: whole_bound_ffn(*a, *args[5:]), *args[:5])
    want = (want, *pull(w.reshape(want.shape)))

    pair_of_slot, tile_group, tiles_used = (np.asarray(a) for a in args[5:8])
    pairs, tiles = x.shape[0] * x.shape[1] * 4, int(tiles_used[0])
    here = int((pair_of_slot < pairs).sum())
    assert not (pair_of_slot[tiles * 128:] < pairs).any()
    assert pair_of_slot.shape[0] % (window * 128) == 0
    assert {"none-here": here == 0, "every-pair-here": here == pairs}.get(
        case, 0 < here < pairs)
    assert 4 <= tiles <= -(-here // 128) + 4  # an expert pads by a tile at most

    assert np.isfinite(float(out))
    np.testing.assert_allclose(out, want_out, rtol=1e-5)
    names = ("out", "d_x", "d_gates", "d_w_gate", "d_w_up", "d_w_down")
    for name, a, b in zip(names, got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
    flat = lambda g: jax.tree_util.tree_leaves_with_path(g)  # noqa: E731
    for (path, a), (_, b) in zip(flat(grads), flat(want_grads)):
        name = jax.tree_util.keystr(path)
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
    moved = {jax.tree_util.keystr(p) for p, a in flat(grads[0]) if np.asarray(a).any()}
    assert "['shared']['up_proj']['kernel']" in moved
    for name in ("['router']['kernel']", "['w_gate']", "['w_up']", "['w_down']"):
        assert (name in moved) == (here > 0), name


def test_a_share_is_refused_outside_the_gmm_dispatch():
    layer, _ = expert_layer((0, 4), moe_dispatch="capacity")
    with pytest.raises(ValueError, match="experts_held"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 32)))
