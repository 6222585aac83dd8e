"""What the test files of Xing4's architecture share (``tests/test_xing4_*.py``):
the interpreter's switch, the configuration file at its rehearsal size as a
model with parameters away from their initial values (``xing4``), and the
fixtures more than one file reads. A plain module: a piece imports what it
reads by name, and each piece that reads a module-scoped fixture makes it once
for itself.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.reference import xing4_decoder as reference
from ray_tpu.models.mixtral import MoELayer
from ray_tpu.models.mla import YarnScaling
from ray_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM


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
def expected_logits(xing4_f32):
    """The unchanged reference's logits of the unchanged parameters."""
    config, _, params, ids = xing4_f32
    return reference.forward(params, ids, config, SEQ)


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


def gathered_rows_give_what_walked_rows_give(rank, routing, monkeypatch):
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
