"""Xing4's architecture through the program's models, on the CPU: what needs no
model: the file's assumed initial values, yarn's two names, Sinkhorn, one
hyper-connection, the streams, the expert layer's ranks, the mixer with and
without the q latent (``tests/test_xing4_model.py`` has the model against its
reference and says what the reference is; ``tests/xing4_cases.py`` what the
files share).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.reference import (
    sarvam_mla_decoder as sarvam_reference, xing4_decoder as reference,
)
from ray_tpu.models.hyper_connections import (
    HyperConnection, HyperConnections, collapse_streams, expand_streams,
    sinkhorn, write_streams,
)
from ray_tpu.models.mla import MLAConfig, MLAMixer, yarn_scaling
from ray_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM, xing4_config

from xing4_cases import (  # noqa: F401 - fixtures
    CONFIG, PUBLISHED_YARN, expert_layer, interpret,
)


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
