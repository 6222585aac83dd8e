"""MoE (expert parallelism) model tests on the CPU mesh.

Runs under the conftest's 8-virtual-device CPU backend.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def tiny_moe():
    from ray_tpu.models.mixtral import CONFIGS, MixtralForCausalLM

    cfg = CONFIGS["mixtral-tiny"]
    import dataclasses

    cfg = dataclasses.replace(cfg, dtype=jnp.float32, remat=False)
    model = MixtralForCausalLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    return cfg, model, ids, params


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


def _routing(case, rng):
    """(experts, expert of each (token, k) pair [S, K], dtype) of a case."""
    if case == "top1":
        return 4, rng.randint(0, 4, (24, 1)), jnp.float32
    if case == "one_expert_takes_every_token":
        return 4, np.full((24, 2), 2), jnp.float32
    # 8 of 64, the experts from 40 on chosen by no token; the same in
    # bfloat16, held to a float32 oracle.
    picks = np.stack([rng.permutation(40)[:8] for _ in range(32)])
    return 64, picks, jnp.bfloat16 if case == "bfloat16" else jnp.float32


@pytest.mark.parametrize("case", [
    "top1", "top8_of_64_with_experts_empty", "one_expert_takes_every_token",
    "bfloat16",
])
def test_gmm_row_moves_are_the_scatters_they_replace(case):
    """`_rows_to_slots` and `_slots_to_rows`, forward and every gradient,
    against the plain gather and `.at[].add` over the sorted pairs that
    the "gmm" branch moved its rows by (the "ragged" branch still does):
    the index maps there are built as that branch built them."""
    from ray_tpu.models.mixtral import (
        _pair_slots, _rows_to_slots, _slots_to_rows,
    )
    from ray_tpu.ops.gmm import aligned_group_layout

    rng = np.random.RandomState(7)
    E, picks, dtype = _routing(case, rng)
    (S, K), N, D = picks.shape, picks.size, 16
    order, dst, _, m_pad = aligned_group_layout(
        jnp.asarray(picks.reshape(N), jnp.int32), E, block_m=8
    )
    slot_of_pair, pair_of_slot = _pair_slots(order, dst, m_pad, K)

    tok_sorted = (jnp.arange(N, dtype=jnp.int32) // K)[order]
    inv = jnp.full((m_pad,), N, jnp.int32).at[dst].set(jnp.arange(N))
    src_tok = jnp.concatenate([tok_sorted, jnp.full((1,), S, jnp.int32)])[inv]
    padding = np.asarray(inv) == N
    assert padding.sum() == m_pad - N > 0

    def dispatch_oracle(x2):
        return jnp.concatenate([x2, jnp.zeros((1, D), x2.dtype)])[src_tok]

    def combine_oracle(eo, gates):
        pair_out = eo[dst] * gates.reshape(N)[order][:, None]
        return jnp.zeros((S, D), eo.dtype).at[tok_sorted].add(pair_out)

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape), dtype)

    # Padding slots hold noise, in the expert outputs and the cotangents:
    # neither pass may read them.
    x2, eo, d_lhs, d_out = draw(S, D), draw(m_pad, D), draw(m_pad, D), draw(S, D)
    gates = jnp.asarray(rng.rand(S, K), dtype)

    def f32(*arrays):
        return [a.astype(jnp.float32) for a in arrays]

    lhs, pull_x = jax.vjp(lambda x: _rows_to_slots(x, slot_of_pair, pair_of_slot), x2)
    out, pull_eo = jax.vjp(
        lambda e, g: _slots_to_rows(e, g, slot_of_pair, pair_of_slot), eo, gates
    )
    got = [lhs, *pull_x(d_lhs), out, *pull_eo(d_out)]
    assert [a.dtype for a in got] == [dtype] * 5
    want_lhs, pull_x = jax.vjp(dispatch_oracle, *f32(x2))
    want_out, pull_eo = jax.vjp(combine_oracle, *f32(eo, gates))
    want = [want_lhs, *pull_x(*f32(d_lhs)), want_out, *pull_eo(*f32(d_out))]

    # A sum of K or D products rounds once to the dtype: half a unit in
    # bfloat16's eighth bit, and float32's own noise.
    rtol = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-6
    for name, a, b in zip(
        ("lhs", "d_x", "out", "d_eo", "d_gates"), f32(*got), want
    ):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=1e-6, err_msg=name)
    d_eo = np.asarray(got[3].astype(jnp.float32))
    assert (d_eo[padding] == 0).all() and (d_eo[~padding] != 0).any()
    assert (np.asarray(lhs.astype(jnp.float32))[padding] == 0).all()


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


# ------------------------------------------- a held share's two roads
#
# One expert layer, forward and backward, at a router of 16 experts, top-4,
# over 256 tokens of 32: the whole layer, and a rank's quarter by either
# road (``held_rows``).


def _layer_step(**over):
    """(the layer's loss and gradients as a function, its arguments' shapes)."""
    from ray_tpu.models.mixtral import MixtralConfig, MoELayer

    layer = MoELayer(MixtralConfig(
        hidden_size=32, intermediate_size=64, num_experts=16,
        num_experts_per_tok=4, num_shared_experts=1, router_score="sigmoid",
        moe_dispatch="gmm", dtype=jnp.bfloat16, **over,
    ))
    x = jax.ShapeDtypeStruct((1, 256, 32), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)

    def step(params, x):
        return layer.apply(params, x).astype(jnp.float32).sum()

    return jax.value_and_grad(step, (0, 1)), (params, x)


# sha1 of the lowered text (without the counters JAX gives its private
# functions), read by this code at the parent of the PR that gave the
# "gather" road ``_held_ffn``'s slot-side loops (commit 6ab58fe): a layer that
# walks (Kimi-Linear's and sarvam's) and a layer that holds every expert
# (OLMoE's) lower to the text they lowered to, so their steps cannot have
# moved with it.
LAYER_TEXTS_BEFORE = {
    "walk": (dict(experts_held=(4, 8), held_rows="walk"), "c3f6f0628800c651"),
    "whole": ({}, "119ae5fb0bbf48d7"),
}


@pytest.mark.parametrize("name", sorted(LAYER_TEXTS_BEFORE))
def test_the_layers_off_the_gather_road_lower_to_what_they_did(name, monkeypatch):
    import hashlib
    import re

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    over, before = LAYER_TEXTS_BEFORE[name]
    step, shapes = _layer_step(**over)
    text = jax.jit(step).lower(*shapes).as_text()
    text = re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
    assert hashlib.sha1(text.encode()).hexdigest()[:16] == before


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(inner)


@pytest.mark.parametrize("held_rows, row_adds", [("gather", 0), ("walk", 2)])
def test_no_row_is_scatter_added_on_the_gather_road(held_rows, row_adds, monkeypatch):
    """A scatter-add into [., 32] arrays (rows of tokens or of slots): the
    walk's two, forward into the result and backward into x's gradient, and
    none where rows are gathered; there the traced step holds two calls of
    the kernel over tokens (``ops.gmm.pairs_summed``), forward and backward,
    and no gather of every pair's row, [256, 4, 32], on either road."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    step, shapes = _layer_step(experts_held=(4, 8), held_rows=held_rows)
    eqns = list(_equations(jax.make_jaxpr(step)(*shapes).jaxpr))
    adds = [e for e in eqns if e.primitive.name == "scatter-add"
            and e.outvars[0].aval.shape[-1:] == (32,)]
    assert len(adds) == row_adds
    whole = [e for e in eqns if e.primitive.name == "gather"
             and e.outvars[0].aval.shape == (256, 4, 32)]
    assert not whole
    over_tokens = [e for e in eqns if e.primitive.name == "pallas_call"
                   and e.params["jaxpr"].debug_info.func_name == "_pairs_summed_kernel"]
    assert len(over_tokens) == (2 if held_rows == "gather" else 0)
    for call in over_tokens:
        assert call.outvars[0].aval.shape == (256, 32)


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


# ------------------------------------------------- the capacity branch's FFN
#
# expert_ffn against the plain einsum (_swiglu) on the same buffers. The
# tiny configuration's widths with Mixtral's eight experts, top-2, at
# capacity factor 4.0 and 2,048 tokens a row: C = 2,048 slots, four tiles of
# 512, and a buffer that one expert's pairs can fill. The tiled FFN's weight
# gradients are ops/gmm.py's kernel, interpreted here: a block of them that
# no trip visits reads NaN, and one written twice holds its last visit alone.

FFN_ROWS, FFN_TOKENS, FFN_EXPERTS, FFN_TOP_K = 2, 2048, 8, 2
# counts[row][expert]: pairs in the buffer of an (expert, row); the slots
# that hold them are the prefix, as arrival order fills them. A row's counts
# come to its 4,096 pairs and none is over its 2,048 tokens, as a router's do.
ROUTINGS = {
    "expert_with_no_pair": [[0, 1400, 600, 80, 1000, 1016, 0, 0],
                            [5, 0, 512, 1, 2048, 1500, 30, 0]],
    "prefix_ends_inside_a_tile": [[700, 3, 0, 130, 513, 1100, 1550, 100],
                                  [600, 513, 100, 1, 1027, 1300, 255, 300]],
    "prefix_ends_at_a_tiles_edge": [[1024, 512, 512, 0, 1536, 512, 0, 0],
                                    [0, 1024, 0, 1024, 512, 512, 1024, 0]],
    "full_buffer": [[2048, 2048, 0, 0, 0, 0, 0, 0],
                    [1, 2048, 600, 1447, 0, 0, 0, 0]],
    # Every pair of a row to one half of the experts, in prefixes that end
    # just inside a tile: the 11 tiles of 16 that _ffn_trips allows a chip
    # of seq=2 x expert=2.
    "most_tiles_a_chip_can_reach": [[1152, 1152, 1152, 640, 0, 0, 0, 0],
                                    [0, 0, 0, 0, 640, 1152, 1152, 1152]],
    # One expert of a chip's four holds all of a row's pairs that come to
    # the chip: the three others' only trips are empty tiles.
    "every_pair_to_one_expert_a_chip": [[2048, 0, 0, 0, 0, 2048, 0, 0],
                                        [0, 0, 0, 2048, 0, 0, 2048, 0]],
    # Experts that reach tiles between experts that reach none: the empty
    # tiles that fill the trips lie before, between and after the reached
    # ones, and each expert's trips still have to be consecutive.
    "reached_and_empty_interleave": [[1, 0, 2047, 0, 2048, 0, 0, 0],
                                     [0, 600, 0, 1448, 0, 2048, 0, 0]],
}
FFN_MESHES = {
    "single_device": None,
    "expert2": dict(expert=2),
    # Two experts a chip can be sent every pair of a row: nothing to skip,
    # and the FFN is the plain einsum on the mesh.
    "expert4": dict(expert=4),
    "seq2_expert2": dict(seq=2, expert=2),
    "data2_expert2_tensor2": dict(data=2, expert=2, tensor=2),
}


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _mesh_context(axes):
    import contextlib

    from ray_tpu.parallel import MeshSpec

    if axes is None:
        return contextlib.nullcontext()
    return jax.set_mesh(MeshSpec(**axes).build())


def _ffn_case(counts, seed=0):
    """Buffers [E, B, C, D] whose occupied slots are the counts' prefixes
    (empty slots are zero rows, as the dispatch leaves them), their slots'
    gates [E, B, C] (zero in an empty slot), a cotangent that is zero where
    no pair is, as combine's is, and the weights."""
    from ray_tpu.models.mixtral import CONFIGS, _ffn_trips

    cfg = CONFIGS["mixtral-tiny"]
    E, K = FFN_EXPERTS, FFN_TOP_K
    D, F = cfg.hidden_size, cfg.intermediate_size
    C = int(4.0 * FFN_TOKENS * K / E)
    assert C == 4 * 512 and _ffn_trips(E, FFN_ROWS, C, FFN_TOKENS * K) == 30
    counts = np.asarray(counts, np.float32)  # [B, E], as expert_mask.sum(1)
    assert (counts.sum(1) == FFN_TOKENS * K).all() and counts.max() <= FFN_TOKENS
    rng = np.random.RandomState(seed)
    occupied = np.arange(C)[None, None] < counts.T[:, :, None]  # [E, B, C]
    x = rng.randn(E, FFN_ROWS, C, D).astype(np.float32) * occupied[..., None]
    g = rng.randn(E, FFN_ROWS, C, D).astype(np.float32) * occupied[..., None]
    gates = rng.rand(E, FFN_ROWS, C).astype(np.float32) * occupied
    weights = [
        (rng.randn(*shape) * 0.1).astype(np.float32)
        for shape in ((E, D, F), (E, D, F), (E, F, D))
    ]
    return x, gates, weights, g, jnp.asarray(counts), FFN_TOKENS * K


@pytest.mark.parametrize("mesh", FFN_MESHES)
@pytest.mark.parametrize("routing", ROUTINGS)
def test_expert_ffn_matches_the_plain_einsum(routing, mesh, interpret):
    """Values and all five gradients (x, the slots' gates, w_gate, w_up,
    w_down) against the plain einsum's rows times their gates: skipping the
    tiles past each prefix changes nothing, the gates' gradient taken
    on the other side of w_down is the one JAX takes through the rows, and
    the weights' gradients added up an expert at a time by the grouped
    matmul after the loop are the ones added up over all slots, on
    one device, on an expert-only mesh, with the rows shared out over seq,
    and with the experts' width split over a tensor axis. An expert that
    no pair reached has gradients of exact zeros."""
    from ray_tpu.models.mixtral import _swiglu, expert_ffn

    x, gates, weights, g, counts, pairs = _ffn_case(ROUTINGS[routing])

    def value_and_grads(ffn):
        def loss(x, gates, *w):
            return (ffn(x, gates, *w) * g).sum()

        return jax.jit(lambda x, gates, *w: (
            ffn(x, gates, *w),
            jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, gates, *w),
        ))(x, gates, *weights)

    with _mesh_context(FFN_MESHES[mesh]):
        want, want_grads = value_and_grads(
            lambda x, gates, *w: _swiglu(x, *w) * gates[..., None]
        )
        got, got_grads = value_and_grads(
            lambda x, gates, *w: expert_ffn(x, gates, *w, counts, pairs)
        )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(want_grads[1])).max() > 0
    for name, a, b in zip(
        ("x", "gates", "w_gate", "w_up", "w_down"), got_grads, want_grads
    ):
        scale = float(np.abs(b).max()) or 1.0
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-5, err_msg=name
        )
    unreached = np.asarray(counts).sum(0) == 0
    for name, a in zip(("w_gate", "w_up", "w_down"), got_grads[2:]):
        assert not np.asarray(a)[unreached].any(), name


@pytest.mark.parametrize("routing", ROUTINGS)
def test_expert_ffn_computes_the_tiles_its_prefixes_reach(routing, interpret):
    """Which slots the FFN computes: rows of ones in every slot, against the
    invariant, come back non-zero from every 512-slot tile that the prefix
    of its own (expert, row) reaches and from as many others as make up the
    trips: the same number of tiles whatever the routing."""
    from ray_tpu.models.mixtral import _ffn_trips, expert_ffn

    x, gates, weights, _, counts, pairs = _ffn_case(ROUTINGS[routing])
    out = jax.jit(expert_ffn, static_argnums=6)(
        np.ones_like(x), np.ones_like(gates), *weights, counts, pairs
    )
    E, B, C, _ = x.shape
    computed = np.abs(np.asarray(out)).reshape(E, B, C // 512, 512, -1).any(
        axis=(3, 4)
    )
    reached = np.arange(C // 512) < -(-np.asarray(counts, int).T // 512)[..., None]
    assert computed[reached].all()
    assert reached.sum() <= computed.sum() == _ffn_trips(E, B, C, pairs) == 30


@pytest.mark.parametrize("shape, trips", [
    # The MoE cell's chip: four of eight experts, one row of 4,096 tokens,
    # top-2, factor 4.0. Its 8,192 pairs fill 16 tiles and can end in three
    # more (2,176 + 2,176 + 2,176 + 1,664: 5 + 5 + 5 + 4).
    ((4, 1, 4096, 8192), 19),
    ((8, 2, 4096, 8192), 2 * 23),  # the same layer on one device
    ((2, 2, 2048, 4096), 0),  # two experts can be sent every pair: all tiles
    ((8, 1, 1280, 8192), 0),  # factor 1.25: C is not whole tiles
    ((8, 1, 1536, 8192), 0),  # factor 1.5: 23 of 24 tiles can be reached
    ((4, 1, 512, 8192), 0),  # one tile a buffer
    # Pairs that are not whole tiles: 513 + 1 + 1 of them reach all four
    # trips of the bound and leave the fourth expert without one.
    ((4, 1, 1024, 515), 0),
])
def test_ffn_trips_are_the_tiles_that_can_hold_a_pair(shape, trips):
    from ray_tpu.models.mixtral import _ffn_trips

    assert _ffn_trips(*shape) == trips


def _routings(experts, rows, C, pairs, rng, n):
    """``n`` counts [experts, rows] of a device's share of each row's pairs:
    some of the experts, chosen anew each time, hold prefixes that end just
    inside a tile; the worst for the tiles reached."""
    for _ in range(n):
        counts = np.zeros((experts, rows), int)
        for row in range(rows):
            some = rng.permutation(experts)[: rng.randint(0, experts + 1)]
            left = rng.randint(0, pairs + 1)
            for e in some:
                counts[e, row] = took = min(
                    left, C, rng.randint(0, C // 512 + 1) * 512 + 1
                )
                left -= took
        yield counts


@pytest.mark.parametrize("shape", [
    (4, 1, 4096, 8192), (8, 2, 4096, 8192), (2, 2, 2048, 4096),
    (8, 1, 1280, 8192), (8, 1, 1536, 8192), (4, 1, 512, 8192),
])
def test_worklist_keeps_an_experts_trips_together_and_leaves_no_expert_out(shape):
    """What the weights' gradients need of the trips, on the shapes the
    bound is tested on: every reached tile among them and none twice, in
    (expert, row, slot) order so that an expert's trips are consecutive,
    and at least one trip in every expert, the ones no pair reaches too.
    Where the FFN is the plain einsum the trips asked for are all tiles."""
    from ray_tpu.models.mixtral import _ffn_trips, _worklist

    experts, rows, C, pairs = shape
    per = -(-C // 512)
    trips = _ffn_trips(*shape) or experts * rows * per
    worklist = jax.jit(_worklist, static_argnums=(1, 2))
    rng = np.random.RandomState(experts * rows + C)
    for counts in _routings(experts, rows, C, pairs, rng, 40):
        tiles = -(-counts // 512)
        e, b, slot = (np.asarray(i) for i in worklist(jnp.asarray(tiles), per, trips))
        flat = (e * rows + b) * per + slot // 512
        assert len(flat) == trips and (np.diff(flat) > 0).all(), (counts, flat)
        assert set(e) == set(range(experts)), (counts, e)
        reached = {
            (x * rows + y) * per + z
            for x in range(experts) for y in range(rows)
            for z in range(tiles[x, y])
        }
        assert reached <= set(flat), (counts, flat)


def test_ffn_trips_cover_the_worst_routing():
    """No routing reaches more tiles than the trips: over random splits of
    a row's pairs among a chip's experts, with prefixes made to end just
    inside a tile, the tiles reached stay within the bound, and the worst
    found meets it."""
    from ray_tpu.models.mixtral import _ffn_trips

    experts, C, pairs = 4, 4096, 8192
    bound = _ffn_trips(experts, 1, C, pairs)
    rng = np.random.RandomState(0)
    most = 0
    for _ in range(2000):
        cuts = np.sort(rng.randint(0, pairs // 128 + 1, experts - 1)) * 128
        counts = np.minimum(np.diff([0, *cuts, pairs]), C)
        most = max(most, int((-(-counts // 512)).sum()))
    assert most == bound == 19


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


LAYER_MESHES = {"expert2": dict(expert=2), "seq2_expert2": dict(seq=2, expert=2)}


@pytest.fixture(scope="module")
def compiled_layers(tiny_moe):
    """mesh name -> (the compiled text of the layer's forward and backward
    at factor 4.0, its parameters on that mesh, their gradients, the
    compiled text of the same under a remat that saves nothing)."""
    import dataclasses

    from ray_tpu.models.mixtral import MoELayer
    from ray_tpu.parallel import MeshSpec, logical_sharding, shard_params

    cfg, _, _, _ = tiny_moe
    cfg = dataclasses.replace(
        cfg, moe_dispatch="capacity", capacity_factor=4.0
    )
    layer = MoELayer(cfg)
    x = jnp.asarray(
        np.random.RandomState(7).randn(2, FFN_TOKENS, cfg.hidden_size),
        jnp.float32,
    )
    host_params = layer.init(jax.random.PRNGKey(7), x[:, :8])
    out = {}
    for name, axes in LAYER_MESHES.items():
        mesh = MeshSpec(**axes).build()
        with pytest.MonkeyPatch.context() as patch, jax.set_mesh(mesh):
            patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
            params = shard_params(host_params, mesh)
            xs = jax.device_put(
                x, logical_sharding(mesh, ("batch", "seq", "embed"))
            )
            step = jax.jit(
                jax.grad(lambda p, x: (layer.apply(p, x) ** 2).sum())
            )
            text = step.lower(params, xs).compile().as_text()
            replaying = jax.jit(jax.grad(lambda p, x: (jax.checkpoint(
                layer.apply, policy=jax.checkpoint_policies.nothing_saveable
            )(p, x) ** 2).sum()))
            out[name] = (
                text, params, step(params, xs),
                replaying.lower(params, xs).compile().as_text(),
            )
    return out


def _ffn_loops(text):
    """The expert FFN's loops over its tiles: those that carry a capacity
    buffer [e, b, C, D]. (The grouped matmuls of the weights' gradients,
    interpreted, are loops over their grids and carry none.)"""
    import re

    return [
        line for line in text.split("\n")
        if " while(" in line and "/experts/" in line
        and re.search(r"\[\d+,\d+,\d+,\d+\]", line)
    ]


@pytest.mark.parametrize("mesh", LAYER_MESHES)
def test_expert_ffn_is_not_replicated_over_seq(compiled_layers, mesh):
    """Each slot is computed by one chip: the buffers that a device's
    forward and backward loops walk hold the layer's E x B x C slots over
    the number of devices, with a seq axis as without one. Replicated over
    seq, as the token layout alone leaves them, they would hold twice that
    on seq x expert."""
    import re

    from ray_tpu.models.mixtral import CONFIGS

    cfg = CONFIGS["mixtral-tiny"]
    C = int(4.0 * FFN_TOKENS * cfg.num_experts_per_tok / cfg.num_experts)
    slots = cfg.num_experts * FFN_ROWS * C
    devices = int(np.prod(list(LAYER_MESHES[mesh].values())))
    loops = _ffn_loops(compiled_layers[mesh][0])
    assert len(loops) == 2, loops  # forward and backward
    for line in loops:
        buffers = set(re.findall(
            rf"f32\[(\d+),(\d+),{C},{cfg.hidden_size}\]", line
        ))
        assert len(buffers) == 1, line
        (e, b), = buffers
        assert int(e) * int(b) * C == slots // devices, (e, b, line)


@pytest.mark.parametrize("mesh", LAYER_MESHES)
def test_replay_does_not_run_the_ffn_loop_again(compiled_layers, mesh):
    """A layer that saves nothing replays its forward in the backward, and
    the replay holds no FFN loop: the expert FFN's backward computes a
    tile's activations itself and takes the gates' gradient from them, and
    combine is linear in the weighted rows, so nothing reads what the
    forward loop wrote and the compiled step has the two loops it has
    without remat. A gate applied in combine makes them three: its gradient
    is the cotangent times the unweighted rows, which only the whole
    forward loop can give."""
    loops = _ffn_loops(compiled_layers[mesh][3])
    assert len(loops) == 2, loops  # forward and backward; no replay


@pytest.mark.parametrize("mesh", LAYER_MESHES)
def test_layer_gradients_lie_as_its_parameters(compiled_layers, mesh):
    """The buffers' split of the expert axis stays inside the FFN: a step
    that donates its parameters gets back arrays laid as it passed them."""
    _, params, grads, _ = compiled_layers[mesh]
    for g, p in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(params)):
        assert g.sharding.is_equivalent_to(p.sharding, g.ndim), (
            g.sharding, p.sharding
        )


def test_moe_train_step_on_seq_and_expert_mesh_keeps_its_layout(interpret):
    """Two donating train steps of the whole model at factor 4.0 on
    seq=2 x expert=2, where the tiled FFN runs: the compiled step returns
    parameters and optimizer state laid as it takes them (else the second
    call is refused), and the loss falls."""
    import dataclasses

    import optax

    from ray_tpu.models.mixtral import CONFIGS, MixtralForCausalLM, moe_lm_loss
    from ray_tpu.parallel import MeshSpec, logical_sharding, shard_params
    from ray_tpu.train import make_train_step

    cfg = dataclasses.replace(
        CONFIGS["mixtral-tiny"], max_seq_len=FFN_TOKENS,
        moe_dispatch="capacity", capacity_factor=4.0,
        remat=True, remat_policy="nothing",
    )
    mesh = MeshSpec(seq=2, expert=2).build()
    model = MixtralForCausalLM(cfg, mesh=mesh)
    ids = jnp.asarray(
        np.random.RandomState(8).randint(0, cfg.vocab_size, (FFN_ROWS, FFN_TOKENS)),
        jnp.int32,
    )
    params = jax.jit(MixtralForCausalLM(cfg).init)(
        jax.random.PRNGKey(8), ids[:1, :8]
    )
    tx = optax.adamw(1e-3)
    with jax.set_mesh(mesh):
        params = shard_params(params, mesh)
        opt_state = tx.init(params)
        step = make_train_step(
            lambda p, ids, targets: moe_lm_loss(model, p, ids, targets), tx
        )
        batch = jax.device_put(
            (ids, jnp.roll(ids, -1, 1)), logical_sharding(mesh, ("batch", "seq"))
        )
        compiled = step.lower(params, opt_state, *batch).compile()
        assert "/moe/experts/shard_map/while" in compiled.as_text()
        leaves = jax.tree_util.tree_leaves((params, opt_state))
        taken = jax.tree_util.tree_leaves(compiled.input_shardings[0][:2])
        returned = jax.tree_util.tree_leaves(compiled.output_shardings[:2])
        assert len(taken) == len(returned) == len(leaves)
        for leaf, a, b in zip(leaves, taken, returned):
            assert a.is_equivalent_to(b, leaf.ndim), (a, b)
        params, opt_state, first = compiled(params, opt_state, *batch)
        params, opt_state, second = compiled(params, opt_state, *batch)
    assert np.isfinite(float(first)) and float(second) < float(first)
