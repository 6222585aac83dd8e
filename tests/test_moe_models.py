"""MoE (expert parallelism) + GPT model family tests on the CPU mesh.

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
    p = params["params"]
    router_w = np.asarray(p["router"]["kernel"], np.float64)
    wg = np.asarray(p["w_gate"], np.float64)
    wu = np.asarray(p["w_up"], np.float64)
    wd = np.asarray(p["w_down"], np.float64)
    xs = np.asarray(x, np.float64)
    B, T, D = xs.shape
    logits = xs @ router_w
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros_like(xs)
    for b in range(B):
        for t in range(T):
            topk = np.argsort(-probs[b, t])[: cfg.num_experts_per_tok]
            gates = probs[b, t, topk]
            gates = gates / gates.sum()
            acc = np.zeros(D)
            for gate, e in zip(gates, topk):
                h = xs[b, t] @ wg[e]
                u = xs[b, t] @ wu[e]
                silu = h / (1 + np.exp(-h))
                acc += gate * ((silu * u) @ wd[e])
            want[b, t] = acc
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-3, rtol=2e-3)


def test_moe_capacity_drops_tokens(tiny_moe):
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


def test_gpt_forward_and_grads():
    import dataclasses

    from ray_tpu.models.gpt import CONFIGS, GPTForCausalLM
    from ray_tpu.models.llama import causal_lm_loss

    cfg = dataclasses.replace(CONFIGS["gpt2-tiny"], dtype=jnp.float32,
                              remat=False)
    model = GPTForCausalLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 32)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    logits = model.apply(params, ids)
    assert logits.shape == (2, 32, cfg.vocab_size)
    loss, grads = jax.value_and_grad(
        lambda p: causal_lm_loss(model.apply(p, ids), jnp.roll(ids, -1, 1))
    )(params)
    assert np.isfinite(float(loss))
    gnorm = sum(
        float(jnp.abs(g).sum()) for g in jax.tree_util.tree_leaves(grads)
    )
    assert gnorm > 0


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


def test_moe_dispatch_auto_resolution(tiny_moe, monkeypatch, tmp_path):
    """"auto" resolves via a measured probe, keeps the verdict in the
    process (nothing is written under the home directory), forces
    capacity under an expert-sharded mesh, and lets a backend's failure
    through."""
    import dataclasses

    from ray_tpu.models import mixtral as mx

    cfg, _, _, _ = tiny_moe
    auto_cfg = dataclasses.replace(cfg, moe_dispatch="auto")

    # Env override wins without probing.
    monkeypatch.setenv("RAY_TPU_MOE_DISPATCH", "ragged")
    mx._RESOLVED.clear()
    assert mx.resolve_moe_dispatch(auto_cfg) == "ragged"
    monkeypatch.delenv("RAY_TPU_MOE_DISPATCH")

    # Expert-sharded mesh forces the EP-capable capacity layout.
    from ray_tpu.parallel import MeshSpec

    mesh = MeshSpec(data=2, expert=4).build()
    mx._RESOLVED.clear()
    assert mx.resolve_moe_dispatch(auto_cfg, mesh=mesh) == "capacity"

    # A backend whose kernel cannot compile here (the gmm kernel on a CPU
    # backend without interpret mode) fails the resolution; it is not
    # dropped in favour of the other one.
    monkeypatch.setenv("HOME", str(tmp_path))
    mx._RESOLVED.clear()
    with pytest.raises(Exception):
        mx.resolve_moe_dispatch(auto_cfg, tokens=64, steps=1)
    assert not mx._RESOLVED

    # Measured probe on this backend: must return a working backend
    # (gmm needs interpret mode to be probe-able on CPU).
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    winner = mx.resolve_moe_dispatch(auto_cfg, tokens=64, steps=1)
    assert winner in ("capacity", "gmm")
    assert list(tmp_path.iterdir()) == []
    # Kept for the process: resolving again does not probe.
    monkeypatch.setattr(mx, "MoELayer", None)
    assert mx.resolve_moe_dispatch(auto_cfg) == winner
