"""Llama model: shapes, loss, sharded training step on the CPU mesh.

The chunked head's own derivative rule is in
``tests/test_llama_chunked_head.py`` beside this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import CONFIGS, LlamaForCausalLM
from ray_tpu.models.llama import causal_lm_loss
from ray_tpu.parallel import MeshSpec, shard_params


CFG = CONFIGS["llama-tiny"]


@pytest.fixture(scope="module")
def tiny_params():
    model = LlamaForCausalLM(CFG)
    ids = jnp.zeros((2, 32), jnp.int32)
    return model.init(jax.random.PRNGKey(0), ids)


def test_forward_shape(tiny_params):
    model = LlamaForCausalLM(CFG)
    ids = jnp.ones((2, 32), jnp.int32)
    logits = model.apply(tiny_params, ids)
    assert logits.shape == (2, 32, CFG.vocab_size)
    assert logits.dtype == jnp.float32


def test_causal_lm_loss_decreases(tiny_params):
    model = LlamaForCausalLM(CFG)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, CFG.vocab_size, (4, 32)), jnp.int32)
    targets = jnp.roll(ids, -1, axis=1)
    tx = optax.adam(1e-3)
    params = tiny_params
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            return causal_lm_loss(model.apply(p, ids), targets)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_causality(tiny_params):
    """Changing a future token must not affect earlier logits."""
    model = LlamaForCausalLM(CFG)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, CFG.vocab_size, (1, 16)), jnp.int32)
    logits1 = model.apply(tiny_params, ids)
    ids2 = ids.at[0, 10].set((ids[0, 10] + 1) % CFG.vocab_size)
    logits2 = model.apply(tiny_params, ids2)
    np.testing.assert_allclose(
        np.asarray(logits1[0, :10]), np.asarray(logits2[0, :10]), atol=1e-4
    )


def test_num_params_formula(tiny_params):
    counted = sum(x.size for x in jax.tree_util.tree_leaves(tiny_params))
    assert counted == CFG.num_params()


def test_sharded_train_step_dp_tp(tiny_params):
    """Full train step jitted over a 2x2x2 (data x tensor x seq... ) mesh."""
    mesh = MeshSpec(data=2, fsdp=2, tensor=2).build()
    model = LlamaForCausalLM(CFG, mesh=mesh)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, CFG.vocab_size, (4, 32)), jnp.int32)
    targets = jnp.roll(ids, -1, axis=1)

    with jax.set_mesh(mesh):
        params = shard_params(tiny_params, mesh)

        @jax.jit
        def step(p):
            def loss_fn(p_):
                return causal_lm_loss(model.apply(p_, ids), targets)

            loss, grads = jax.value_and_grad(loss_fn)(p)
            return loss, grads

        loss, grads = step(params)
    assert np.isfinite(float(loss))
    # Grad tree mirrors param tree.
    assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(
        params
    )


def test_seq_parallel_matches_single_device():
    """Ring-attention model output == plain model output (f32 compute so
    the only difference is the blockwise softmax merge, ~1e-5)."""
    from dataclasses import replace

    cfg32 = replace(CFG, dtype=jnp.float32)
    mesh = MeshSpec(seq=4).build()
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg32.vocab_size, (2, 64)), jnp.int32)
    params = LlamaForCausalLM(cfg32).init(jax.random.PRNGKey(0), ids)
    plain = LlamaForCausalLM(cfg32).apply(params, ids)
    with jax.set_mesh(mesh):
        ringed = LlamaForCausalLM(cfg32, mesh=mesh).apply(params, ids)
    np.testing.assert_allclose(
        np.asarray(plain), np.asarray(ringed), atol=2e-4, rtol=1e-4
    )


def test_a_model_given_a_mesh_is_applied_under_it(tiny_params):
    """The layers read the ambient mesh: a model told of a mesh and applied
    under none, or under another, would gather the sequence without a word."""
    ids = jnp.zeros((2, 32), jnp.int32)
    model = LlamaForCausalLM(CFG, mesh=MeshSpec(seq=4).build())
    with pytest.raises(ValueError, match="jax.set_mesh"):
        model.apply(tiny_params, ids)
    with jax.set_mesh(MeshSpec(seq=2).build()):
        with pytest.raises(ValueError, match="jax.set_mesh"):
            model.apply(tiny_params, ids)


@pytest.mark.parametrize(
    "axes, table_gathers",
    [(dict(fsdp=2, tensor=2), 0), (dict(seq=4), 1)],
    ids=["table_split", "table_whole"],
)
def test_embedding_lookup_follows_the_tables_layout(axes, table_gathers):
    """The lookup is chosen from what the mesh does to the table: a
    one-hot contraction where `tensor` or `fsdp` splits it (no gather of
    table rows in the compiled forward), a gather where it is whole.
    Either way the logits are the single-device ones."""
    import re
    from dataclasses import replace

    cfg32 = replace(CFG, dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg32.vocab_size, (2, 64)), jnp.int32
    )
    params = LlamaForCausalLM(cfg32).init(jax.random.PRNGKey(0), ids)
    plain = LlamaForCausalLM(cfg32).apply(params, ids)
    mesh = MeshSpec(**axes).build()
    model = LlamaForCausalLM(cfg32, mesh=mesh)
    with jax.set_mesh(mesh):
        sharded = shard_params(params, mesh)
        compiled = jax.jit(model.apply).lower(sharded, ids).compile()
        logits = compiled(sharded, ids)
    rows = re.findall(
        rf"gather\(.*slice_sizes={{1,{cfg32.hidden_size}}}.*/embed_tokens/",
        compiled.as_text(),
    )
    assert len(rows) == table_gathers, rows
    np.testing.assert_allclose(
        np.asarray(plain), np.asarray(logits), atol=2e-4, rtol=1e-4
    )


def _equations(jaxpr, inside=()):
    """(primitive name, names of the enclosing equations, equation) down a
    jaxpr and every jaxpr its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside, eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, inside + (eqn.primitive.name,))


def test_the_chunked_head_multiplies_three_times_a_chunk_and_replays_nothing():
    """The gradient's jaxpr: one scan, whose body holds the head's three
    matmuls (the logits and the two products of their cotangent), and no
    checkpoint anywhere, so nothing lowers to a rematted_computation."""
    from ray_tpu.models.llama import chunked_head_loss

    hidden = jnp.zeros((2, 32, 16), jnp.float32)
    head = jnp.zeros((256, 16), jnp.float32)
    targets = jnp.zeros((2, 32), jnp.int32)
    grad = jax.grad(lambda h, w: chunked_head_loss(h, w, targets, None, 8), (0, 1))
    found = list(_equations(jax.make_jaxpr(grad)(hidden, head).jaxpr))
    names = [name for name, _, _ in found]
    assert names.count("scan") == 1
    assert not {"checkpoint", "remat", "remat2"} & set(names)
    matmuls = [(inside, eqn) for name, inside, eqn in found if name == "dot_general"]
    assert len(matmuls) == 3 and all("scan" in inside for inside, _ in matmuls)
    shapes = sorted(tuple(eqn.outvars[0].aval.shape) for _, eqn in matmuls)
    assert shapes == [(2, 8, 16), (2, 8, 256), (256, 16)]  # d hidden, logits, d head
    assert {eqn.params["preferred_element_type"] for _, eqn in matmuls} == {jnp.dtype("float32")}
    # forward mode has no rule
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda h: chunked_head_loss(h, head, targets, None, 8), (hidden,), (hidden,))


def test_chunked_loss_matches_full(tiny_params):
    """chunked_causal_lm_loss (scanned LM head, logits never fully
    materialized) equals the full-logits loss — value AND gradients."""
    import numpy as np

    from ray_tpu.models.llama import causal_lm_loss, chunked_causal_lm_loss

    model = LlamaForCausalLM(CFG)
    params = tiny_params
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, CFG.vocab_size, (2, 32)),
        jnp.int32,
    )
    targets = jnp.roll(ids, -1, axis=1)

    def full(p):
        return causal_lm_loss(model.apply(p, ids), targets)

    def chunked(p):
        return chunked_causal_lm_loss(model, p, ids, targets, chunk_size=8)

    lf, gf = jax.value_and_grad(full)(params)
    lc, gc = jax.value_and_grad(chunked)(params)
    assert abs(float(lf) - float(lc)) < 1e-4, (lf, lc)
    for a, b in zip(
        jax.tree_util.tree_leaves(gf), jax.tree_util.tree_leaves(gc)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        )

    # Broadcastable [1, T] mask + odd length not divisible by the
    # chunk (padding path) agree with the full loss too.
    mask = (jnp.arange(ids.shape[1])[None, :] < ids.shape[1] - 3).astype(
        jnp.float32
    )
    lf = causal_lm_loss(model.apply(params, ids), targets, mask=mask)
    lc = chunked_causal_lm_loss(
        model, params, ids, targets, mask=mask, chunk_size=8
    )
    assert abs(float(lf) - float(lc)) < 1e-4
    odd_ids, odd_t = ids[:, :29], targets[:, :29]
    lf = causal_lm_loss(model.apply(params, odd_ids), odd_t)
    lc = chunked_causal_lm_loss(
        model, params, odd_ids, odd_t, chunk_size=8
    )
    assert abs(float(lf) - float(lc)) < 1e-4

    # bf16 params (the bench configuration): the chunked head must
    # accumulate in f32 and stay comparable to the full path.
    import dataclasses

    bcfg = dataclasses.replace(CFG, param_dtype=jnp.bfloat16)
    bmodel = LlamaForCausalLM(bcfg)
    bparams = bmodel.init(jax.random.PRNGKey(1), ids)
    lf = causal_lm_loss(bmodel.apply(bparams, ids), targets)
    lc = chunked_causal_lm_loss(bmodel, bparams, ids, targets, chunk_size=8)
    assert abs(float(lf) - float(lc)) < 5e-3, (lf, lc)
