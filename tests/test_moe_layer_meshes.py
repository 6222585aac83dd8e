"""The expert layer compiled on an expert mesh and on a seq-and-expert mesh: the
FFN is not replicated over seq, the replay runs no FFN loop again, the
gradients lie as the parameters, and a train step keeps its layout
(``tests/moe_cases.py`` has the tiny model).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moe_cases import (  # noqa: F401 - fixtures
    FFN_ROWS, FFN_TOKENS, interpret, tiny_moe,
)


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
