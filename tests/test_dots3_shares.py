"""dots3-note-prev's architecture through the program's models, on the CPU: every
rank's share of a full layer's heads and of an expert layer's experts adds up
to the uncut reference (``tests/test_dots3_model.py`` has the model against
its reference and says what the reference is; ``tests/dots3_cases.py`` what
the files share).
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import dots3_note_decoder as reference
from ray_tpu.models.dots3 import Dots3Config
from ray_tpu.models.mixtral import MoELayer

from dots3_cases import interpret  # noqa: F401 - fixtures


def expert_layer(held):
    """One expert layer at dots3's routing: 32 experts scored, top-2,
    sigmoid, renormalised, x 1, one shared expert; ``held`` of them here."""
    cfg = Dots3Config(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_experts=32, num_experts_per_tok=2, num_shared_experts=1,
        experts_held=held, initializer_range=0.5,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return MoELayer(cfg)


def layer_config(held) -> dict:
    """The reference's keys for that layer."""
    lo, hi = held or (0, 32)
    return {"n_routed_experts_published": 32, "n_routed_experts": hi - lo,
            "expert_rank": lo // (hi - lo), "num_experts_per_tok": 2,
            "routed_scaling_factor": 1, "norm_topk_prob": True,
            "n_shared_experts": 1}


def test_the_32_expert_ranks_shares_add_up_to_the_uncut_layer():
    """32 ranks of one expert each: the routed parts they give, with the
    shared expert (which every rank computes alike) counted once, are the
    uncut reference's expert layer. With the head ranks' sum above, the parts
    of all 4 x 32 ranks are the uncut layer's two sublayers."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 64, 32)), jnp.float32)
    params = expert_layer(None).init(jax.random.PRNGKey(1), x)["params"]
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.moe(params, tokens, layer_config(None))
        shared = reference.swiglu(params["shared"], tokens)
        gates = np.asarray(reference.router_gates(params, tokens, layer_config(None)))
    total, pairs = 0.0, 0
    for rank in range(32):
        held = (rank, rank + 1)
        mine = {**params, **{k: params[k][held[0]:held[1]]
                             for k in ("w_gate", "w_up", "w_down")}}
        out = expert_layer(held).apply({"params": mine}, x).reshape(-1, 32)
        if rank % 8 == 0:
            with jax.default_matmul_precision("highest"):
                want = reference.moe(mine, tokens, layer_config(held))
            np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        pairs += int((gates[:, rank] > 0).sum())
        total = total + (out - shared)
    assert pairs == 64 * 2  # every pair is held by exactly one rank
    np.testing.assert_allclose(total + shared, uncut, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-5)  # renormalised, x 1
