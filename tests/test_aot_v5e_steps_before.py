"""The steps of the models the benchmark had first, at their rehearsal sizes,,
lowered ahead of time for a v5e chip, with no chip (``tests/aot_v5e.py`` has
how; ``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
import jax
import pytest

from aot_v5e import topo, v5e  # noqa: F401 - fixtures


# The models the benchmark already had lower to the Pallas kernels they had
# before a layer could choose its mixer and FFN: read by this same code at
# commit 57913f4, each configuration file at its rehearsal size, b1 x s256.
KERNELS_BEFORE = {
    "mistral-7b-l4": {"_fwd_kernel": 4, "_bwd_dkv_kernel": 2, "_bwd_dq_kernel": 2},
    "mixtral-8x7b-l2": {"_fwd_kernel": 4, "_bwd_dkv_kernel": 2, "_bwd_dq_kernel": 2},
    "olmoe-1b-7b-1chip": {"_fwd_kernel": 4, "_bwd_dkv_kernel": 2, "_bwd_dq_kernel": 2,
                          "_gmm_kernel": 18, "_tgmm_kernel": 6},
}


@pytest.mark.parametrize("name", sorted(KERNELS_BEFORE))
def test_the_models_that_were_there_lower_to_the_kernels_they_had(v5e, monkeypatch, name):
    import importlib

    import numpy as np
    import optax

    from benchmarks.lib import cells, checks
    from ray_tpu import train
    from ray_tpu.models.llama import causal_lm_loss
    from ray_tpu.models.mixtral import moe_lm_loss

    # The program takes its kernels where the backend is the TPU; here it is
    # the CPU, and the test stands in for that one probe.
    attention = importlib.import_module("ray_tpu.ops.attention")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    config = cells.load_json(f"{cells.BENCH_DIR}/configs/{name}.json")
    config = {**config, **config["rehearsal"]}
    cfg = cells.program_config(config)
    model = cells.resolve(config["program"]["model"])(cfg)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), tree)

    if hasattr(cfg, "num_experts"):
        loss = lambda p, ids, t: moe_lm_loss(model, p, ids, t)  # noqa: E731
    else:
        loss = lambda p, ids, t: causal_lm_loss(model.apply(p, ids), t)  # noqa: E731
    tx = optax.adamw(3e-4)
    batch = jax.ShapeDtypeStruct((1, 256), np.int32, sharding=v5e)
    text = train.make_train_step(loss, tx).lower(
        placed(shapes), placed(jax.eval_shape(tx.init, shapes)), batch, batch
    ).as_text()
    names = ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel", "_gmm_kernel",
             "_tgmm_kernel", "_kda_fwd_kernel", "_kda_bwd_kernel", "_unwritten_kernel",
             # none at a rehearsal's heads of 32 lanes: ROTARY_STEPS has the cells'
             "_rotary_kernel")
    counts = {k: n for k, n in checks.count_pallas_kernels(text, names).items() if n}
    assert counts == KERNELS_BEFORE[name]
