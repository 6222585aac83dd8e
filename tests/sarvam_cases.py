"""What the test files of sarvam's architecture share
(``tests/test_sarvam_mla_*.py``): the interpreter's switch, the configuration
file at its rehearsal size as a model with norm weights away from their
initial ones (``sarvam``), and the reference's logits of it. A plain module: a
piece imports what it reads by name, and each piece that reads a module-scoped
fixture makes it once for itself.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.reference import sarvam_mla_decoder as reference
from ray_tpu.models.mla import YarnScaling
from ray_tpu.models.sarvam_mla import SarvamMLAForCausalLM


SEQ = 128
CONFIG = f"{cells.BENCH_DIR}/configs/sarvam-105b-l5.json"
PUBLISHED_YARN = YarnScaling(
    factor=40, original_max_position_embeddings=4096, beta_fast=32, beta_slow=1,
    mscale=1, mscale_all_dim=1,
)


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def sarvam(dtype: str):
    """(configuration dict at its rehearsal size, model, params, ids)."""
    config = cells.load_json(CONFIG)
    config = {**config, **config["rehearsal"]}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": dtype, "param_dtype": dtype},
    }
    model = SarvamMLAForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    # Norm weights away from their initial ones, so that a norm on the wrong
    # side of the rotation, or left out, shows.
    rng = np.random.default_rng(3)
    p = jax.tree_util.tree_map(lambda a: a, params)
    for i in range(config["num_hidden_layers"]):
        for name in ("q_norm", "k_norm"):
            scale = p["params"][f"layers_{i}"]["mla"][name]["scale"]
            p["params"][f"layers_{i}"]["mla"][name]["scale"] = jnp.asarray(
                rng.uniform(0.5, 1.5, scale.shape), scale.dtype)
    return config, model, p, ids


@pytest.fixture(scope="module")
def sarvam_f32():
    return sarvam("float32")


# -------------------------------------------- the model against the reference


@pytest.fixture(scope="module")
def expected(sarvam_f32):
    """The reference's logits of the float32 parameters, which every case that
    holds a float32 program to it reads."""
    config, _, params, ids = sarvam_f32
    return reference.forward(params, ids, config, SEQ)
