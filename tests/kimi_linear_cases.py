"""What the test files of Kimi-Linear's architecture share
(``tests/test_kimi_linear_*.py``): the interpreter's switch, the configuration
file at its rehearsal size as a model (``kimi``), the reference's logits of
it, and one expert layer alone, whole (``whole_layer``) or a rank's share. A
plain module: a piece imports what it reads by name, and each piece that reads
a module-scoped fixture makes it once for itself.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.reference import kimi_linear_decoder as reference
from ray_tpu.models.kimi_linear import KimiLinearConfig, KimiLinearForCausalLM
from ray_tpu.models.mixtral import MoELayer


SEQ = 128
CONFIG = f"{cells.BENCH_DIR}/configs/kimi-linear-48b-a3b-l5.json"


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted, and
    # with them the scan kernels of ops/kda.py.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def kimi(dtype: str):
    """(configuration dict at its rehearsal size, model, params, ids)."""
    config = cells.load_json(CONFIG)
    config = {**config, **config["rehearsal"]}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": dtype, "param_dtype": dtype},
    }
    model = KimiLinearForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    return config, model, params, ids


@pytest.fixture(scope="module")
def kimi_f32():
    return kimi("float32")


@pytest.fixture(scope="module")
def expected(kimi_f32):
    """The reference's logits of the float32 parameters, which every case that
    holds a float32 program to it reads."""
    config, _, params, ids = kimi_f32
    return reference.forward(params, ids, config, SEQ)


# ------------------------------------------------- the expert layer alone


def expert_layer(held, **over):
    cfg = KimiLinearConfig(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_experts=16, num_experts_per_tok=4, num_shared_experts=1,
        routed_scaling_factor=2.446, experts_held=held, initializer_range=0.5,
        dtype=jnp.float32, param_dtype=jnp.float32, **over,
    )
    return MoELayer(cfg), cfg


@pytest.fixture(scope="module")
def whole_layer():
    """An uncut layer (every expert held), its parameters with a selection
    bias that is not zero, and tokens."""
    layer, cfg = expert_layer(None)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 48, 32)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    bias = np.random.default_rng(2).normal(size=16).astype(np.float32) * 0.3
    return cfg, {**params, "router_bias": jnp.asarray(bias)}, x
