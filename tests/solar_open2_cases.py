"""What the test files of Solar-Open2's architecture share
(``tests/test_solar_open2_*.py``): the interpreter's switch, the configuration
file at its rehearsal size as a model (``solar``), and the constants. A plain
module: a piece imports what it reads by name, and each piece that reads a
module-scoped fixture makes it once for itself.
"""
import jax
import numpy as np
import pytest

from benchmarks.lib import cells
from ray_tpu.models.solar_open2 import SolarOpen2ForCausalLM


SEQ = 128
CONFIG = f"{cells.BENCH_DIR}/configs/solar-open2-250b-l4.json"


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted, and
    # with them the scan kernels of ops/kda.py and, at 128 rows, the flash
    # kernels.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


@pytest.fixture(scope="module")
def solar():
    """(configuration dict at its rehearsal size, model, params, ids): 4 q
    heads over 2 K/V heads of 32, 4 KDA heads of 32, 20 experts top-4 of
    which 4 are held, float32."""
    config = cells.load_json(CONFIG)
    config = {**config, **config["rehearsal"]}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": "float32",
                "param_dtype": "float32"},
    }
    model = SolarOpen2ForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    # b_proj's draw of 0.02 leaves beta within 0.25 of 1: widen it, so that
    # beta runs over (0, 2) and a doubling left out is far from the model.
    p = dict(params["params"])
    for i in (1, 2, 3):
        kda = dict(p[f"layers_{i}"]["kda"])
        kda["b_proj"] = {"kernel": kda["b_proj"]["kernel"] * 12.0}
        p[f"layers_{i}"] = {**p[f"layers_{i}"], "kda": kda}
    return config, model, {"params": p}, ids
