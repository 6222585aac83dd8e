"""What the test files of Laguna's architecture share
(``tests/test_laguna_*.py``): the interpreter's switch, the configuration file
at its rehearsal size as a model (``laguna``), and the constants. A plain
module: a piece imports what it reads by name, and each piece that reads a
module-scoped fixture makes it once for itself.
"""
import jax
import numpy as np
import pytest

from benchmarks.lib import cells
from ray_tpu.models.laguna import LagunaForCausalLM


SEQ = 128
CONFIG = f"{cells.BENCH_DIR}/configs/laguna-xs2-33b-a3b-l8.json"


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted, and
    # at 128 rows so are the flash kernels, windowed and causal.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


@pytest.fixture(scope="module")
def laguna():
    """(configuration dict at a tiny size, model, params, ids): the file's
    rehearsal widths, a window of 40 under 128 positions, 16 experts top-2 of
    which 4 are held, float32."""
    config = cells.load_json(CONFIG)
    config = {**config, **config["rehearsal"], "sliding_window": 40,
              "num_experts_per_tok": 2}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": "float32",
                "param_dtype": "float32"},
    }
    model = LagunaForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    return config, model, params, ids
