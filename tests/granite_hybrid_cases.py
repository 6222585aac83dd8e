"""What the test files of Granite's architecture share
(``tests/test_granite_hybrid_*.py``): the interpreter's switch, the
configuration file at its rehearsal size as a model (``granite``), and the
constants. A plain module: a piece imports what it reads by name, and each
piece that reads a module-scoped fixture makes it once for itself.
"""
import math

import jax
import numpy as np
import pytest

from benchmarks.lib import cells
from ray_tpu.models.granite_hybrid import GraniteHybridForCausalLM


SEQ = 512  # two chunks of 256, and two blocks of the reference's query rows
CONFIG = f"{cells.BENCH_DIR}/configs/granite-4-h-micro-l10.json"
NEAR = {"per_position_rel_err": 5e-5, "min_share_within": 1.0}


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # The scan's and the convolution's kernels and, from 128 rows, the flash ones.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def in_float32(config):
    return {**config, "program": {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": "float32",
                "param_dtype": "float32"}}}


def build(sizes, seq, seed):
    config = in_float32({**cells.load_json(CONFIG), **sizes})
    model = GraniteHybridForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(seed).integers(0, config["vocab_size"], seq)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), ids[None, :8])
    # The draws of 0.02 leave every projection of 128 channels near zero: the
    # steps are then all bias, B and C all filter bias and q.k all weight.
    # Projections of unit size give the recurrence and the soft-max data.
    p = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 8.0 if path[-1].key == "kernel" and path[-2].key in (
            "q_proj", "k_proj", "v_proj", "xbc_proj", "dt_proj", "z_proj") else w,
        params["params"])
    return config, model, {"params": p}, ids


@pytest.fixture(scope="module")
def granite():
    """(configuration dict at the rehearsal size, model, params, ids), float32."""
    return build(cells.load_json(CONFIG)["rehearsal"], SEQ, 0)


def leaves(tree):
    return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
