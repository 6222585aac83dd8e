"""What the test files of dots3-note-prev's architecture share
(``tests/test_dots3_*.py``): the interpreter's switch, the configuration file
at its rehearsal widths as a model (``dots3``), the reference's logits of it,
and one latent mixer alone. A plain module: a piece imports what it reads by
name, and each piece that reads a module-scoped fixture makes it once for
itself.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.reference import dots3_note_decoder as reference
from ray_tpu.models.dots3 import Dots3Config, Dots3ForCausalLM
from ray_tpu.models.mla import LatentKind, MLAMixer
from ray_tpu.util import tracing


SEQ = 128
CONFIG = f"{cells.BENCH_DIR}/configs/dots3-note-prev-l5.json"
# Past these a float32 program is another function than the reference.
FAR = {"per_position_rel_err": 1e-3, "min_share_within": 0.5}


@pytest.fixture(scope="module", autouse=True)
def interpret():
    # "gmm" has no XLA stand-in: on the CPU its kernels are interpreted, and
    # at 128 rows so are the indexer, the selection's and the band's kernels.
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        yield


def tiny(**changes) -> dict:
    """The file at its rehearsal widths in float32: 128 positions choose 48
    keys of up to 128 in the full layers and see 40 in the sliding ones."""
    config = cells.load_json(CONFIG)
    config = {**config, **config["rehearsal"], "index_topk": 48,
              "sliding_window_size": 40, "num_experts_per_tok": 2, **changes}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": "float32",
                "param_dtype": "float32"},
    }
    return config


@pytest.fixture(scope="module")
def dots3():
    config = tiny()
    model = Dots3ForCausalLM(cells.program_config(config))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    return config, model, params, ids


@pytest.fixture(scope="module")
def expected(dots3):
    config, _, params, ids = dots3
    return reference.forward(params, ids, config, SEQ)


# ------------------------------------------------ a hand-written line each


def one_mixer(kind: LatentKind, seq=SEQ, hidden=64, seed=0):
    cfg = Dots3Config(
        hidden_size=hidden, latents=((tracing.MLA, kind),),
        initializer_range=0.3, dtype=jnp.float32, param_dtype=jnp.float32)
    x = jnp.asarray(
        np.random.default_rng(seed).normal(size=(1, seq, hidden)), jnp.float32)
    positions = jnp.arange(seq)[None]
    mixer = MLAMixer(cfg, name=tracing.MLA)
    params = mixer.init(jax.random.PRNGKey(seed), x, positions)
    return mixer, params, x, positions
