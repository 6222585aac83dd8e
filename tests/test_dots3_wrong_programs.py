"""dots3-note-prev's architecture through the program's models, on the CPU:
``benchmarks/tools/wrong_dots3.py``'s wrong programs are far from the
reference (``tests/test_dots3_model.py`` has the model against its reference
and says what the reference is; ``tests/dots3_cases.py`` what the files
share).
"""
import jax
import pytest

from benchmarks.lib.checks import logits_agreement
from benchmarks.tools import wrong_dots3
from ray_tpu.models.dots3 import Dots3ForCausalLM
from ray_tpu.util import tracing

from dots3_cases import (  # noqa: F401 - fixtures
    FAR, dots3, expected, interpret,
)


def without(params, names):
    return {"params": {
        layer: {mixer: {k: v for k, v in sub.items() if k not in names}
                if mixer in tracing.MIXERS else sub for mixer, sub in held.items()}
        if layer.startswith("layers_") else held
        for layer, held in params["params"].items()}}


@pytest.mark.parametrize("name", [
    "system_no_rescale", "system_no_gate", "system_window_512",
    "system_window_514", "system_top_2047", "system_no_selection"])
def test_a_wrong_program_is_far_from_the_reference(dots3, expected, name):
    _, model, params, ids = dots3
    cfg, *drop = wrong_dots3.programs(model.cfg)[name]
    logits = jax.jit(Dots3ForCausalLM(cfg).apply)(
        without(params, drop[0]) if drop else params, ids[None])[0]
    found = logits_agreement(logits, expected, FAR)
    assert not found["ok"], found
