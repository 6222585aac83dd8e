"""Each plain reference against the system at a tiny size on the CPU.

In float32 the two must agree to rounding: that is what shows the reference
computes the system's function (and the published one, which it follows).
In the system's bfloat16 they must agree within the tolerance the
reference's file states, which is what a chip run holds them to."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement

CONFIGS = ["mistral-7b-l4", "mixtral-8x7b-l2"]


def tiny(name, dtype):
    config = cells.load_json(f"{cells.BENCH_DIR}/configs/{name}.json")
    config = {**config, **config["rehearsal"]}
    config["program"] = {
        **config["program"],
        "set": {**config["program"]["set"], "dtype": dtype, "param_dtype": dtype},
    }
    return config


def both_logits(config, seq=256, last=128):
    cfg = cells.program_config(config)
    model = cells.resolve(config["program"]["model"])(cfg)
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], seq)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[None, :8])
    reference = importlib.import_module(config["reference"])
    system = model.apply(params, ids[None])[0, -last:]
    return system, reference.forward(params, ids, config, last), reference


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_the_system_in_float32(name):
    system, expected, _ = both_logits(tiny(name, "float32"))
    assert system.dtype == jnp.float32
    result = logits_agreement(
        system, expected,
        # A routing flip needs two router probabilities within float32
        # rounding of each other: none is expected in 128 positions.
        {"per_position_rel_err": 1e-4, "min_share_within": 1.0},
    )
    assert result["ok"], result


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_the_system_in_bfloat16_within_its_tolerance(name):
    system, expected, reference = both_logits(tiny(name, "bfloat16"))
    tolerance = dict(reference.TOLERANCE)
    if tolerance["min_share_within"] < 1.0:
        # The share of routing flips is the real widths' (measured on the
        # chip). At hidden 128 with 4 experts the router's gaps are narrower
        # and 2 to 3 positions in 128 flip: per position the file's
        # tolerance, for the share this size's own.
        tolerance["min_share_within"] = 0.95
    result = logits_agreement(system, expected, tolerance)
    assert result["ok"], result
    # and the tolerance is not slack: the system is not the reference
    assert result["rel_err_median"] > 1e-4


def test_a_reference_of_another_function_is_refused():
    config = tiny("mistral-7b-l4", "float32")
    system, _, reference = both_logits(config)
    other, _, _ = both_logits({**config, "rope_theta": 10000.0})
    result = logits_agreement(system, other.astype(jnp.float32), reference.TOLERANCE)
    assert not result["ok"], result


def test_the_references_import_nothing_from_the_programs_models_or_ops():
    import os
    import re

    directory = os.path.join(cells.BENCH_DIR, "reference")
    for entry in os.listdir(directory):
        if entry.endswith(".py"):
            with open(os.path.join(directory, entry)) as f:
                assert not re.search(r"^\s*(from|import) ray_tpu", f.read(), re.M), entry
