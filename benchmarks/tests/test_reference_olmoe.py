"""OLMoE's plain reference at a tiny size on the CPU: against the system
(the Pallas kernels of its ``gmm`` dispatch in interpret mode), and against
counts made by hand. tests/test_olmoe_model.py holds the system to it in
more ways (gradients, wrong programs refused); this file is about the
reference itself."""
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.lib.checks import logits_agreement
from benchmarks.reference import olmoe_decoder

SEQ = 128


@pytest.fixture(scope="module")
def tiny():
    """(config at its rehearsal size in float32, model, params, ids)."""
    config = cells.load_json(f"{cells.BENCH_DIR}/configs/olmoe-1b-7b-1chip.json")
    config = {**config, **config["rehearsal"]}
    config["program"] = {**config["program"], "set": {
        **config["program"]["set"], "dtype": "float32", "param_dtype": "float32"}}
    model = cells.resolve(config["program"]["model"])(cells.program_config(config))
    ids = np.random.default_rng(1).integers(0, config["vocab_size"], SEQ)
    ids = ids.astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), ids[None, :8])
    return config, model, params, ids


def test_reference_agrees_with_the_system_in_float32(tiny, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    config, model, params, ids = tiny
    result = logits_agreement(
        model.apply(params, ids[None])[0, -64:],
        olmoe_decoder.forward(params, ids, config, 64),
        {"per_position_rel_err": 1e-4, "min_share_within": 1.0},
    )
    assert result["ok"] and result["positions"] == 64, result


def test_a_reference_in_eight_bit_floats_is_not_correct(tiny):
    """The nearest precision below the configuration's bfloat16: the
    tolerance has to refuse it (on the chip it reads 0.09, PERF.md)."""
    config, _, params, ids = tiny
    expected = olmoe_decoder.forward(params, ids, config, SEQ)
    rounded = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), params
    )
    result = logits_agreement(
        olmoe_decoder.forward(rounded, ids, config, SEQ), expected,
        olmoe_decoder.TOLERANCE,
    )
    assert not result["ok"] and result["share_within"] < 0.05, result


def test_gates_are_the_top_k_probabilities_as_they_are(tiny):
    config, _, params, _ = tiny
    p = params["params"]["layers_0"]["moe"]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(16, config["hidden_size"])),
                    jnp.float32)
    out, _ = olmoe_decoder.moe(p, x, config)
    renormalised, _ = olmoe_decoder.moe(p, x, {**config, "norm_topk_prob": True})
    probs = jax.nn.softmax(x @ p["router"]["kernel"], axis=-1)
    mass = jax.lax.top_k(probs, config["num_experts_per_tok"])[0].sum(-1)
    assert float(mass.max()) < 1.0
    np.testing.assert_allclose(out, renormalised * mass[:, None], rtol=1e-5, atol=1e-7)
    # by hand for one token: its two experts' SwiGLU, weighted
    top, idx = jax.lax.top_k(probs[0], 2)
    want = sum(
        g * (jax.nn.silu(x[0] @ p["w_gate"][e]) * (x[0] @ p["w_up"][e])) @ p["w_down"][e]
        for g, e in zip(top, idx)
    )
    np.testing.assert_allclose(out[0], want, rtol=1e-4, atol=1e-7)


def test_loss_is_cross_entropy_plus_the_load_balancing_term(tiny):
    config, _, params, ids = tiny
    targets = np.roll(ids, -1)
    logits = olmoe_decoder.forward(params, ids, config, SEQ)
    nll = -jax.nn.log_softmax(logits)[np.arange(SEQ), targets].mean()
    _, balance = olmoe_decoder.hidden_states(params, ids, config)
    assert float(olmoe_decoder.loss(params, ids, targets, config)) == pytest.approx(
        float(nll) + 0.01 * float(balance), rel=1e-6)
    # A router that cannot tell the experts apart: p_e = 1/E, every token
    # chooses k experts, so E * sum_e f_e * p_e = k.
    flat = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a) if "router" in str(path) else a, params)
    _, uniform = olmoe_decoder.hidden_states(flat, ids, config)
    assert float(uniform) == pytest.approx(config["num_experts_per_tok"], rel=1e-6)
    assert float(balance) >= config["num_experts_per_tok"] * 0.999


def test_the_readings_the_tolerance_is_set_from_can_be_taken_again(
        tmp_path, monkeypatch):
    """benchmarks/tools/reference_readings.py at the rehearsal size: the
    system within the tolerance, the wrong programs and the reference in
    eight-bit floats outside it."""
    from benchmarks.tools import reference_readings

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cell = "olmoe-1b-7b-1chip.dropless-4k"
    monkeypatch.setattr(sys, "argv", [
        "reference_readings.py", "--workload", cell, "--seeds", "4000000001",
        "--wrong", "1", "--expert-scale", "0.5", "--rehearse",
        "--out", str(tmp_path)])
    reference_readings.main()
    (line,) = (tmp_path / f"{cell}.jsonl").read_text().splitlines()
    line = json.loads(line)
    within = f"within_{olmoe_decoder.TOLERANCE['per_position_rel_err']}"
    share = olmoe_decoder.TOLERANCE["min_share_within"]
    assert line["seed"] == 4000000001 and line["positions"] == 256
    assert line["system"][within] >= share
    assert line["system_experts_scaled"][within] >= share
    for wrong in ("system_capacity_1.25_drops", "system_other_gate_normalisation",
                  "reference_e4m3"):
        assert line[wrong][within] < share, wrong
    assert "reference_e4m3_experts" in line
