"""The long16k cell's train step at its real size, lowered ahead of time for a
v5e chip, with no chip (``tests/aot_v5e.py`` has how;
``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
from aot_v5e import _lower_step, topo, v5e  # noqa: F401 - fixtures


def test_long16ks_step_makes_the_heads_gradients_in_the_forward_loop_and_fits(
        topo, v5e, monkeypatch):
    """mistral-7b-l4.long16k's step at the benchmark's real size (b1 x s16384
    in eight chunks of 2,048 over a vocabulary of 32,768). The lowered text
    multiplies at the head's shape three times, the logits and the two
    products of their cotangent, all in the loss's one loop (``models/llama.py``
    ``_chunked_nll``; the replayed loss had four and a second loop under
    ``transpose(jvp(loss))``). Compiled for v5e as the benchmark lowers it
    (``benchmarks/rehearse.py``), the [32768, 4096] sum of the head's gradients
    is alive with every layer's residuals and the step still takes no more of
    the chip than the replayed loss's did (6.38 GiB of arguments + 7.50 of
    temporaries, PERF.md 6, PR 66)."""
    import importlib
    import os
    import re
    import sys

    # benchmarks/rehearse.py is a script: as it is imported it puts the repo
    # first on sys.path and names a log directory, and a cluster that a later
    # test of this process starts would hand its workers that sys.path[0]
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    rehearse = importlib.import_module("benchmarks.rehearse")

    _, lowered = _lower_step(v5e, "mistral-7b-l4.long16k")
    text = lowered.as_text(debug_info=True)
    matmuls = [line for line in text.splitlines()
               if "stablehlo.dot_general" in line and "32768" in line]
    assert len(matmuls) == 3, matmuls
    assert sorted(re.search(r"-> tensor<(\w+)>", line).group(1) for line in matmuls) == [
        "1x2048x32768xf32", "1x2048x4096xf32", "32768x4096xf32"]
    names = re.findall(r'"(jit\(train_step\)/[^"]*)"', text)
    assert any(n.startswith("jit(train_step)/jvp(loss)/while/") for n in names)
    assert not [n for n in names if n.startswith("jit(train_step)/transpose(jvp(loss))/while")]
    assert not [n for n in names if "(loss)" in n and "rematted_computation" in n]
    monkeypatch.setattr(
        importlib.import_module("ray_tpu.ops.attention"), "_on_tpu", lambda: True)
    found = rehearse.compile_cell("mistral-7b-l4.long16k", topo)
    assert found["holds_stated_kernels"]
    assert found["arguments_gib"] + found["temporaries_gib"] < 13.9, found
