"""The MiniCPM-SALA cell's train step at its real size, lowered ahead of time for
a v5e chip, with no chip (``tests/aot_v5e.py`` has how;
``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
import pytest

from ray_tpu.ops import kda

from aot_v5e import (  # noqa: F401 - fixtures
    ROTARY_STEPS, _lowered_step, _turns, topo, v5e,
)


@pytest.fixture(scope="module")
def minicpm_salas_step(v5e):
    return _lowered_step(v5e, "minicpm-sala-9b-l4.long16k")


def test_minicpm_salas_step_holds_its_kernels_under_their_names(minicpm_salas_step):
    """MiniCPM-SALA's step at the benchmark's real size (b1 x s16384, four
    layers at the published widths): every kernel its configuration states and
    no more of any (the remat policy keeps ``sparse_o``, ``sparse_lse``,
    ``lightning_o``, ``lightning_states``: no replay runs a forward kernel);
    the Lightning states [1, 32, 64, 128, 128] float32, written thrice and
    read thrice; no causal flash kernel, no delta-rule kernel."""
    from benchmarks.lib import cells, checks

    cell, text = minicpm_salas_step
    stated = cells.stated_kernels(cell)
    counts = checks.count_pallas_kernels(text, stated)
    assert counts == {k: s["least"] for k, s in stated.items()} == {
        "_sparse_fwd_kernel": 1, "_bwd_dkv_sparse_kernel": 1,
        "_bwd_dq_sparse_kernel": 1, "_lightning_fwd_kernel": 3,
        "_lightning_bwd_kernel": 3}
    assert _turns(text) == ROTARY_STEPS[cell["name"]]
    others = ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel",
              "_gdn_fwd_kernel", "_kda_fwd_kernel")
    assert not any(checks.count_pallas_kernels(text, others).values())
    states = f"tensor<1x32x{16384 // kda.LIGHTNING_CHUNK}x128x128xf32>"
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum(f"{states})" in line for line in calls) == 3
    assert sum(f"{states}," in line for line in calls) == 3


def test_minicpm_salas_step_repeats_no_k_or_v_and_makes_no_t_by_t_array(minicpm_salas_step):
    """K and V reach the sparse kernels at their own 2 heads: no [1, 32, 16384,
    128] array is made from a [1, 2, ...] one by a broadcast (``jnp.repeat``'s
    lowering), and the kernels' K and V operands are [2, 16384, 128]. No array
    of scores or of a mask is [.., 16384, 16384] (the SwiGLU's [1, 16384,
    16384] bfloat16 products are the only ones of that extent: the
    intermediate size is the sequence's length here): the selection is [1, 2,
    16384, 256] bits and [2, 16384, 128] words. The replay is handed the set and
    chooses nothing again: one while loop of the selection in the step."""
    import re

    _, text = minicpm_salas_step
    assert not re.findall(r"16384x16384x(?:f32|i1|i8|i32)|(?:32|16|2)x16384x16384x", text)
    repeats = re.findall(
        r"stablehlo\.broadcast_in_dim.*\(tensor<1x2x(?:1x)?16384x128xbf16>\) -> "
        r"tensor<1x2x16x16384x128xbf16>", text)
    assert not repeats, repeats[:2]
    sparse = [line for line in text.splitlines()
              if "tpu_custom_call" in line and "_sparse_fwd_kernel" in line]
    assert len(sparse) == 1 and sparse[0].count("tensor<2x16384x128xbf16>") >= 2
    assert "tensor<2x16384x128xi32>" in sparse[0]  # the words, a lane a key tile
    assert "tensor<1x2x16384x256xi1>" in text  # the chosen blocks
    assert text.count("tensor<1x2x16384x256xi1>") >= 2
