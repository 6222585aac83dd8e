"""The Granite cell's train step at its real size, lowered ahead of time for a
v5e chip, with no chip (``tests/aot_v5e.py`` has how;
``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
import pytest

from ray_tpu.ops import kda

from aot_v5e import _lowered_step, topo, v5e  # noqa: F401 - fixtures


@pytest.fixture(scope="module")
def granites_step(v5e):
    return _lowered_step(v5e, "granite-4-h-micro-l10.pretrain-8k")


def test_granites_step_holds_its_kernels_and_its_replay_runs_no_scan(granites_step):
    """The Granite cell's step at the benchmark's real size (b1 x s8192, ten
    layers at the published widths): every kernel its configuration states; a
    mamba layer is one ``_ssd_fwd_kernel`` and one ``_ssd_bwd_kernel`` in the
    whole step (the remat policy keeps ``ssd_y`` and ``ssd_states``), the
    states [1, 32, 8, 4, 128, 128] float32 written nine times and read nine
    times; B and C reach the kernels as [1, 8192, 128], never a head's copy;
    the one attention layer's three causal kernels; no other scan's kernel."""
    import re

    from benchmarks.lib import cells, checks

    cell, text = granites_step
    stated = cells.stated_kernels(cell)
    counts = checks.count_pallas_kernels(text, stated)
    assert checks.holds_stated_kernels(counts, stated), (counts, stated)
    assert (counts["_ssd_fwd_kernel"], counts["_ssd_bwd_kernel"]) == (9, 9)
    assert (counts["_fwd_kernel"], counts["_bwd_dkv_kernel"], counts["_bwd_dq_kernel"]) == (1, 1, 1)
    others = ("_kda_fwd_kernel", "_gdn_fwd_kernel", "_lightning_fwd_kernel",
              "_sparse_fwd_kernel", "_fwd_window_kernel")
    assert not any(checks.count_pallas_kernels(text, others).values())
    states = f"tensor<1x{8192 // kda.SSD_CHUNK}x8x4x128x128xf32>"
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum(f"{states})" in line for line in calls) == 9
    assert sum(f"{states}," in line for line in calls) == 9
    assert "tensor<1x8192x64x128x" not in text  # no B or C a head
    # the convolution by the kernels, forward, replayed and backward, a layer
    entries = {entry: len(re.findall(rf"call @{entry}(?:_\d+)?\(", text))
               for entry in ("_conv_forward", "_conv_backward")}
    assert entries == {"_conv_forward": 2 * 9, "_conv_backward": 9}
    assert "tensor<1x8195x4352xf32>" not in text  # no short_conv fallback
