"""The LFM2 cell's train step at its real size, lowered ahead of time for a v5e
chip, with no chip (``tests/aot_v5e.py`` has how;
``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
import pytest

from aot_v5e import _lowered_step, topo, v5e  # noqa: F401 - fixtures


@pytest.fixture(scope="module")
def lfm2s_step(v5e):
    return _lowered_step(v5e, "lfm2-8b-a1b-l5.dropfree-4k")


def test_lfm2s_step_holds_its_kernels_and_reads_the_projections_thirds_in_place(lfm2s_step):
    """The LFM2 cell's step at the benchmark's real size (b2 x s4096, layers
    1-5 at the published widths, all 32 experts): every kernel its
    configuration states; the four conv layers' gated convolutions by the
    kernels, forward, replayed and backward, each reading the one [2, 4096,
    6144] array and the pass back writing its cotangent whole (no slice of a
    third, no float32 copy, no concatenation, no padded copy of the XLA
    road); four expert layers of six ``_gmm_kernel`` and three
    ``_tgmm_kernel`` calls and a replay's three; the attention layer's three
    causal kernels; no scan's kernel and no un-gated convolution."""
    import re

    from benchmarks.lib import cells, checks

    cell, text = lfm2s_step
    stated = cells.stated_kernels(cell)
    counts = checks.count_pallas_kernels(text, stated)
    assert checks.holds_stated_kernels(counts, stated), (counts, stated)
    assert counts == {
        "_fwd_kernel": 2, "_bwd_dkv_kernel": 1, "_bwd_dq_kernel": 1,
        "_gmm_kernel": 36, "_tgmm_kernel": 12,
        "_gated_conv_fwd_kernel": 2, "_gated_conv_bwd_kernel": 1}
    others = ("_conv_fwd_kernel", "_conv_bwd_kernel", "_kda_fwd_kernel", "_gdn_fwd_kernel",
              "_ssd_fwd_kernel", "_lightning_fwd_kernel", "_rotary_kernel")
    assert not any(checks.count_pallas_kernels(text, others).values())
    entries = {entry: len(re.findall(rf"call @{entry}(?:_\d+)?\(", text))
               for entry in ("_gated_forward", "_gated_backward")}
    assert entries == {"_gated_forward": 2 * 4, "_gated_backward": 4}
    assert "tensor<2x4096x6144xf32>" not in text and "tensor<2x4098x2048xf32>" not in text
    thirds = [line for line in text.splitlines()
              if "stablehlo.slice" in line and "tensor<2x4096x6144xbf16>" in line]
    joined = [line for line in text.splitlines()
              if "stablehlo.concatenate" in line and "tensor<2x4096x6144xbf16>" in line]
    assert not thirds and not joined
