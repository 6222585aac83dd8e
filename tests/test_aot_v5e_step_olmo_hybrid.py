"""The Olmo-Hybrid cell's train step at its real size, lowered ahead of time for
a v5e chip, with no chip (``tests/aot_v5e.py`` has how;
``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
import pytest

from aot_v5e import _lowered_step, topo, v5e  # noqa: F401 - fixtures


@pytest.fixture(scope="module")
def olmo_hybrids_step(v5e):
    return _lowered_step(v5e, "olmo-hybrid-7b-l4.pretrain-8k")


def test_olmo_hybrids_step_holds_its_kernels_and_its_replay_runs_no_scan(olmo_hybrids_step):
    """Olmo-Hybrid's step at the benchmark's real size (b1 x s8192, four layers
    at the published widths): every kernel its configuration states; a linear
    layer is one ``_gdn_fwd_kernel`` and one ``_gdn_bwd_kernel`` in the whole
    step (the remat policy keeps ``gdn_o``, ``gdn_states``, ``gdn_t``), the
    states [B, N, H, 192, 96] float32 and the inverses a pair's two blocks side
    by side; and no KDA kernel."""
    from benchmarks.lib import cells, checks

    cell, text = olmo_hybrids_step
    stated = cells.stated_kernels(cell)
    counts = checks.count_pallas_kernels(text, stated)
    assert checks.holds_stated_kernels(counts, stated), (counts, stated)
    assert (counts["_gdn_fwd_kernel"], counts["_gdn_bwd_kernel"]) == (3, 3)
    assert (counts["_fwd_kernel"], counts["_bwd_dkv_kernel"], counts["_bwd_dq_kernel"]) == (1, 1, 1)
    assert checks.count_pallas_kernels(text, ("_kda_fwd_kernel", "_kda_bwd_kernel")) == {
        "_kda_fwd_kernel": 0, "_kda_bwd_kernel": 0}
    states, inverses = "tensor<1x128x30x192x96xf32>", "tensor<1x128x15x64x128xbf16>"
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    wrote = sum(f"{states}, {inverses})" in line for line in calls)
    read = sum(f"{states}, {inverses}," in line for line in calls)
    assert (wrote, read) == (3, 3)


def test_olmo_hybrids_step_convolves_by_the_kernels_and_broadcasts_no_decay(olmo_hybrids_step):
    """A linear layer's q with k (5,760 channels, float32 out) and its v (5,760,
    bfloat16 out) each go through ``_conv_forward`` in the forward pass and in
    the replay and through ``_conv_backward`` once: no ``short_conv`` fallback
    at these widths (no padded [B, T + 3, 5760] or [B, T + 3, 2880] copy). The
    decay reaches the scan as [B, H, T, 1]: no [B, T, H, 96] or [B, H, T, 96]
    array is made from it by a broadcast. And no norm over a head's channels
    is left to XLA: no float32 [1, 8192, 30, d] or [1, 30, 8192, d] array is
    reduced over its last axis."""
    import re

    from benchmarks.lib import checks

    _, text = olmo_hybrids_step
    calls = {entry: len(re.findall(rf"call @{entry}(?:_\d+)?\(", text))
             for entry in ("_conv_forward", "_conv_backward")}
    assert calls == {"_conv_forward": 2 * 2 * 3, "_conv_backward": 2 * 3}
    bodies = checks.count_pallas_kernels(text, ("_conv_fwd_kernel", "_conv_bwd_kernel"))
    assert bodies == {"_conv_fwd_kernel": 4, "_conv_bwd_kernel": 2}
    for channels in (2880, 5760):
        assert f"tensor<1x8195x{channels}xf32>" not in text
    broadcasts = re.findall(
        r"stablehlo\.broadcast_in_dim.*\(tensor<1x(?:8192x30|30x8192)(?:x1)?xf32>\) -> "
        r"tensor<1x(?:8192x30|30x8192)x96xf32>", text)
    assert not broadcasts, broadcasts[:2]
    reduced = re.findall(
        r"stablehlo\.reduce.* across dimensions = \[3\] : "
        r"\(tensor<1x(?:8192x30|30x8192)x(?:96|192)xf32>", text)
    assert not reduced, reduced[:2]


def test_olmo_hybrids_step_moves_no_operand_of_the_scan_but_the_decay_and_beta(
        olmo_hybrids_step):
    """The convolution writes q with k as [1, 60, 8192, 96], which the scan's
    kernels read as it lies (one operand [1, 2, 30, 8192, 96], a reshape of
    major extents), and their cotangents come back the same way: the lowered
    step transposes no float32 array of 96-wide heads, slices no [1, 8192,
    5760] projection to q's or k's 2,880 lanes and pads or joins none back,
    and no [1, 8192, 2880] array exists. v, the gate, o and their cotangents
    go through the kernels [1, 8192, 5760], as v's convolution and ``g_proj``
    write and ``o_proj`` reads them: no array of 192-wide heads is transposed
    either (there were 15 one way and 12 the other: v's, the gate's and o's,
    forward, replayed and backward)."""
    import re

    _, text = olmo_hybrids_step
    turned = re.findall(
        r"stablehlo\.transpose.*: \(tensor<([\dx]+)x(f32|bf16)>\) -> tensor<([\dx]+)x", text)
    assert turned
    assert not [t for t in turned if t[0].endswith(("x96", "x192"))], turned
    assert "x2880xf32>" not in text
    # the scan's calls take what the convolution's return, a reshape apart
    scans = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "tensor<1x2x30x8192x96xf32>" in line]
    assert len(scans) == 2 * 3
    assert all("tensor<1x8192x5760xbf16>" in line for line in scans)
    assert text.count("-> tensor<1x60x8192x96xf32>") >= 2 * 3  # ``_conv_forward``'s
