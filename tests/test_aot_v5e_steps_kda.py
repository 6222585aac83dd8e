"""The Kimi-Linear and Solar-Open2 cells' train steps (the KDA hybrids) at their
real sizes, lowered ahead of time for a v5e chip, with no chip
(``tests/aot_v5e.py`` has how; ``tests/test_kernels_aot_v5e.py`` the flash
kernels).
"""
import pytest

from ray_tpu.ops import kda

from aot_v5e import _lowered_step, topo, v5e  # noqa: F401 - fixtures


# Kimi-Linear's step at the benchmark's real size (b1 x s16384, five layers at
# the published widths), lowered once for the tests below; and Solar-Open2's
# (b1 x s4096, four layers).
@pytest.fixture(scope="module")
def kimi_linears_step(v5e):
    return _lowered_step(v5e, "kimi-linear-48b-a3b-l5.longctx-16k")


@pytest.fixture(scope="module")
def solar_open2s_step(v5e):
    return _lowered_step(v5e, "solar-open2-250b-l4.pretrain-4k")


@pytest.mark.parametrize("step,layers", [("kimi_linears_step", 4), ("solar_open2s_step", 3)])
def test_a_steps_replay_runs_no_kda_forward_and_its_backward_reads_the_inverses(
        request, step, layers):
    """A KDA layer is one ``_kda_fwd_kernel`` and one ``_kda_bwd_kernel`` in
    the whole step, forward pass, replay and backward pass together: the
    remat policy keeps o, the states and every chunk's inverse T
    (``kda_o``, ``kda_states``, ``kda_t``), so no replay runs the forward
    kernel to remake one of them. T leaves the forward kernel as [B, N, H /
    2, 64, 128] in the matmuls' dtype, a pair's two blocks side by side, an
    eighth of the states' bytes."""
    from benchmarks.lib import checks

    cell, text = request.getfixturevalue(step)
    counts = checks.count_pallas_kernels(text, ("_kda_fwd_kernel", "_kda_bwd_kernel"))
    assert counts == {"_kda_fwd_kernel": layers, "_kda_bwd_kernel": layers}
    traffic, kda_cfg = cell["traffic"], cell["config"]["linear_attn_config"]
    b, n, h, d = (traffic["batch"], traffic["seq"] // kda.CHUNK, kda_cfg["num_heads"],
                  kda_cfg["head_dim"])
    states, inverses = f"tensor<{b}x{n}x{d}x{h * d}xf32>", f"tensor<{b}x{n}x{h // 2}x64x128xbf16>"
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    # the forward's last two results; the backward's operands before do
    wrote = sum(f"{states}, {inverses})" in line for line in calls)
    read = sum(f"{states}, {inverses}," in line for line in calls)
    assert (wrote, read) == (layers, layers)


@pytest.mark.parametrize("step,layers", [("kimi_linears_step", 4), ("solar_open2s_step", 3)])
def test_a_kda_layer_convolves_its_three_projections_by_the_kernels(request, step, layers):
    """A layer's q, k and v each go through ``_conv_forward`` in the forward
    pass and again in the replay (the remat policy keeps none of the
    convolution's outputs: 0.67 GB a layer at 16k tokens) and through
    ``_conv_backward`` once. The bodies stand behind the jitted entries: a
    backward one a cotangent's dtype (float32 of q and k, bfloat16 of v), a
    forward one a dtype and again for the replay, whose partial evaluation
    copies the entry. No pad of a [B, T, H * d] projection is left to XLA."""
    import re

    from benchmarks.lib import checks

    cell, text = request.getfixturevalue(step)
    calls = {entry: len(re.findall(rf"call @{entry}(?:_\d+)?\(", text))
             for entry in ("_conv_forward", "_conv_backward")}
    assert calls == {"_conv_forward": 2 * 3 * layers, "_conv_backward": 3 * layers}
    bodies = checks.count_pallas_kernels(text, ("_conv_fwd_kernel", "_conv_bwd_kernel"))
    assert bodies == {"_conv_fwd_kernel": 4, "_conv_bwd_kernel": 2}
    traffic, kda_cfg = cell["traffic"], cell["config"]["linear_attn_config"]
    channels = kda_cfg["num_heads"] * kda_cfg["head_dim"]
    padded = f"tensor<{traffic['batch']}x{traffic['seq'] + 3}x{channels}xf32>"
    assert padded not in text


def test_kimi_linears_step_holds_its_kernels_and_no_gather_over_the_bound(kimi_linears_step):
    """Every kernel its configuration states, and the held share's rows moved
    a window of tiles at a time, never over the static bound of every
    (token, expert) pair."""
    import re

    from benchmarks.lib import cells, checks

    cell, text = kimi_linears_step
    config, traffic = cell["config"], cell["traffic"]
    stated = cells.stated_kernels(cell)
    counts = checks.count_pallas_kernels(text, stated)
    assert checks.holds_stated_kernels(counts, stated), (counts, stated)
    pairs = traffic["batch"] * traffic["seq"] * config["num_experts_per_token"]
    gathered = [
        int(rows) for rows in re.findall(
            r'"stablehlo\.gather".*\) -> tensor<(?:1x)?(\d+)x', text)
    ]
    width = config["hidden_size"]
    assert f"-> tensor<2048x{width}xbf16>" in text  # a window of sixteen tiles
    assert gathered and max(gathered) < pairs, sorted(set(gathered))


def test_kimi_linears_step_leaves_no_norm_over_a_heads_channels_to_xla(kimi_linears_step):
    """q's and k's L2 norm and o's gated RMSNorm happen on the scan kernels'
    own blocks in every KDA layer or in none: the lowered step reduces no
    [1, 16384, 32, 128] float32 array over a head's channels token by token
    (before the kernels took them: 36, forward, replay and backward of four
    layers; the sums over tokens that are left are the gradients of the
    decay's per-head parameters), and the scan's call sites are eight, a
    forward and a backward a layer: the replay holds none, the remat policy
    keeps o and the states."""
    import re

    from benchmarks.lib import checks

    cell, text = kimi_linears_step
    traffic, kda_cfg = cell["traffic"], cell["config"]["linear_attn_config"]
    rows = "x".join(str(n) for n in (
        traffic["batch"], traffic["seq"], kda_cfg["num_heads"], kda_cfg["head_dim"]))
    reduced = re.findall(
        rf"stablehlo\.reduce.* across dimensions = \[([\d, ]+)\] : \(tensor<{rows}xf32>",
        text)
    assert reduced and all("1" in dims.split(", ") for dims in reduced), reduced
    counts = checks.count_pallas_kernels(text, ("_kda_fwd_kernel", "_kda_bwd_kernel"))
    assert counts == {"_kda_fwd_kernel": 4, "_kda_bwd_kernel": 4}
