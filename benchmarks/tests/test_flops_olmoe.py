"""OLMoE's required FLOPs and the grouped matmul's count, by hand."""
import pytest

from benchmarks.lib import cells
from benchmarks.lib.flops_gmm import gmm_call
from benchmarks.lib.flops_olmoe import olmoe_decoder


def test_olmoe_1b_7b_required_flops_match_the_hand_count():
    cell = cells.load_cell("olmoe-1b-7b-1chip.dropless-4k")
    cfg = cell["config"]
    assert cells.resolve(cfg["required_flops"]) is olmoe_decoder
    # One layer: q, k, v, o of 2048x2048 (16 heads of 128, 16 KV heads), a
    # 2048x64 router and the 8 experts a token is sent to (not the 64 it
    # could be), three 2048x1024 matrices each. QK-norm has no matmul.
    layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert layer == 67_239_936
    head = 2048 * 50304  # untied; the embedding gather counts for nothing
    want = 6 * (3 * layer + head) + 6 * 3 * 4096 * 2048  # causal half of attention
    assert olmoe_decoder(cfg, cell["traffic"]["seq"]) == want
    assert want / 1e9 == pytest.approx(1.979, abs=5e-4)
    # what the cell's `why` says: the head 31% and the experts with their
    # router 46% at 3 layers; the head 8% in the 16-layer model
    experts = 6 * 3 * (2048 * 64 + 8 * 3 * 2048 * 1024)
    assert 6 * head / want == pytest.approx(0.31, abs=0.005)
    assert experts / want == pytest.approx(0.46, abs=0.005)
    full = olmoe_decoder({**cfg, "num_hidden_layers": 16}, 4096)
    assert 6 * head / full == pytest.approx(0.08, abs=0.005)


@pytest.mark.parametrize("kernel", ["_gmm_kernel", "_tgmm_kernel"])
def test_gmm_call_counts_the_rows_that_hold_a_pair_and_each_operand_once(kernel):
    # The cell's layer: 2 x 4096 tokens x top-8 = 65,536 rows (the padded
    # layout has 73,728), 2048 x 1024 matrices, 64 experts, bfloat16.
    flops, nbytes = gmm_call(kernel, 65536, 2048, 1024, 64)
    assert flops == 2 * 65536 * 2048 * 1024 == 274_877_906_944
    assert nbytes == 2 * (65536 * 2048 + 65536 * 1024 + 64 * 2048 * 1024)
    # the down projection and the gradients swap the two widths: same count
    assert gmm_call(kernel, 65536, 1024, 2048, 64) == (flops, nbytes)
    # the floor on a v5e is the MXU's: 1.40 ms against 0.82 ms of HBM traffic
    assert flops / 197e12 == pytest.approx(1.395e-3, rel=1e-3)
    assert nbytes / 819e9 == pytest.approx(0.819e-3, rel=1e-2)


def test_olmoe_states_its_kernels_counts_and_one_calls_need():
    from benchmarks.lib.flops import flash_call
    from benchmarks.lib.kernels_olmoe import olmoe_decoder as kernels

    cell = cells.load_cell("olmoe-1b-7b-1chip.dropless-4k")
    assert cells.resolve(cell["config"]["kernels"]) is kernels
    stated = cells.stated_kernels(cell)
    # three layers: the flash kernels once each, the three expert matmuls
    # and their inputs' gradients through _gmm_kernel, the weights' through
    # _tgmm_kernel; a kept replay of the forward is not asked for
    assert {k: s["least"] for k, s in stated.items()} == {
        "_fwd_kernel": 3, "_bwd_dkv_kernel": 3, "_bwd_dq_kernel": 3,
        "_gmm_kernel": 18, "_tgmm_kernel": 9}
    for kernel in ("_gmm_kernel", "_tgmm_kernel"):
        assert stated[kernel]["call"] == gmm_call(kernel, 65536, 2048, 1024, 64)
    # 2 sequences x 16 heads of 128 over the whole 4,096-token context
    for kernel in ("_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel"):
        assert stated[kernel]["call"] == flash_call(kernel, 32, 4096, 4096, 128, True)
    # a mesh divides what one device's call takes
    split = kernels(cell["config"], {**cell["traffic"], "mesh": {"seq": 2, "data": 2}})
    assert split["_fwd_kernel"]["call"] == flash_call(
        "_fwd_kernel", 16, 2048, 2048, 128, True)


def test_gmm_call_knows_only_the_grouped_matmul():
    with pytest.raises(KeyError):
        gmm_call("_fwd_kernel", 1, 1, 1, 1)
