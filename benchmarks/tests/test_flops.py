"""The required-FLOPs functions against a count made by hand."""
import pytest

from benchmarks.lib import cells, flops


def config(name):
    return cells.load_json(f"{cells.BENCH_DIR}/configs/{name}.json")


def test_mistral_7b_l4_required_flops_match_the_hand_count():
    # One layer: q 4096x4096, k and v 4096x1024 each, o 4096x4096, and three
    # 4096x14336 MLP matrices; the head is 4096x32768. No embedding gather.
    attn = 4096 * 4096 * 2 + 4096 * 1024 * 2
    assert attn == 41_943_040
    layer = attn + 3 * 4096 * 14336
    assert layer == 218_103_808
    matmul = 4 * layer + 4096 * 32768
    assert matmul == 1_006_632_960
    cfg = config("mistral-7b-l4")
    for seq, gflop in ((2048, 6.241), (16384, 7.650), (512, 6.090)):
        want = 6 * matmul + 6 * 4 * seq * 4096  # causal half of attention
        assert flops.dense_decoder(cfg, seq) == want
        assert want / 1e9 == pytest.approx(gflop, abs=5e-4)


def test_mixtral_8x7b_l2_required_flops_match_the_hand_count():
    # One layer: the same attention, a 4096x8 router and the 2 experts a
    # token is sent to (not the 8 it could be); the tied head is 4096x32000.
    layer = 41_943_040 + 4096 * 8 + 2 * 3 * 4096 * 14336
    assert layer == 394_297_344
    matmul = 2 * layer + 4096 * 32000
    want = 6 * matmul + 6 * 2 * 4096 * 4096
    assert flops.moe_decoder(config("mixtral-8x7b-l2"), 4096) == want
    assert want / 1e9 == pytest.approx(5.719, abs=5e-4)


def test_flash_call_counts_the_causal_half_and_each_operand_once():
    # 32 heads, one 16,384-token sequence, head 128: the forward's two
    # matmuls over half of the [T, T] pairs.
    f, b = flops.flash_call("_fwd_kernel", 32, 16384, 16384, 128, causal=True)
    assert f == 2 * 2 * 32 * (16384 * 16384 / 2) * 128
    assert b == 32 * 4 * 16384 * 128 * 2 + 32 * 16384 * 4
    full, _ = flops.flash_call("_fwd_kernel", 32, 16384, 16384, 128, causal=False)
    assert full == 2 * f
    dkv, _ = flops.flash_call("_bwd_dkv_kernel", 32, 16384, 16384, 128, True)
    dq, _ = flops.flash_call("_bwd_dq_kernel", 32, 16384, 16384, 128, True)
    assert (dkv, dq) == (2 * f, 1.5 * f)


@pytest.mark.parametrize("kernel, qk_matmuls, v_matmuls, qk_rows, v_rows", [
    # scores q k^T contract q/k's dim and p v produces v's; q, k in at one
    # width and v in, o out at the other
    ("_fwd_kernel", 1, 1, 4096 + 4096, 4096 + 4096),
    # scores again and dK = dS^T q; dP = do v^T and dV = p^T do; q, k in and
    # dk out; v, do in and dv out
    ("_bwd_dkv_kernel", 2, 2, 4096 + 2 * 4096, 2 * 4096 + 4096),
    # scores again and dQ = dS k; dP; q, k in and dq out; v, do in
    ("_bwd_dq_kernel", 2, 1, 2 * 4096 + 4096, 4096 + 4096),
])
def test_flash_call_takes_the_two_head_dims_apart(kernel, qk_matmuls, v_matmuls,
                                                  qk_rows, v_rows):
    # 64 (batch x head) blocks of 4096 x 4096, q and k of 192, v of 128
    f, b = flops.flash_call(kernel, 64, 4096, 4096, 192, True, d_v=128)
    pairs = 4096 * 4096 // 2
    assert pairs == 8_388_608
    assert f == 2 * 64 * pairs * (qk_matmuls * 192 + v_matmuls * 128)
    assert b == 64 * 2 * (qk_rows * 192 + v_rows * 128) + 64 * 4096 * 4
    # where the two are equal it is the count with one width, as before
    for d in (64, 128):
        one = flops.flash_call(kernel, 64, 4096, 4096, d, True)
        assert one == flops.flash_call(kernel, 64, 4096, 4096, d, True, d_v=d)
        assert one[0] == (qk_matmuls + v_matmuls) * 2 * 64 * pairs * d
        assert one[1] == 64 * 2 * (qk_rows + v_rows) * d + 64 * 4096 * 4


def test_the_forward_at_192_and_128_by_hand():
    f, b = flops.flash_call("_fwd_kernel", 64, 4096, 4096, 192, True, d_v=128)
    assert (f, b) == (343_597_383_680.0, 336_592_896.0)


def test_an_unknown_device_kind_is_an_error():
    from benchmarks.lib.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9")
