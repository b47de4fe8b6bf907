"""Operations and bytes against counts made by hand."""
import pytest

from perfbench.lib import cell, cost

QWEN05 = cell.load("qwen1.5-0.5b.decode_closed").config
QWEN7 = cell.load("qwen2-7b.decode_closed").config


def test_qwen1_5_0_5b_decode_gate_projection():
    # 32 slot rows through wg: K=1024, N=2816
    ops, nbytes = cost.ovp_matmul(32, 1024, 2816)
    assert ops == 184_549_376
    # packed weights 1,441,792 + column scales 11,264 + bf16 rows 65,536
    # + row scales 128 + f32 out 360,448
    assert nbytes == 1_879_168


def test_qwen2_7b_prefill_down_projection():
    # one 128-token chunk through wd: K=18944, N=3584
    ops, nbytes = cost.ovp_matmul(128, 18944, 3584)
    assert ops == 17_381_195_776
    assert nbytes == 33_947_648 + 14_336 + 4_849_664 + 512 + 1_835_008


def test_layer_shapes_follow_the_configurations():
    assert cost.layer_matmuls(QWEN05) == [
        ("wq", 1024, 1024), ("wk", 1024, 1024), ("wv", 1024, 1024),
        ("wo", 1024, 1024), ("wg", 1024, 2816), ("wu", 1024, 2816),
        ("wd", 2816, 1024)]
    assert cost.layer_matmuls(QWEN7)[1] == ("wk", 3584, 512)
    assert len(cost.step_ovp_matmuls(QWEN05, 32)) == 7 * 24
    assert len(cost.step_ovp_matmuls(QWEN7, 128)) == 7 * 7


def test_flops_per_token_counts_weights_head_and_attention():
    # per layer 4 * 1024^2 + 3 * 1024 * 2816 weights, 24 layers, head
    # 1024 * 151936; attention 4 * ctx * 16 heads * 64 * 24 layers
    weights = 24 * (4 * 1024 ** 2 + 3 * 1024 * 2816) + 1024 * 151936
    assert cost.flops_per_token(QWEN05, 500) == 2 * weights + 4 * 500 * 1024 * 24


def test_least_time_takes_the_slower_bound():
    assert cost.least_time_s(10.0, 1.0, 10.0, 10.0) == 1.0
    assert cost.least_time_s(1.0, 20.0, 10.0, 10.0) == 2.0
