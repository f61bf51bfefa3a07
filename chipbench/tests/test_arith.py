"""Parameter counts, FLOPs a token, bytes a decode step and the roofline
arithmetic against hand-worked numbers for both configurations."""
import json
import os

import pytest

from chipbench import arith

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


ERNIE, INTERN = cfg("ernie-4.5-0.3b"), cfg("internlm2-1.8b")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameter_counts():
    # ERNIE: layer 1024*2048 (q) + 2*1024*256 (k, v) + 2048*1024 (o)
    # + 3*1024*3072 (mlp) = 14,155,776; 18 layers = 254,803,968;
    # tied table 103,424*1,024 = 105,906,176; norms 18*2*1024 + 1024
    assert arith.layer_matrix_params(ERNIE) == 14_155_776
    assert arith.num_params(ERNIE) == 254_803_968 + 105_906_176 + 36_864 + 1_024
    assert round(arith.num_params(ERNIE) / 1e9, 2) == 0.36
    # InternLM2: layer 2*2048*2048 + 2*2048*1024 + 3*2048*8192 = 62,914,560;
    # 24 layers = 1,509,949,440; two tables of 92,544*2,048 = 189,530,112
    assert arith.layer_matrix_params(INTERN) == 62_914_560
    assert arith.num_params(INTERN) == 1_509_949_440 + 2 * 189_530_112 \
        + 24 * 2 * 2048 + 2048
    assert round(arith.num_params(INTERN) / 1e9, 2) == 1.89


def test_train_flops_per_token():
    # 6 per matrix parameter; attention 6 * L * heads * head_dim * S (causal,
    # counted once): ERNIE 6*18*16*128*4096 = 0.906e9; InternLM2 1.208e9
    assert arith.train_flops_per_token(ERNIE, 4096) == pytest.approx(
        6 * 360_710_144 + 905_969_664)
    assert arith.train_flops_per_token(ERNIE, 4096) / 1e9 == pytest.approx(3.07, abs=0.005)
    assert arith.train_flops_per_token(INTERN, 4096) == pytest.approx(
        6 * (1_509_949_440 + 189_530_112) + 1_207_959_552)
    assert arith.train_flops_per_token(INTERN, 4096) / 1e9 == pytest.approx(11.40, abs=0.01)


def test_flash_flops_from_shapes():
    # forward: 4 * B * heads * head_dim * S^2 / 2
    assert arith.flash_fwd_flops(ERNIE, 4, 4096) == 4 * 4 * 16 * 128 * 4096 ** 2 / 2
    assert arith.flash_bwd_flops(ERNIE, 4, 4096) == 2.5 * arith.flash_fwd_flops(ERNIE, 4, 4096)
    # all layers, forward and backward, a token: the attention term above
    per_token = 18 * 3.5 * arith.flash_fwd_flops(ERNIE, 1, 4096) / 4096
    assert per_token == pytest.approx(1.75 * 905_969_664 / 1.5, rel=1e-12)


def test_decode_step_bytes_and_least_time():
    # keys and values of one position: 2 * 24 layers * 8 heads * 128 * 2 B
    assert arith.kv_bytes_per_token(INTERN) == 98_304
    ctx = [1000] * 32
    weights = (1_509_949_440 + 189_530_112 + 24 * 2 * 2048 + 2048) * 2
    want = weights + 32 * 2048 * 2 + 98_304 * 32_000
    assert arith.decode_step_bytes(INTERN, ctx) == want
    # 6.54 GB at 819 GB/s = 8.0 ms; its FLOPs (2*1.70e9*32 + 4*24*16*128*32000)
    # take 0.58 ms at 197 TFLOP/s: the step is bound by bytes
    flops = arith.decode_step_flops(INTERN, ctx)
    assert flops == 2 * 1_699_479_552 * 32 + 4 * 24 * 16 * 128 * 32_000
    least = arith.least_seconds(flops, want, V5E)
    assert least == pytest.approx(want / 819e9)
    assert least * 1e3 == pytest.approx(7.99, abs=0.02)


def test_share_of_a_peak_is_never_clipped():
    assert arith.share(1.0, 4.0, "x") == 25.0
    with pytest.raises(ValueError, match="above"):
        arith.share(1.2, 1.0, "kernel.x_roofline")
    with pytest.raises(ValueError):
        arith.share(1.0, 0.0, "x")


def test_a_device_not_in_the_table_is_an_error():
    assert arith.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert arith.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no peaks"):
        arith.load_peaks("cpu")
