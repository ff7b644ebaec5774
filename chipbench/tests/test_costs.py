"""The FLOP and byte functions against values worked by hand at the
published widths."""
import json
import os

import pytest

from chipbench import costs
from chipbench.peaks import peaks_for

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_smollm2_parameters_and_train_flops():
    c = cfg("smollm2-1.7b-train-d6")
    # 4 x 2048^2 attention + 3 x 2048 x 8192 MLP
    assert costs.layer_matmul_params(c) == 4 * 2048 ** 2 + 3 * 2048 * 8192 \
        == 67_108_864
    assert costs.matmul_params(c) == 6 * 67_108_864 + 2048 * 49152 \
        == 503_316_480
    # tied: one embedding; 13 norm vectors
    assert costs.total_params(c) == 503_316_480 + 13 * 2048
    # 6 per weight + 6 x layers x seq x hidden for causal attention
    assert costs.train_flops_per_token(c, 2048) == \
        6 * 503_316_480 + 6 * 6 * 2048 * 2048 == 3_170_893_824
    # PR 23 read 16,059 tokens/s and an MFU of 25.87% on this chip
    assert 100 * 16059 * 3_170_893_824 / 197e12 == pytest.approx(25.85, 0.01)


def test_mistral_parameters_and_decode_bytes():
    c = cfg("mistral-7b-v0.3-serve-d16")
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert costs.layer_matmul_params(c) == layer == 218_103_808
    assert costs.matmul_params(c) == 16 * layer + 4096 * 32768
    assert costs.total_params(c) == 16 * layer + 2 * 4096 * 32768 \
        + 33 * 4096
    # K and V of one token: 2 x 16 layers x 8 heads x 128 x 2 bytes = 64 KiB
    assert costs.kv_bytes_per_token(c) == 65_536
    # 4,096 pages of 16 tokens: 4.29 GB
    assert 4096 * 16 * costs.kv_bytes_per_token(c) == 4_294_967_296
    w = (16 * layer + 4096 * 32768) * 2
    assert costs.decode_step_bytes(c, 0) == w == 7_247_757_312
    assert costs.decode_step_bytes(c, 1000) == w + 65_536_000
    # read at 819 GB/s that is 8.85 ms a step
    assert w / 819e9 == pytest.approx(8.85e-3, 0.01)


def test_flash_costs_and_roofline():
    c = cfg("smollm2-1.7b-train-d6")
    flops, nbytes = costs.flash_fwd_cost(c, 4, 2048)
    # two products over the lower triangle: 2 x 2 x 4 x 32 x 64 x 2048^2 / 2
    assert flops == 2 * 4 * 32 * 64 * 2048 ** 2 == 68_719_476_736
    # Q, K, V, O in bf16 and one fp32 row of logsumexp per head
    assert nbytes == 4 * (4 * 2048 * 2048 * 2) + 4 * 32 * 2048 * 4 \
        == 135_266_304
    least, bound = costs.roofline_seconds(flops, nbytes,
                                          peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(348.8e-6, 0.01)
    bflops, bbytes = costs.flash_bwd_cost(c, 4, 2048)
    assert bflops == 2.5 * flops
    assert bbytes == 8 * (4 * 2048 * 2048 * 2) + 2 * 4 * 32 * 2048 * 4


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError):
        peaks_for("TPU v9 imaginary")
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
