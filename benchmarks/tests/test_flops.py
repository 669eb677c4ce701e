"""``lib/flops.py`` against counts made by hand."""

import json
import os

import pytest

from benchmarks.lib import flops

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_starcoder2_layer_and_model_params():
    cfg = _config("starcoder2-3b-l4")
    # wq 3072x3072, wk and wv 3072x256 (2 kv heads of 128), wo 3072x3072,
    # MLP 3072x12288 twice
    by_hand = 3072 * 3072 * 2 + 2 * 3072 * 256 + 2 * 3072 * 12288
    assert flops.lm_layer_matmul_params(cfg) == by_hand == 95_944_704
    assert flops.lm_matmul_params(cfg) == 4 * by_hand + 3072 * 49152
    assert flops.lm_matmul_params(cfg) == 534_773_760


@pytest.mark.parametrize("t,window,want", [
    (4, None, 2.5),                      # 1+2+3+4 over 4
    (4, 2, 1.75),                        # 1+2+2+2 over 4
    (8192, 4096, 3072.25),               # the train cell: 2 x window
    (2048, 4096, 1024.5),                # shorter than the window: plain causal
])
def test_avg_keys(t, window, want):
    assert flops.avg_keys_per_query(t, window) == pytest.approx(want)
    brute = sum(min(q + 1, window or t) for q in range(t)) / t
    assert flops.avg_keys_per_query(t, window) == pytest.approx(brute)


def test_lm_train_flops_with_and_without_window():
    cfg = _config("starcoder2-3b-l4")
    weights = 6 * 534_773_760
    with_window = weights + 12 * 4 * 3072 * 3072.25
    assert flops.lm_train_flops_per_token(cfg, 8192) == pytest.approx(with_window)
    assert with_window / 1e9 == pytest.approx(3.6617, abs=1e-4)
    no_window = dict(cfg, sliding_window=None)
    assert flops.lm_train_flops_per_token(no_window, 8192) == pytest.approx(
        weights + 12 * 4 * 3072 * 4096.5)


def test_flash_kernel_cost():
    # 1 x 8192 tokens, 24 heads of 128, window 4096: 24*8192*3072.25 pairs
    pairs = 24 * 8192 * 3072.25
    f, b = flops.flash_kernel_cost("fwd", batch=1, heads=24, t=8192,
                                   head_dim=128, window=4096)
    assert f == pytest.approx(2 * 2 * 128 * pairs)
    assert b == 4 * 8192 * 24 * 128 * 2 + 24 * 8192 * 4
    f, _ = flops.flash_kernel_cost("dkdv", batch=1, heads=24, t=8192,
                                   head_dim=128, window=4096)
    assert f == pytest.approx(4 * 2 * 128 * pairs)
    f, _ = flops.flash_kernel_cost("dq", batch=1, heads=24, t=8192,
                                   head_dim=128, window=4096)
    assert f == pytest.approx(3 * 2 * 128 * pairs)


def test_resnet18_layer_by_layer():
    cfg = _config("resnet18-cifar10")
    layers = dict(flops.resnet_conv_layers(cfg))
    hand = {
        "stem": 32 * 32 * 64 * 3 * 9,
        "s0b0_c1": 32 * 32 * 64 * 64 * 9, "s0b0_c2": 32 * 32 * 64 * 64 * 9,
        "s0b1_c1": 32 * 32 * 64 * 64 * 9, "s0b1_c2": 32 * 32 * 64 * 64 * 9,
        "s1b0_c1": 16 * 16 * 128 * 64 * 9, "s1b0_c2": 16 * 16 * 128 * 128 * 9,
        "s1b0_proj": 16 * 16 * 128 * 64,
        "s1b1_c1": 16 * 16 * 128 * 128 * 9, "s1b1_c2": 16 * 16 * 128 * 128 * 9,
        "s2b0_c1": 8 * 8 * 256 * 128 * 9, "s2b0_c2": 8 * 8 * 256 * 256 * 9,
        "s2b0_proj": 8 * 8 * 256 * 128,
        "s2b1_c1": 8 * 8 * 256 * 256 * 9, "s2b1_c2": 8 * 8 * 256 * 256 * 9,
        "s3b0_c1": 4 * 4 * 512 * 256 * 9, "s3b0_c2": 4 * 4 * 512 * 512 * 9,
        "s3b0_proj": 4 * 4 * 512 * 256,
        "s3b1_c1": 4 * 4 * 512 * 512 * 9, "s3b1_c2": 4 * 4 * 512 * 512 * 9,
        "out": 512 * 10,
    }
    assert layers == hand
    macs = sum(hand.values())
    assert macs == 555_422_720
    assert flops.resnet_train_flops_per_sample(cfg) == 2 * (3 * macs - hand["stem"])
    assert flops.resnet_params(cfg) == 11_176_970


def test_roofline_bound():
    pk = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000.0, 10.0, pk) == (10.0, "compute")
    assert flops.roofline_seconds(10.0, 1000.0, pk) == (100.0, "memory")
