"""benchmark/work.py against counts made by hand."""
import json
import os

import pytest

from bench_util import ROOT

from benchmark import work


def test_lm_train_flops_per_token_of_the_l8_configuration():
    # 6 x (8 x (4 x 2048^2 + 2 x 2048 x 8192) + 2048 x 50257) matmul
    # FLOPs + 6 x 8 x 1024 x 2048 of causal attention
    by_hand = 6 * (8 * (4 * 2048 ** 2 + 2 * 2048 * 8192) + 2048 * 50257) \
        + 6 * 8 * 1024 * 2048
    got = work.gpt2_train_flops_per_token(8, 2048, 8192, 50257, 1024)
    assert got == by_hand
    assert got / 1e9 == pytest.approx(3.134, abs=1e-3)


def test_parameter_counts():
    assert work.gpt2_param_count(8, 2048, 8192, 50257, 2048) == 612_967_505
    full = work.gpt2_param_count(24, 2048, 8192, 50257, 2048)
    assert full / 1e9 == pytest.approx(1.4187, abs=1e-4)
    tied = work.gpt2_param_count(24, 2048, 8192, 50257, 2048, tied_head=True)
    assert tied / 1e9 == pytest.approx(1.3157, abs=1e-4)


def test_paged_kernel_bytes_for_a_known_block_table():
    # two slots at contexts 17 and 32, 16-token pages, 16 heads of 128,
    # bf16: both read 2 pages of K and of V a head, whole
    page = 16 * 128 * 2
    got = work.paged_attention_step_work([17, 32], 16, 128, 16)
    qo = 2 * 16 * 128 * 2
    assert got["bytes"] == 2 * (2 * 2 * page * 16 + qo)
    assert got["flops"] == 4 * 16 * 128 * (17 + 32)
    one = work.paged_attention_step_work([16], 16, 128, 16)
    assert one["bytes"] == 2 * 1 * page * 16 + qo


def test_flash_attention_work_is_causal_halved():
    got = work.flash_attention_train_work(8, 16, 1024, 128)
    assert got["flops"] == 14 * 8 * 16 * 1024 * 1024 * 128 / 2
    assert got["bytes"] == 12 * 8 * 16 * 1024 * 128 * 2


def test_resnet50_multiply_adds():
    macs = work.resnet_forward_macs([3, 4, 6, 3],
                                    [64, 256, 512, 1024, 2048], 224, 1000)
    # stem by hand: 112 x 112 outputs x 64 filters x 3 x 7 x 7
    assert macs > 112 * 112 * 64 * 147
    assert macs / 1e9 == pytest.approx(4.09, abs=0.01)


def test_decode_and_prefill_flops():
    n_mat = 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192) + 2048 * 50257
    assert work.gpt2_decode_flops(24, 2048, 8192, 50257, 300) == \
        2 * n_mat + 4 * 24 * 300 * 2048
    body = n_mat - 2048 * 50257
    assert work.gpt2_prefill_flops(24, 2048, 8192, 50257, 256) == \
        2 * body * 256 + 2 * 2048 * 50257 + 2 * 24 * 2048 * 256 * 256


def test_roofline_names_the_binding_limit():
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    t, bound = work.roofline_seconds({"flops": 197e12, "bytes": 1.0}, peak)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = work.roofline_seconds({"flops": 1.0, "bytes": 819e9}, peak)
    assert bound == "memory" and t == pytest.approx(1.0)
