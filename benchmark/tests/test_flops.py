"""The FLOP counts against hand-worked numbers for both configurations."""
import json
import os

import pytest

from benchmark import flops

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt3_medium_train_flops_per_token():
    c = cfg("gpt3-medium")
    # per layer 12 h^2 = 12 * 1024^2 = 12,582,912; 24 layers = 301,989,888
    # head 50304 * 1024 = 51,511,296            -> N = 353,501,184
    assert flops.matmul_params(c) == 353_501_184
    # 6 N = 2,121,007,104; causal attention, forward + backward:
    # 3 * 4 * 24 * 1024 * (2048 / 2) = 301,989,888
    assert flops.train_flops_per_token(c, 2048) == pytest.approx(
        2_121_007_104 + 301_989_888)


def test_bert_base_train_flops_per_token():
    c = cfg("bert-base")
    # per layer 12 * 768^2 = 7,077,888; 12 layers = 84,934,656
    # head 30522 * 768 = 23,440,896; MLM transform 768^2 = 589,824
    assert flops.matmul_params(c) == 84_934_656 + 23_440_896 + 589_824
    # bidirectional attention: 3 * 4 * 12 * 768 * 512 = 56,623,104
    assert flops.train_flops_per_token(c, 512) == pytest.approx(
        6 * 108_965_376 + 56_623_104)


def test_flash_step_work_gpt():
    c = cfg("gpt3-medium")
    f, b = flops.flash_step_work(c, 4, 2048)
    # one product: 2 * b * s * keys * h = 2 * 4 * 2048 * 1024 * 1024
    one = 2 * 4 * 2048 * 1024 * 1024
    assert f == 24 * 6 * one
    # 12 tensors of b * s * h bf16 values per layer
    assert b == 24 * 12 * 4 * 2048 * 1024 * 2


def test_serve_flops_one_request():
    c = cfg("gpt3-medium")
    body = 24 * 12 * 1024 * 1024
    # prompt 3, 2 new tokens: positions 0..3 are fed (4), keys 1+2+3+4 = 10
    want = 2 * body * 4 + 4 * 24 * 1024 * 10 + 2 * 50304 * 1024 * 2
    assert flops.serve_flops(c, 3, 2) == want


def test_paged_decode_work():
    c = cfg("gpt3-medium")
    # 1000 cached positions: a K and a V row of 16 x 64 bf16 values each in
    # 24 layers; QK^T and PV are 2 x 2 x 64 FLOPs a head and position
    f, b = flops.paged_decode_work(c, 1000, 2)
    assert b == 24 * 1000 * 2 * 1024 * 2
    assert f == 24 * 1000 * 16 * 4 * 64
    assert f / b == 1.0            # 1 FLOP a byte in bf16: bound by bytes
    assert flops.paged_decode_work(c, 1000, 4) == (f, 2 * b)
    assert flops.paged_decode_work(c, 0, 2) == (0.0, 0.0)
