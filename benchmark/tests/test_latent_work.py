"""The deepseek_v3 family's counts against hand-worked numbers for
``kanana-2-30b-a3b`` (7 layers: one dense, six of experts), and the runner
that recounts a window's FLOPs with them."""
import json
import os
import types

import pytest

from benchmark import harness, latent_work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "kanana-2-30b-a3b.json")) as f:
        return json.load(f)


# attention of a layer: q 2048 x 32 x 192 = 12,582,912; kva 2048 x 576 =
# 1,179,648; kvb 512 x 32 x 256 = 4,194,304; o 4096 x 2048 = 8,388,608
ATTN = 26_345_472
DENSE = 3 * 2048 * 6144                      # 37,748,736
EXPERT = 3 * 2048 * 768                      # 4,718,592
ROUTER = 2048 * 128                          # 262,144
HEAD = 2048 * 128256                         # 262,668,288


def test_resident_and_expert_parameters(cfg):
    assert latent_work.expert_params(cfg) == EXPERT
    assert latent_work.resident_params(cfg) == (
        7 * ATTN + DENSE + 6 * (2 * EXPERT + ROUTER) + HEAD) == 543_031_296


def test_decode_step_bytes(cfg):
    # 600 experts hit, 60,000 live rows, bf16: 2 x (543,031,296 + 600 x
    # 4,718,592) + 7 x 60,000 x 576 x 2
    assert latent_work.decode_step_bytes(cfg, 600, 60_000, 2) == \
        7_232_212_992


def test_mla_decode_work(cfg):
    flops, nbytes = latent_work.mla_decode_work(cfg, 60_000, 2)
    assert nbytes == 7 * 60_000 * 576 * 2 == 483_840_000
    # a head and row: 576 multiply-adds of score, 512 of value
    assert flops == 2 * 7 * 60_000 * 32 * (576 + 512) == 29_245_440_000
    # bytes-bound on a v5e: 60 FLOPs a byte against a ridge of 240
    assert flops / nbytes < 197e12 / 819e9


def test_family_serve_flops_counts_active_parameters_only(cfg):
    family = harness.load_module("models", "deepseek_v3")
    active = 7 * ATTN + DENSE + 6 * (ROUTER + 8 * EXPERT)
    assert family.active_body_params(cfg) == active == 450_232_320
    # prompt 100, 3 tokens: 102 positions through the body; prompt keys
    # 5050 at 192 + 128 a head, decoded keys 101 + 102 at 576 + 512;
    # the head three times
    assert family.serve_flops(cfg, 100, 3) == pytest.approx(
        2 * active * 102 + 2 * 7 * 32 * (5050 * 320 + 203 * 1088)
        + 2 * HEAD * 3)
    assert family.serve_flops(cfg, 100, 3) == pytest.approx(94_246_318_080)
    # GPT's formula would count every expert layer as a dense MLP of 6144
    from benchmark import flops
    assert flops.serve_flops(cfg, 100, 3) != family.serve_flops(cfg, 100, 3)


def test_window_flops_is_the_window_work_less_the_ramp():
    runner = harness.load_module("runners", "serve_family_flops")
    reqs = [types.SimpleNamespace(prompt=[0] * 10),
            types.SimpleNamespace(prompt=[0] * 20),
            types.SimpleNamespace(prompt=[0] * 30)]
    served = {"token_times": [[-1.0, 0.5, 1.5, 2.5],      # ramp: 1 before
                              [0.2, 0.9],                  # all in window
                              [-1.0]]}                     # nothing new

    def count(cfg, prompt_len, new):
        return 1000.0 * prompt_len + new

    got = runner.window_flops(count, None, reqs, served, 2.0)
    # request 0: 3 tokens by 2.0 s less the 1 before; request 1: 2 tokens
    assert got == (10_003 - 10_001) + 20_002
