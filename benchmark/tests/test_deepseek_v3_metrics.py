"""The per-layer readers of the ``deepseek_v3`` cell on made-up spans: what
they compute, and that they read NOTHING (``None``, never 0) from a run
without a capture, without the markers, or of another family."""
import json
import os
import types

import pytest

from benchmark import harness, hostspans, latent_work
from benchmark.hostspans import Span
from benchmark.peaks import PEAKS

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = PEAKS["TPU v5e"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "kanana-2-30b-a3b.json")) as f:
        return json.load(f)


def reader(name):
    return harness.load_module("layer_metrics", name)


def decode_span(start, pages_live, hit, tokens_max, with_marker=True):
    s = Span("serving.decode", start, start + 20e6, 0,
             {"pages_live": pages_live, "live": 32})
    if with_marker:
        m = Span("serving.experts", start + 19e6, start + 19e6 + 10, 0,
                 {"experts_hit": hit, "expert_tokens_max": tokens_max,
                  "rows": 32, "layers": 6}, parent=s)
        s.children.append(m)
    return s


def flat(spans):
    out = []
    for s in spans:
        out.append(s)
        out.extend(s.descendants())
    return out


def run_of(cfg, trace=None):
    return {"trace": trace, "peak": PEAK, "chips": 1, "cfg": cfg,
            "traffic": {"engine": {"page_size": 16, "dtype": "bfloat16"}}}


@pytest.fixture
def capture(monkeypatch):
    """A made-up capture: two decode steps, the device busy 18 ms in each."""
    spans = [decode_span(0.0, 3800, 600, 7), decode_span(30e6, 3900, 580, 5)]
    profile = object()
    monkeypatch.setattr(hostspans, "load_current", lambda: profile)
    monkeypatch.setattr(hostspans, "host_spans", lambda p: flat(spans))
    monkeypatch.setattr(hostspans, "busy_seconds_inside",
                        lambda p, which: [0.018] * len(which))
    return spans


def test_decode_hbm_roofline(cfg, capture):
    need = (latent_work.decode_step_bytes(cfg, 600, 3800 * 16, 2)
            + latent_work.decode_step_bytes(cfg, 580, 3900 * 16, 2))
    want = 100.0 * need / 819e9 / 0.036
    assert reader("decode_hbm_roofline.serve").read(run_of(cfg)) == \
        pytest.approx(want)
    assert 20.0 < want < 100.0


def test_moe_expert_imbalance(cfg, capture):
    # mean load 32 x 6 / 128 = 1.5: ratios 7 / 1.5 and 5 / 1.5, median
    assert reader("moe_expert_imbalance.serve").read(run_of(cfg)) == \
        pytest.approx((7 / 1.5 + 5 / 1.5) / 2)


def test_mla_decode_roofline(cfg, capture):
    trace = {"op_seconds": {"%mla_paged_decode.3 = bf16[32,32,512]": 0.004,
                            "%fusion.1 = bf16[32,2048]": 1.0}}
    _flops, nbytes = latent_work.mla_decode_work(cfg, 7700 * 16, 2)
    assert reader("mla_decode_roofline.serve").read(
        run_of(cfg, trace)) == pytest.approx(100.0 * nbytes / 819e9 / 0.004)


@pytest.mark.parametrize("name", ["decode_hbm_roofline.serve",
                                  "moe_expert_imbalance.serve",
                                  "mla_decode_roofline.serve"])
def test_nothing_to_read_is_none(cfg, name, monkeypatch):
    read = reader(name).read
    trace = {"op_seconds": {"%fusion.1 = bf16[32,2048]": 1.0}}
    # no capture at all
    monkeypatch.setattr(hostspans, "load_current", lambda: None)
    assert read(run_of(cfg, trace)) is None
    # a capture of a program without the markers and without the kernel
    # (the parent's program, or another family's)
    spans = [decode_span(0.0, 1000, 0, 0, with_marker=False)]
    monkeypatch.setattr(hostspans, "load_current", lambda: object())
    monkeypatch.setattr(hostspans, "host_spans", lambda p: flat(spans))
    monkeypatch.setattr(hostspans, "busy_seconds_inside",
                        lambda p, which: [0.01] * len(which))
    assert read(run_of(cfg, trace)) is None
    gpt = {"family": "gpt", "hidden_size": 1024, "num_hidden_layers": 24}
    assert read(run_of(gpt, trace)) is None
    assert read(dict(run_of(cfg, None), peak=None)) is None
