"""``grouped_decode_roofline.serve`` on made-up spans, programs and device
events: the kernel's operations inside the programs a decode span
launched are its time, the span's ``pages_live`` its bytes; a program
launched elsewhere, another kernel, a span whose programs the capture lost
and a family without grouped K/V pages add nothing."""
import json
import os

import pytest

from benchmark import harness, hostspans, launches, xplane
from benchmark.hostspans import Span
from benchmark.peaks import PEAKS

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = PEAKS["TPU v5e"]
READ = harness.load_module("layer_metrics", "grouped_decode_roofline.serve")


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def run_of(cfg):
    return {"trace": None, "peak": PEAK, "chips": 1, "cfg": cfg,
            "traffic": {"engine": {"page_size": 16, "dtype": "bfloat16"}}}


def plant(monkeypatch, spans, modules, events):
    monkeypatch.setattr(hostspans, "load_current", lambda: object())
    monkeypatch.setattr(hostspans, "host_spans", lambda p: spans)
    monkeypatch.setattr(launches, "modules", lambda p: modules)
    monkeypatch.setattr(hostspans, "_device_plane",
                        lambda p: type("P", (), {"name": "/device:TPU:0"}))
    monkeypatch.setattr(xplane, "device_events",
                        lambda p: {"/device:TPU:0": events})


def decode(start, pages_live):
    return Span("serving.decode", start, start + 40e6, 0,
                {"live": 32, "pages_live": pages_live, "kernel": True})


KERNEL = ("%grouped_paged_decode.1 = bf16[32,28,128]{2,1,0} "
          "custom-call(bf16[32,28,512] %q, bf16[32769,16,512] %k)")
OTHER = "%paged_decode.3 = bf16[32,1,1024]{2,1,0} custom-call(%a)"
GATHER = "%fusion.12 = bf16[32768,16,512]{2,1,0} fusion(%b)"


@pytest.mark.parametrize("name,layers", [
    ("smallthinker-21b-a3b-instruct", 2), ("granite-4.0-h-small", 1)])
def test_kernel_time_inside_the_spans_programs(name, layers, monkeypatch):
    """Two decode spans each launch one pass holding two kernel calls; a
    third span's pass is not in the capture (a cut one): its pages do not
    count.  A pass launched outside every span, the other paged kernel and
    a gather add no time."""
    cfg = config(name)
    spans = [decode(0.0, 4000), decode(100e6, 4100), decode(200e6, 9000),
             Span("serving.prefill", 300e6, 340e6, 0, {"tokens": 900})]
    modules = [launches.Module(10e6, 40e6, 5e6, "run_id", spans[0]),
               launches.Module(110e6, 140e6, 105e6, "run_id", spans[1]),
               launches.Module(310e6, 330e6, 305e6, "run_id", spans[3]),
               launches.Module(400e6, 430e6, 350e6, "run_id", None)]
    events = [(KERNEL, 12e6, 13e6), (GATHER, 14e6, 20e6),
              (KERNEL, 21e6, 22.5e6), (KERNEL, 111e6, 112e6),
              (OTHER, 115e6, 118e6), (KERNEL, 120e6, 121e6),
              (KERNEL, 315e6, 316e6), (KERNEL, 405e6, 409e6)]
    plant(monkeypatch, spans, modules, events)
    row = 2 * cfg["num_key_value_heads"] * 128 * 2    # K and V, bf16
    need = layers * (4000 + 4100) * 16 * row
    want = 100.0 * need / PEAK.hbm_bytes_s / 4.5e-3
    assert READ.read(run_of(cfg)) == pytest.approx(want)


def test_nothing_to_read_is_none(monkeypatch):
    cfg = config("smallthinker-21b-a3b-instruct")
    monkeypatch.setattr(hostspans, "load_current", lambda: None)
    assert READ.read(run_of(cfg)) is None           # no capture at all
    spans = [decode(0.0, 4000)]
    modules = [launches.Module(10e6, 40e6, 5e6, "run_id", spans[0])]
    # the parent's program: the table-width gather, no kernel
    plant(monkeypatch, spans, modules, [(GATHER, 12e6, 30e6)])
    assert READ.read(run_of(cfg)) is None
    plant(monkeypatch, spans, modules, [(KERNEL, 12e6, 13e6)])
    assert READ.read(run_of(cfg)) > 0.0
    assert READ.read(dict(run_of(cfg), peak=None)) is None
    assert READ.read(run_of(config("sdar-30b-a3b-chat"))) is None
    assert READ.read(run_of(config("gpt3-medium"))) is None
