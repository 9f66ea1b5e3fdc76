"""The ``smallthinker`` cell: ``smallthinker_work``'s counts against
hand-worked numbers for ``smallthinker-21b-a3b-instruct`` as it is cut
(eight layers, everything else whole), the family's FLOPs, the two window
readers on made-up spans and device events, and whole rehearsal runs: a
sound one comes out correct; one whose served token is altered does not,
nor does one whose window layers attend past their window (a fault planted
in the program by ``tools/plant_window_fault.py``)."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, hostspans, launches, run, smallthinker_work
from benchmark import xplane
from benchmark.hostspans import Span
from benchmark.peaks import PEAKS

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = PEAKS["TPU v5e"]
CELL = "smallthinker21b_serve_longdoc"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs",
                           "smallthinker-21b-a3b-instruct.json")) as f:
        return json.load(f)


# q 2560 x 3584 and o 3584 x 2560, k and v 2560 x 512 each
ATTN = 2 * 2560 * 3584 + 2 * 2560 * 512          # 20,971,520
ROUTER = 2560 * 64                               # 163,840
EXPERT = 3 * 2560 * 768                          # 5,898,240
EMBED = 151936 * 2560                            # 388,956,160
LAYER = ATTN + 2 * 2560 + ROUTER + 64 * EXPERT   # 398,627,840


def test_the_published_widths_are_in_the_file(cfg):
    want = {"hidden_size": 2560, "num_attention_heads": 28,
            "num_key_value_heads": 4, "head_dim": 128,
            "moe_num_primary_experts": 64, "moe_ffn_hidden_size": 768,
            "moe_num_active_primary_experts": 6, "vocab_size": 151936,
            "rope_theta": 1500000, "rms_norm_eps": 1e-6,
            "sliding_window_size": 4096, "max_position_embeddings": 16384,
            "num_hidden_layers": 8}
    assert {k: cfg[k] for k in want} == want
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == \
        [0, 1, 1, 1] * 13
    assert cfg["published"] == {"num_hidden_layers": 52}
    for key in ("window_boundary", "router_input", "secondary_experts",
                "attention_bias", "initializer_range"):
        assert key in cfg["assumed"]
    rehearsal = cfg["rehearsal"]
    # the CPU rehearsal's sequences pass its window
    mix = harness.load_json("traffic",
                            "longdoc_saturated_p256-14336_o512-1536.json")
    assert rehearsal["sliding_window_size"] < (
        mix["rehearsal"]["prompt_len"]["hi"]
        + mix["rehearsal"]["output_len"]["hi"])
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "smallthinker-21b-a3b-instruct")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]


def test_parameters_held(cfg):
    assert smallthinker_work.attention_params(cfg) == ATTN
    assert smallthinker_work.expert_params(cfg) == EXPERT
    assert smallthinker_work.layer_params(cfg) == LAYER == 398_627_840
    assert smallthinker_work.params(cfg) == 8 * LAYER + 2 * EMBED + 2560 \
        == 3_966_937_600
    assert smallthinker_work.resident_params(cfg) == 8 * (ATTN + ROUTER) \
        + EMBED


def test_attention_counts_the_keys_each_layer_sees(cfg):
    assert smallthinker_work.keys_seen(5) == 15
    assert smallthinker_work.keys_seen(5, 3) == 1 + 2 + 3 + 3 + 3
    assert smallthinker_work.keys_seen(4096, 4096) == \
        smallthinker_work.keys_seen(4096)
    n = 10000
    pair = 4 * 28 * 128
    want = pair * (2 * n * (n + 1) // 2
                   + 6 * (4096 * 4097 // 2 + (n - 4096) * 4096))
    assert smallthinker_work.attention_flops(cfg, n) == want
    family = harness.load_module("models", "smallthinker")
    active = 8 * (ATTN + ROUTER + 6 * EXPERT)
    assert family.serve_flops(cfg, 9000, 1000) == pytest.approx(
        2.0 * active * n + want + 2.0 * 2560 * 151936 * 1000)


def test_decode_pass_bytes_term_by_term(cfg):
    got = smallthinker_work.decode_pass_bytes(cfg, 480, 32 * 10240,
                                              32 * 4096, 2)
    row = 2 * 512 * 2
    assert got == {"resident": (8 * (ATTN + ROUTER) + EMBED) * 2,
                   "experts": 480 * EXPERT * 2,
                   "kv_full": 2 * 32 * 10240 * row,
                   "kv_window": 6 * 32 * 4096 * row}


def reader(name):
    return harness.load_module("layer_metrics", name)


def run_of(cfg):
    return {"trace": None, "peak": PEAK, "chips": 1, "cfg": cfg,
            "traffic": {"engine": {"page_size": 16, "dtype": "bfloat16"}}}


def decode_span(start, pages_live=640, rows=40000, hit=470, window=True,
                marker=True):
    stats = {"live": 32, "pages_live": pages_live}
    if window:
        stats["window_rows_live"] = rows
    s = Span("serving.decode", start, start + 40e6, 0, stats)
    if marker:
        s.children.append(Span(
            "serving.experts", start + 39e6, start + 39e6 + 10, 0,
            {"experts_hit": hit, "expert_tokens_max": 9, "rows": 32,
             "layers": 8}, parent=s))
    return s


def flat(spans):
    out = []
    for s in spans:
        out.append(s)
        out.extend(s.descendants())
    return out


def plant(monkeypatch, spans, busy):
    monkeypatch.setattr(hostspans, "load_current", lambda: object())
    monkeypatch.setattr(hostspans, "host_spans", lambda p: flat(spans))
    monkeypatch.setattr(hostspans, "busy_seconds_inside",
                        lambda p, which: [busy] * len(which))


def test_window_decode_hbm_roofline(cfg, monkeypatch):
    plant(monkeypatch, [decode_span(0.0), decode_span(
        50e6, pages_live=700, rows=41000, hit=480)], 0.025)
    need = (smallthinker_work.decode_pass_needed(cfg, 470, 640 * 16, 40000,
                                                 2)
            + smallthinker_work.decode_pass_needed(cfg, 480, 700 * 16,
                                                   41000, 2))
    want = 100.0 * need / 819e9 / 0.05
    assert reader("window_decode_hbm_roofline.serve").read(run_of(cfg)) == \
        pytest.approx(want)
    assert 0.0 < want < 100.0


def test_window_prefill_flash_roofline(cfg, monkeypatch):
    """Two prefill spans each launch one program; the flash forwards of
    ``[28, s, 128]`` inside those programs are the kernel time, a grouped
    product and the flash call of a program launched elsewhere are not."""
    spans = [Span("serving.prefill", 0.0, 100e6, 0,
                  {"tokens": 5000, "window_layers": 6, "bucket": 8192}),
             Span("serving.prefill", 200e6, 300e6, 0,
                  {"tokens": 300, "window_layers": 6, "bucket": 512})]
    modules = [launches.Module(10e6, 80e6, 5e6, "run_id", spans[0]),
               launches.Module(210e6, 230e6, 205e6, "run_id", spans[1]),
               launches.Module(400e6, 420e6, 350e6, "run_id", None)]
    flash = ("%custom-call.1 = (bf16[28,8192,128]{2,1,0}, "
             "f32[28,8192,8]{2,1,0}) custom-call(bf16[28,8192,128] %a)")
    small = flash.replace("8192", "512")
    grouped = "%custom-call.7 = f32[30000,1536]{1,0} custom-call(%b)"
    events = [(flash, 20e6, 24e6), (flash, 30e6, 34e6), (grouped, 40e6, 60e6),
              (small, 215e6, 216e6), (flash, 405e6, 409e6)]
    monkeypatch.setattr(hostspans, "load_current", lambda: object())
    monkeypatch.setattr(hostspans, "host_spans", lambda p: spans)
    monkeypatch.setattr(launches, "modules", lambda p: modules)
    monkeypatch.setattr(hostspans, "_device_plane",
                        lambda p: type("P", (), {"name": "/device:TPU:0"}))
    monkeypatch.setattr(xplane, "device_events",
                        lambda p: {"/device:TPU:0": events})
    need = (smallthinker_work.attention_flops(cfg, 5000)
            + smallthinker_work.attention_flops(cfg, 300))
    want = 100.0 * need / 197e12 / 0.009
    got = reader("window_prefill_flash_roofline.serve").read(run_of(cfg))
    assert got == pytest.approx(want)
    assert 0.0 < want < 100.0


@pytest.mark.parametrize("name", ["window_decode_hbm_roofline.serve",
                                  "window_prefill_flash_roofline.serve"])
def test_nothing_to_read_is_none(cfg, name, monkeypatch):
    read = reader(name).read
    monkeypatch.setattr(hostspans, "load_current", lambda: None)
    assert read(run_of(cfg)) is None                # no capture at all
    # the parent's program: no window attributes on its spans
    plant(monkeypatch, [decode_span(0.0, window=False)], 0.01)
    monkeypatch.setattr(launches, "modules", lambda p: [])
    assert read(run_of(cfg)) is None
    gpt = {"family": "gpt", "hidden_size": 1024, "num_hidden_layers": 24}
    plant(monkeypatch, [decode_span(0.0)], 0.01)
    assert read(run_of(gpt)) is None
    assert read(dict(run_of(cfg), peak=None)) is None


# -------------------------------------------------------------- whole runs
def drive(seed, seconds=3.0):
    args = run.parse_args(["--workload", CELL, "--seed", str(seed),
                           "--seconds", str(seconds), "--rehearse-cpu"])
    manifest, ctx, runner = run.open_cell(args)
    line = run.finish(ctx, manifest, runner.run(ctx))
    json.dumps(line)                      # the line has to serialise
    return line, ctx, runner


def test_rehearsal_is_correct_and_checks_past_the_window(capsys):
    assert run.main(["--workload", CELL, "--seed", str(2 ** 31 + 79),
                     "--seconds", "3", "--rehearse-cpu"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert line["compared"]["logit_gap_mean"]["limit"] is not None
    note = next(n for n in line["notes"] if "longest sequence" in n)
    longest = int(note.split("longest sequence ")[1].split()[0])
    assert longest > 16                   # past the rehearsal's window


def test_an_altered_token_and_the_control_are_not_correct():
    """A served token changed after the run, and the fp8 control choosing
    the tokens, both read over the cell's own limit."""
    line, ctx, runner = drive(13)
    assert line["correct"] is True, line["compared"]
    requests, served, picks = ctx.checked
    limit = ctx.cell["limits"]["logit_gap_mean"]
    control = runner.reference_gaps(ctx, requests, served, picks, mode="fp8")
    assert control["mean"] > limit
    tokens = served["tokens"][picks[0]]
    tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 500
    assert runner.reference_gaps(ctx, requests, served, picks)["mean"] > \
        limit


def test_attending_past_the_window_is_not_correct(tmp_path):
    """The window layers' prefill planted to attend over the whole prompt:
    the run prints ``correct: false``.  In a process of its own, with a
    compile cache of its own (the faulty programs never meet the sound
    ones)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "tools", "plant_window_fault.py"),
         "--workload", CELL, "--seed", "12", "--seconds", "3",
         "--rehearse-cpu"], capture_output=True, text=True, env=env,
        cwd=harness.ROOT, timeout=600)
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0
    assert line["correct"] is False, line["compared"]
