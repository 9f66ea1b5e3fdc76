"""The ``qwen3_next`` cell: the configuration's published widths and the
parameters held (four layers, experts 0-255 of 512, vocabulary rows
0-75,967), ``qwen3next_work``'s bytes and FLOPs against hand-worked numbers,
the two delta-rule readers on made-up spans and device programs with and
without the attributes they read, and whole rehearsal runs: a sound one
comes out correct through ``run.main``; one whose chunked rule hands its
state on without the chunk's decay (a fault planted in the program by
``tools/plant_delta_fault.py``) does not."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, hostspans, launches, qwen3next_work
from benchmark.hostspans import Span
from benchmark.peaks import PEAKS

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = PEAKS["TPU v5e"]
CELL = "qwen3next80b_serve_longctx"
FILE = "qwen3-next-80b-a3b-instruct.json"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", FILE)) as f:
        return json.load(f)


# a Gated DeltaNet mixer: qkvz 2048 x 12288, ba 2048 x 64, out_proj 4096 x
# 2048; conv 4 x 8192; A_log, dt_bias 2 x 32; output norm 128
MIXER_MATRICES = 2048 * 12288 + 2048 * 64 + 4096 * 2048      # 33,685,504
MIXER = MIXER_MATRICES + 4 * 8192 + 64 + 128                  # 33,718,464
# q (query and gate) 2048 x 8192, k and v 2048 x 512 each, o 4096 x 2048
ATTN_MATRICES = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048   # 27,262,976
ATTN = ATTN_MATRICES + 2 * 256                                # 27,263,488
SHARED = 3 * 2048 * 512 + 2048               # the expert and its gate
ROUTER = 2048 * 512
EXPERT = 3 * 2048 * 512                      # 3,145,728
EMBED = 75968 * 2048                         # 155,582,464
STATE = 4 * 32 * 128 * 128 + 3 * 8192 * 2    # f32 S + bf16 conv rows


def test_the_published_widths_are_in_the_file(cfg):
    want = {"hidden_size": 2048, "num_attention_heads": 16,
            "num_key_value_heads": 2, "head_dim": 256,
            "partial_rotary_factor": 0.25, "rope_theta": 10000000,
            "linear_num_key_heads": 16, "linear_num_value_heads": 32,
            "linear_key_head_dim": 128, "linear_value_head_dim": 128,
            "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512, "num_experts_per_tok": 10,
            "full_attention_interval": 4, "rms_norm_eps": 1e-6,
            "max_position_embeddings": 262144, "num_hidden_layers": 4,
            "num_experts": 256, "vocab_size": 75968}
    assert {k: cfg[k] for k in want} == want
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    assert cfg["held"] == [0, 256]
    for key in ("A_log", "dt_bias", "conv_kernel_std", "column_grouping",
                "zero_centred_norms", "mtp", "initializer_range"):
        assert key in cfg["assumed"]
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "qwen3-next-80b-a3b-instruct")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == cfg["source"]


def test_parameters_held(cfg):
    assert qwen3next_work.mixer_matrices(cfg) == MIXER_MATRICES
    assert qwen3next_work.mixer_params(cfg) == MIXER == 33_718_464
    assert qwen3next_work.attention_params(cfg) == ATTN == 27_263_488
    moe = ROUTER + SHARED + 2 * 2048 + 256 * EXPERT
    assert qwen3next_work.layer_params(cfg, 0) == MIXER + moe == 843_225_280
    assert qwen3next_work.layer_params(cfg, 3) == ATTN + moe == 836_770_304
    assert qwen3next_work.params(cfg) == 3_677_613_120
    assert qwen3next_work.resident_params(cfg) == (
        3 * MIXER_MATRICES + ATTN_MATRICES + 4 * (SHARED + ROUTER) + EMBED)


def test_decode_pass_bytes_term_by_term(cfg):
    got = qwen3next_work.decode_pass_bytes(cfg, 730, 3 * 64, 64 * 4096, 2)
    assert qwen3next_work.state_bytes(cfg, 2) == STATE == 2_146_304
    assert qwen3next_work.kv_row_bytes(cfg, 2) == 2048
    assert got == {
        "resident": 2 * (3 * MIXER_MATRICES + ATTN_MATRICES
                         + 4 * (SHARED + ROUTER) + EMBED),
        "experts": 2 * 730 * EXPERT,
        "state": 2 * 3 * 64 * STATE,
        "kv": 64 * 4096 * 2048}


def test_prefill_bytes_and_flops(cfg):
    resident = 3 * MIXER_MATRICES + ATTN_MATRICES + 4 * (SHARED + ROUTER) \
        + EMBED
    assert qwen3next_work.prefill_bytes(cfg, 3000, 2) == (
        2 * (resident + 4 * 256 * EXPERT) + 3000 * 2048 + 3 * STATE)
    active = (3 * MIXER_MATRICES + ATTN_MATRICES
              + 4 * (SHARED + ROUTER + 10 * 256 / 512 * EXPERT))
    delta = 3 * 6.0 * 32 * 128 * 128
    assert qwen3next_work.active_params(cfg) == active
    assert qwen3next_work.delta_flops_a_token(cfg) == delta
    attn = 4.0 * 16 * 256 * 3000 * 3001 / 2
    assert qwen3next_work.prefill_flops(cfg, 3000) == pytest.approx(
        (2 * active + delta) * 3000 + attn + 2.0 * EMBED)
    family = harness.load_module("models", "qwen3_next")
    keys = 3000 * 3001 / 2 + sum(3000 + j + 1 for j in range(499))
    assert family.serve_flops(cfg, 3000, 500) == pytest.approx(
        (2 * active + delta) * 3499 + 4.0 * 16 * 256 * keys
        + 2.0 * EMBED * 500)


# ------------------------------------------------------------- readers
def reader(name):
    return harness.load_module("layer_metrics", name)


def run_of(cfg):
    return {"trace": None, "peak": PEAK, "chips": 1, "cfg": cfg,
            "traffic": {"engine": {"page_size": 16, "dtype": "bfloat16"}}}


def decode_span(start, pages_live=16000, rows=192, hit=730, state=True,
                marker=True):
    stats = {"live": 64, "pages_live": pages_live}
    if state:
        stats.update(state_rows=rows, recurrence="gated_delta")
    s = Span("serving.decode", start, start + 20e6, 0, stats)
    if marker:
        s.children.append(Span(
            "serving.experts", start + 19e6, start + 19e6 + 10, 0,
            {"experts_hit": hit, "expert_tokens_max": 9, "rows": 64,
             "layers": 4}, parent=s))
    return s


def flat(spans):
    out = []
    for s in spans:
        out.append(s)
        out.extend(s.descendants())
    return out


def plant(monkeypatch, spans, busy, launched=None):
    monkeypatch.setattr(hostspans, "load_current", lambda: object())
    monkeypatch.setattr(hostspans, "host_spans", lambda p: flat(spans))
    monkeypatch.setattr(hostspans, "busy_seconds_inside",
                        lambda p, which: [busy] * len(which))
    monkeypatch.setattr(launches, "device_seconds_launched",
                        lambda p, which: (launched or [busy] * len(which)))


def test_gdn_decode_hbm_roofline(cfg, monkeypatch):
    plant(monkeypatch, [decode_span(0.0), decode_span(
        30e6, pages_live=16100, hit=740)], 0.012)
    need = (qwen3next_work.decode_pass_needed(cfg, 730, 192, 16000 * 16, 2)
            + qwen3next_work.decode_pass_needed(cfg, 740, 192, 16100 * 16,
                                                2))
    want = 100.0 * need / 819e9 / 0.024
    assert reader("gdn_decode_hbm_roofline.serve").read(run_of(cfg)) == \
        pytest.approx(want)
    assert 0.0 < want < 100.0


def test_gdn_prefill_roofline(cfg, monkeypatch):
    """Two prefill spans: a long prompt bound by FLOPs, a short one by
    bytes; a third whose programs the capture lost counts neither its work
    nor its time."""
    spans = [Span("serving.prefill", 0.0, 100e6, 0,
                  {"tokens": 9000, "scan_tokens": 9000, "bucket": 16384}),
             Span("serving.prefill", 200e6, 300e6, 0,
                  {"tokens": 1100, "scan_tokens": 1100, "bucket": 2048}),
             Span("serving.prefill", 400e6, 500e6, 0,
                  {"tokens": 5000, "scan_tokens": 5000, "bucket": 8192})]
    plant(monkeypatch, spans, None, launched=[0.12, 0.02, 0.0])
    least = [max(qwen3next_work.prefill_flops(cfg, n) / 197e12,
                 qwen3next_work.prefill_bytes(cfg, n, 2) / 819e9)
             for n in (9000, 1100)]
    assert least[0] == qwen3next_work.prefill_flops(cfg, 9000) / 197e12
    assert least[1] == qwen3next_work.prefill_bytes(cfg, 1100, 2) / 819e9
    want = 100.0 * sum(least) / 0.14
    got = reader("gdn_prefill_roofline.serve").read(run_of(cfg))
    assert got == pytest.approx(want)
    assert 0.0 < want < 100.0


@pytest.mark.parametrize("name", ["gdn_decode_hbm_roofline.serve",
                                  "gdn_prefill_roofline.serve"])
def test_nothing_to_read_is_none(cfg, name, monkeypatch):
    read = reader(name).read
    monkeypatch.setattr(hostspans, "load_current", lambda: None)
    assert read(run_of(cfg)) is None                # no capture at all
    # the parent's program: neither state_rows nor scan_tokens
    plain_prefill = Span("serving.prefill", 0.0, 1e6, 0,
                         {"tokens": 900, "bucket": 1024})
    plant(monkeypatch, [decode_span(0.0, state=False), plain_prefill], 0.01)
    assert read(run_of(cfg)) is None
    # another family's spans with the attributes
    granite = {"family": "granitemoehybrid", "hidden_size": 4096}
    full_prefill = Span("serving.prefill", 0.0, 1e6, 0,
                        {"tokens": 900, "scan_tokens": 900, "bucket": 1024})
    plant(monkeypatch, [decode_span(0.0), full_prefill], 0.01)
    assert read(run_of(granite)) is None
    assert read(dict(run_of(cfg), peak=None)) is None
    assert read(run_of(cfg)) is not None


# -------------------------------------------------------------- whole runs
def test_rehearsal_is_correct_through_main(tmp_path):
    """``benchmark/run.py`` (``run.main``) at the rehearsal's sizes, in a
    process of its own with a compile cache of its own: on the CPU a
    serving program stored in one process may fail to load in another or
    beside other cells' programs (PERF.md section 7)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 83), "--seconds", "3", "--rehearse-cpu"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT,
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert line["compared"]["logit_gap_mean"]["limit"] is not None
    note = next(n for n in line["notes"] if "longest sequence" in n)
    longest = int(note.split("longest sequence ")[1].split()[0])
    assert longest > 8                    # past the rehearsal's chunk


def test_no_decay_between_chunks_is_not_correct(tmp_path):
    """The chunked rule planted to hand its state on without the chunk's
    decay: the run prints ``correct: false``.  In a process of its own,
    with a compile cache of its own (the faulty programs never meet the
    sound ones)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "tools", "plant_delta_fault.py"),
         "--workload", CELL, "--seed", "12", "--seconds", "3",
         "--rehearse-cpu"], capture_output=True, text=True, env=env,
        cwd=harness.ROOT, timeout=600)
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0
    assert line["correct"] is False, line["compared"]
