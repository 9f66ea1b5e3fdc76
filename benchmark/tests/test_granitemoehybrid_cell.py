"""The ``granitemoehybrid`` cell: ``ssm_work``'s counts against hand-worked
numbers for ``granite-4.0-h-small`` as it is cut (ten layers, experts 0-35
of 72), the family's FLOPs, the three per-layer readers on made-up spans
with and without the attributes they read, and whole rehearsal runs: a sound
one comes out correct, one whose recurrent state is left unchanged between
decode steps does not."""
import json
import os

import pytest

from benchmark import harness, hostspans, run, ssm_work
from benchmark.hostspans import Span
from benchmark.peaks import PEAKS

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = PEAKS["TPU v5e"]
CELL = "granite4h_serve_chat"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "granite-4.0-h-small.json")) as f:
        return json.load(f)


# a mamba mixer: in_proj 4096 x (8192 + 8448 + 128) = 68,681,728; out_proj
# 8192 x 4096 = 33,554,432; conv 4 x 8448 + 8448 = 42,240; A_log, D,
# dt_bias 384; gated norm 8192
MIXER_MATRICES = 102_236_160
MIXER = 102_286_976
ATTN = 2 * 4096 * 4096 + 2 * 4096 * 1024     # 41,943,040
SHARED = 3 * 4096 * 1536                     # 18,874,368
ROUTER = 4096 * 72                           # 294,912
EXPERT = 3 * 4096 * 768                      # 9,437,184
EMBED = 100352 * 4096                        # 411,041,792


def test_the_published_widths_are_in_the_file(cfg):
    want = {"hidden_size": 4096, "mamba_n_heads": 128, "mamba_d_head": 64,
            "mamba_d_state": 128, "mamba_chunk_size": 256,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "intermediate_size": 768, "num_experts_per_tok": 10,
            "shared_intermediate_size": 1536, "vocab_size": 100352,
            "num_hidden_layers": 10, "num_local_experts": 36}
    assert {k: cfg[k] for k in want} == want
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_local_experts": 72}
    assert cfg["held"] == [0, 36]
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "granite-4.0-h-small")
    assert entry["reduced"] == ["num_hidden_layers", "num_local_experts"]


def test_parameters_held(cfg):
    assert ssm_work.mixer_matrices(cfg) == MIXER_MATRICES
    assert ssm_work.mixer_params(cfg) == MIXER
    assert ssm_work.attention_params(cfg) == ATTN
    assert ssm_work.expert_params(cfg) == EXPERT
    # nine mamba layers, one of attention, ten expert layers of 36 held,
    # the tied table; the 21 norm vectors of 4096 on top
    body = (9 * (MIXER + SHARED + ROUTER) + (ATTN + SHARED + ROUTER)
            + 10 * 36 * EXPERT + EMBED)
    assert body == 4_962_646_656
    assert ssm_work.params(cfg) == body + 21 * 4096 == 4_962_732_672
    # and it is what the weights' own shapes add up to
    spec = harness.load_module("models", "granitemoehybrid").reference \
        .weight_spec(cfg)
    total = 0
    for shape, _kind in spec.values():
        n = 1
        for dim in shape:
            n *= dim
        total += n
    assert total == ssm_work.params(cfg)


def test_decode_step_bytes_term_by_term(cfg):
    """64 live slots, every held expert hit, 700 tokens a slot, bf16."""
    terms = ssm_work.decode_step_terms(cfg, 360, 64 * 9, 64 * 700, 2)
    resident = 9 * MIXER_MATRICES + ATTN + 10 * (SHARED + ROUTER) + EMBED
    assert ssm_work.resident_params(cfg) == resident == 1_564_803_072
    assert terms["resident"] == 2 * resident              # 3.13 GB
    assert terms["experts"] == 2 * 360 * EXPERT == 6_794_772_480
    # a slot a layer: 128 x 64 x 128 float32 + 3 x 8448 bf16, read and
    # written once
    one = 4 * 128 * 64 * 128 + 3 * 8448 * 2
    assert ssm_work.state_bytes(cfg, 2) == one == 4_244_992
    assert terms["state"] == 2 * 576 * one == 4_890_230_784
    # K and V rows of the one attention layer: 8 x 128 x 2 bytes each
    assert terms["kv"] == 64 * 700 * 2 * 1024 * 2 == 183_500_800
    total = ssm_work.decode_step_bytes(cfg, 360, 576, 64 * 700, 2)
    assert total == sum(terms.values()) == pytest.approx(15.0e9, rel=0.01)
    # 18.3 ms at the v5e's 819 GB/s
    assert total / PEAK.hbm_bytes_s == pytest.approx(0.0183, rel=0.01)


def test_family_serve_flops_counts_what_is_held_here(cfg):
    family = harness.load_module("models", "granitemoehybrid")
    # a token meets 10 experts of 72, 36 of which are here: 5 on average
    active = (9 * MIXER_MATRICES + ATTN + 10 * (SHARED + ROUTER)
              + 10 * 5 * EXPERT)
    assert ssm_work.active_params(cfg) == active == 1_625_620_480
    scan = 9 * (4 * 128 * 64 * 128 + 2 * 4 * 8448)
    assert ssm_work.scan_flops_a_token(cfg) == scan
    # prompt 100, 3 tokens: 102 positions through the body; keys 5050 +
    # 101 + 102 at 2 x 128 a query head, 32 heads; the head three times
    assert family.serve_flops(cfg, 100, 3) == pytest.approx(
        (2 * active + scan) * 102 + 4 * 32 * 128 * 5253 + 3 * 2 * EMBED)
    # a prefill of 1024 real tokens: ~3.4 TFLOP, 17 ms at the bf16 peak
    assert ssm_work.prefill_flops(cfg, 1024) / PEAK.bf16_flops == \
        pytest.approx(0.0171, rel=0.02)
    share = 2 * 9 * MIXER_MATRICES * 1024 / ssm_work.prefill_flops(cfg, 1024)
    assert 0.5 < share < 0.6              # the mixers' matrices


# ------------------------------------------------------------ the readers
def reader(name):
    return harness.load_module("layer_metrics", name)


def decode_span(start, state_rows=576, pages_live=2800, hit=350,
                tokens_max=19, marker=True):
    stats = {"pages_live": pages_live, "live": 64}
    if state_rows is not None:
        stats["state_rows"] = state_rows
    s = Span("serving.decode", start, start + 25e6, 0, stats)
    if marker:
        s.children.append(Span(
            "serving.experts", start + 24e6, start + 24e6 + 10, 0,
            {"experts_hit": hit, "expert_tokens_max": tokens_max,
             "rows": 64, "layers": 10}, parent=s))
    return s


def prefill_span(start, tokens=None):
    stats = {"bucket": 512, "tokens": 400}
    if tokens is not None:
        stats.update(scan_tokens=tokens)
    return Span("serving.prefill", start, start + 12e6, 0, stats)


def flat(spans):
    out = []
    for s in spans:
        out.append(s)
        out.extend(s.descendants())
    return out


def run_of(cfg):
    return {"trace": None, "peak": PEAK, "chips": 1, "cfg": cfg,
            "traffic": {"engine": {"page_size": 16, "dtype": "bfloat16"}}}


def plant(monkeypatch, spans, busy):
    monkeypatch.setattr(hostspans, "load_current", lambda: object())
    monkeypatch.setattr(hostspans, "host_spans", lambda p: flat(spans))
    monkeypatch.setattr(hostspans, "busy_seconds_inside",
                        lambda p, which: [busy] * len(which))


def test_ssm_decode_hbm_roofline(cfg, monkeypatch):
    plant(monkeypatch, [decode_span(0.0), decode_span(
        40e6, pages_live=2900, hit=340), prefill_span(30e6, 400)], 0.024)
    need = (ssm_work.decode_step_bytes(cfg, 350, 576, 2800 * 16, 2)
            + ssm_work.decode_step_bytes(cfg, 340, 576, 2900 * 16, 2))
    want = 100.0 * need / 819e9 / 0.048
    assert reader("ssm_decode_hbm_roofline.serve").read(run_of(cfg)) == \
        pytest.approx(want)
    assert 50.0 < want < 100.0


def test_ssm_prefill_roofline(cfg, monkeypatch):
    plant(monkeypatch, [prefill_span(0.0, 400), prefill_span(20e6, 130),
                        decode_span(40e6)], 0.011)
    need = ssm_work.prefill_flops(cfg, 400) + ssm_work.prefill_flops(cfg, 130)
    want = 100.0 * need / 197e12 / 0.022
    assert reader("ssm_prefill_roofline.serve").read(run_of(cfg)) == \
        pytest.approx(want)
    assert 20.0 < want < 100.0


def test_moe_held_imbalance(cfg, monkeypatch):
    plant(monkeypatch, [decode_span(0.0, tokens_max=19),
                        decode_span(40e6, tokens_max=23),
                        decode_span(80e6, tokens_max=31)], 0.02)
    # mean load 64 x 10 / 72 = 8.89 rows an expert: the ROUTER's width
    assert reader("moe_held_imbalance.serve").read(run_of(cfg)) == \
        pytest.approx(23 / (640 / 72))


@pytest.mark.parametrize("name", ["ssm_decode_hbm_roofline.serve",
                                  "ssm_prefill_roofline.serve",
                                  "moe_held_imbalance.serve"])
def test_nothing_to_read_is_none(cfg, name, monkeypatch):
    read = reader(name).read
    monkeypatch.setattr(hostspans, "load_current", lambda: None)
    assert read(run_of(cfg)) is None                # no capture at all
    # a capture of a program whose spans lack the attributes and the
    # marker (the parent's program, or another family's)
    plant(monkeypatch, [decode_span(0.0, state_rows=None, marker=False),
                        prefill_span(30e6)], 0.01)
    assert read(run_of(cfg)) is None
    gpt = {"family": "gpt", "hidden_size": 1024, "num_hidden_layers": 24}
    plant(monkeypatch, [decode_span(0.0, marker=False), prefill_span(
        30e6, 300)], 0.01)
    assert read(run_of(gpt)) is None
    if "roofline" in name:
        plant(monkeypatch, [decode_span(0.0), prefill_span(30e6, 300)], 0.0)
        assert read(run_of(cfg)) is None            # never 0
        assert read(dict(run_of(cfg), peak=None)) is None


# -------------------------------------------------------------- whole runs
def drive(seed, seconds=3.0):
    args = run.parse_args(["--workload", CELL, "--seed", str(seed),
                           "--seconds", str(seconds), "--rehearse-cpu"])
    manifest, ctx, runner = run.open_cell(args)
    line = run.finish(ctx, manifest, runner.run(ctx))
    json.dumps(line)                      # the line has to serialise
    return line, ctx


def test_rehearsal_through_main(capsys):
    assert run.main(["--workload", CELL, "--seed", str(2 ** 31 + 79),
                     "--seconds", "3", "--rehearse-cpu"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert set(line["compared"]) == {"logit_gap", "logit_gap_mean"}
    assert line["compared"]["logit_gap_mean"]["limit"] is not None


def test_state_left_unchanged_is_not_correct(monkeypatch, tmp_path):
    """Decode hands every slot's recurrent state back as it got it (the
    prefill's state, never advanced): under the cell's own limit the run is
    not correct."""
    from paddle_tpu.serving import kv_pool
    # programs compiled with the fault must not come from, or stay in,
    # the checkout's program cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    fault = harness.load_module("tools", "plant_frozen_state")
    monkeypatch.setattr(kv_pool.SlotState, "recur",
                        fault.frozen(kv_pool.SlotState.recur))
    line, ctx = drive(12)
    assert ctx.checked[2], "no finished greedy request was checked"
    assert line["failed"] == 0
    assert line["correct"] is False, line["compared"]
