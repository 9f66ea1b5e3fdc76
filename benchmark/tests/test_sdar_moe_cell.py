"""The ``sdar_moe`` cell: ``sdar_work``'s counts against hand-worked numbers
for ``sdar-30b-a3b-chat`` as it is cut (seven layers, everything else whole),
the family's FLOPs, the three per-layer readers on made-up spans with and
without the attributes they read, and whole rehearsal runs: a sound one comes
out correct, one whose commit pass is skipped does not, nor does the fp8
control in the program's place."""
import json
import os

import pytest

from benchmark import harness, hostspans, run, sdar_work
from benchmark.hostspans import Span
from benchmark.peaks import PEAKS

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = PEAKS["TPU v5e"]
CELL = "sdar30b_serve_chat"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


# q 2048 x 4096 and o 4096 x 2048, k and v 2048 x 512 each
ATTN = 2 * 2048 * 4096 + 2 * 2048 * 512          # 18,874,368
ROUTER = 2048 * 128                              # 262,144
EXPERT = 3 * 2048 * 768                          # 4,718,592
EMBED = 151936 * 2048                            # 311,164,928
LAYER = ATTN + 2 * 128 + 2 * 2048 + ROUTER + 128 * EXPERT


def test_the_published_widths_are_in_the_file(cfg):
    want = {"hidden_size": 2048, "num_attention_heads": 32,
            "num_key_value_heads": 4, "head_dim": 128, "num_experts": 128,
            "moe_intermediate_size": 768, "num_experts_per_tok": 8,
            "vocab_size": 151936, "rope_theta": 1000000,
            "rms_norm_eps": 1e-6, "intermediate_size": 6144,
            "max_position_embeddings": 32768, "max_window_layers": 48,
            "decoder_sparse_step": 1, "num_hidden_layers": 7}
    assert {k: cfg[k] for k in want} == want
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["block_length"] == 4 and cfg["mask_token_id"] == 151669
    for key in ("block_length", "mask_token_id", "denoising_steps",
                "remasking", "confidence_threshold", "logits_shift",
                "qk_norm"):
        assert key in cfg["assumed"]
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "sdar-30b-a3b-chat")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "itl_p95_ms", "serve_mfu_pct",
                         "decode_step_ms", "device_idle_pct.serve",
                         "decode_ms.serve", "prefill_ms.serve",
                         "engine_host_ms.serve"):
            assert m["workloads"][-1] == CELL


def test_the_mix_is_the_chat_mix_with_the_decoding_rule():
    mix = harness.load_json("traffic",
                            "chat_saturated_b4_p128-1024_o64-256.json")
    base = harness.load_json("traffic",
                             "chat_saturated_p128-1024_o64-256.json")
    for key in ("prompt_len", "output_len", "engine", "warm_prompts",
                "drain_seconds", "checked_requests", "reference_pad_to",
                "ramp"):
        assert mix[key] == base[key]
    assert mix["engine"]["max_num_seqs"] == 32 == mix["ramp"]["burst"]
    assert mix["sampling"] == dict(base["sampling"], denoising_steps=4,
                                   remasking="low_confidence_static")
    assert mix["mix_seed"] != base["mix_seed"]


def test_parameters_held(cfg):
    assert sdar_work.attention_params(cfg) == ATTN
    assert sdar_work.expert_params(cfg) == EXPERT
    assert sdar_work.layer_params(cfg) == LAYER == 623_120_640
    assert sdar_work.params(cfg) == 7 * LAYER + 2 * EMBED + 2048 \
        == 4_984_176_384
    # and it is what the weights' own shapes add up to
    spec = harness.load_module("models", "sdar_moe").reference.weight_spec(
        cfg)
    total = 0
    for shape, _kind in spec.values():
        n = 1
        for dim in shape:
            n *= dim
        total += n
    assert total == sdar_work.params(cfg)
    # 9.97 GB in bf16, 62% of the chip
    assert 2 * total == pytest.approx(9.97e9, rel=1e-3)


def test_decode_pass_bytes_term_by_term(cfg):
    """32 live slots of 700 stored positions and a block each, every expert
    of every layer hit, bf16."""
    live_rows = 32 * 704
    terms = sdar_work.decode_pass_bytes(cfg, 896, live_rows, 128, 2)
    resident = 7 * (ATTN + ROUTER) + EMBED
    assert sdar_work.resident_params(cfg) == resident == 445_120_512
    assert terms["resident"] == 2 * resident               # 0.89 GB
    assert terms["experts"] == 2 * 896 * EXPERT == 8_455_716_864   # 8.46 GB
    # K and V rows: 4 x 128 x 2 bytes each, 2,048 B a token a layer
    assert sdar_work.kv_row_bytes(cfg, 2) == 2048
    assert terms["kv_read"] == 7 * live_rows * 2048 == 322_961_408
    assert terms["kv_write"] == 7 * 128 * 2048
    total = sdar_work.decode_pass_needed(cfg, 896, live_rows, 128, 2)
    assert total == sum(terms.values()) == pytest.approx(9.67e9, rel=0.01)
    # 11.8 ms at the v5e's 819 GB/s
    assert total / PEAK.hbm_bytes_s == pytest.approx(0.0118, rel=0.01)


def test_family_serve_flops_counts_one_forward_a_position(cfg):
    family = harness.load_module("models", "sdar_moe")
    active = 7 * (ATTN + ROUTER + 8 * EXPERT)
    assert sdar_work.active_body_params(cfg) == active == 398_196_736
    # prompt 10, 3 tokens: 13 positions through the body; position i sees
    # (i // 4 + 1) x 4 keys: 4 x 4 + 4 x 8 + 4 x 12 + 16 = 112; 32 heads of
    # 2 x 128 a key, two products, seven layers; the head three times
    assert family.serve_flops(cfg, 10, 3) == pytest.approx(
        2 * active * 13 + 2 * 7 * 32 * 2 * 128 * 112 + 3 * 2 * EMBED)
    # a generated position costs the head, a prompt position does not
    assert family.serve_flops(cfg, 11, 3) - family.serve_flops(cfg, 10, 3) \
        < family.serve_flops(cfg, 10, 4) - family.serve_flops(cfg, 10, 3)


# ------------------------------------------------------------ the readers
def reader(name):
    return harness.load_module("layer_metrics", name)


def decode_span(start, live=32, commits=6, pages_live=1500, hit=890,
                tokens_max=20, blocks=True, marker=True):
    stats = {"pages_live": pages_live, "live": live}
    if blocks:
        stats.update(block_rows=4 * live, commits=commits, masked=60)
    s = Span("serving.decode", start, start + 40e6, 0, stats)
    if marker:
        s.children.append(Span(
            "serving.experts", start + 39e6, start + 39e6 + 10, 0,
            {"experts_hit": hit, "expert_tokens_max": tokens_max,
             "rows": 128, "layers": 7}, parent=s))
    return s


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


def test_block_tokens_per_forward(cfg, monkeypatch):
    plant(monkeypatch, [decode_span(0.0, commits=6),
                        decode_span(50e6, commits=7),
                        decode_span(100e6, live=30, commits=6)], 0.03)
    assert reader("block_tokens_per_forward.serve").read(run_of(cfg)) == \
        pytest.approx(4 * 19 / 94)


def test_block_decode_hbm_roofline(cfg, monkeypatch):
    plant(monkeypatch, [decode_span(0.0), decode_span(
        50e6, pages_live=1600, hit=880)], 0.038)
    need = (sdar_work.decode_pass_needed(cfg, 890, 1500 * 16, 128, 2)
            + sdar_work.decode_pass_needed(cfg, 880, 1600 * 16, 128, 2))
    want = 100.0 * need / 819e9 / 0.076
    assert reader("block_decode_hbm_roofline.serve").read(run_of(cfg)) == \
        pytest.approx(want)
    assert 25.0 < want < 100.0


def test_moe_block_imbalance(cfg, monkeypatch):
    plant(monkeypatch, [decode_span(0.0, tokens_max=19),
                        decode_span(50e6, tokens_max=23),
                        decode_span(100e6, tokens_max=31)], 0.03)
    # mean load 128 x 8 / 128 = 8 rows an expert
    assert reader("moe_block_imbalance.serve").read(run_of(cfg)) == \
        pytest.approx(23 / 8)


@pytest.mark.parametrize("name", ["block_tokens_per_forward.serve",
                                  "block_decode_hbm_roofline.serve",
                                  "moe_block_imbalance.serve"])
def test_nothing_to_read_is_none(cfg, name, monkeypatch):
    read = reader(name).read
    monkeypatch.setattr(hostspans, "load_current", lambda: None)
    assert read(run_of(cfg)) is None                # no capture at all
    # a capture of a program whose decode spans lack the attributes (a
    # next-token model, or the parent's program)
    plant(monkeypatch, [decode_span(0.0, blocks=False)], 0.01)
    assert read(run_of(cfg)) is None
    plant(monkeypatch, [], 0.01)
    assert read(run_of(cfg)) is None
    gpt = {"family": "gpt", "hidden_size": 1024, "num_hidden_layers": 24}
    plant(monkeypatch, [decode_span(0.0, blocks=False, marker=False)], 0.01)
    assert read(run_of(gpt)) is None
    if "roofline" in name:
        plant(monkeypatch, [decode_span(0.0)], 0.0)
        assert read(run_of(cfg)) is None            # never 0
        assert read(dict(run_of(cfg), peak=None)) is None
        plant(monkeypatch, [decode_span(0.0, marker=False)], 0.01)
        assert read(run_of(cfg)) is None


# -------------------------------------------------------------- whole runs
def drive(seed, seconds=3.0):
    args = run.parse_args(["--workload", CELL, "--seed", str(seed),
                           "--seconds", str(seconds), "--rehearse-cpu"])
    manifest, ctx, runner = run.open_cell(args)
    line = run.finish(ctx, manifest, runner.run(ctx))
    json.dumps(line)                      # the line has to serialise
    return line, ctx, runner


def test_rehearsal_through_main(capsys):
    assert run.main(["--workload", CELL, "--seed", str(2 ** 31 + 79),
                     "--seconds", "3", "--rehearse-cpu"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert set(line["compared"]) == {"logit_gap_mean", "order_gap_mean"}
    assert line["compared"]["logit_gap_mean"]["limit"] is not None


def test_the_control_in_the_programs_place_is_not_correct():
    """The trajectory the program served, replayed by the reference with
    fp8 operands choosing the tokens: over the cell's own limit."""
    line, ctx, runner = drive(13)
    assert line["correct"] is True, line["compared"]
    requests, served, picks = ctx.checked
    assert picks, "no finished greedy request was checked"
    limit = ctx.cell["limits"]["logit_gap_mean"]
    control = runner.reference_gaps(ctx, requests, served, picks, mode="fp8")
    assert control["tokens"] >= 16
    assert control["mean"] > limit
    sound = runner.reference_gaps(ctx, requests, served, picks)
    assert sound["mean"] <= limit and sound["tokens"] == control["tokens"]


def test_a_skipped_commit_is_not_correct(monkeypatch):
    """The pass that fixes a block's last position also moves the length:
    the pages keep the K/V of a block that still held a mask.  Under the
    cell's own limit the run is not correct."""
    from paddle_tpu.serving import generation
    fault = harness.load_module("tools", "plant_skipped_commit")
    monkeypatch.setattr(generation.BlockDiffusion, "decoded",
                        fault.skipping(generation.BlockDiffusion.decoded))
    line, ctx, _runner = drive(12)
    assert ctx.checked[2], "no finished greedy request was checked"
    assert line["failed"] == 0
    assert line["correct"] is False, line["compared"]
