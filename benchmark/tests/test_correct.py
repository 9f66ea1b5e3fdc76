"""``correct`` has to come out false where it should.

Each case skips the harness's look for a chip (the CPU rehearsal sizes) and
drives the rest of a run through ``run.main``'s own pieces with the timed
path broken underneath — or with the control (the reference in the nearest
precision below bfloat16: fp8 operands) put in the program's place — and a
sound run beside them comes out true.  The limits are the cells' own.
"""
import json
import os

import numpy as np
import pytest

from benchmark import compare, harness, run

ROOT = harness.ROOT


def drive(workload, seed, seconds=1.0, before_run=None):
    args = run.parse_args(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--rehearse-cpu"])
    manifest, ctx, runner = run.open_cell(args)
    if before_run is not None:
        before_run(ctx, runner)
    line = run.finish(ctx, manifest, runner.run(ctx))
    json.dumps(line)                      # the line has to serialise
    line["ctx"], line["runner"] = ctx, runner
    return line


def cells(runner):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return [c["name"] for c in manifest["workloads"]
            if harness.load_json("workloads", c["name"] + ".json")
            ["runner"] == runner]


def train_cells():
    return cells("train")


def serve_cells():
    return cells("serve")


# ----------------------------------------------------------------- training
def state_unchanged(monkeypatch):
    """The step returns its state unchanged: the optimizer does nothing."""
    def plant(ctx, runner):
        import paddle_tpu as P
        monkeypatch.setattr(P.optimizer.AdamW, "step", lambda self: None)
    return plant


def half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    def plant(ctx, runner):
        make_loss = ctx.family.make_loss

        def halved(model):
            loss = make_loss(model)
            return lambda ids, labels: loss(ids[:ids.shape[0] // 2],
                                            labels[:labels.shape[0] // 2])
        monkeypatch.setattr(ctx.family, "make_loss", halved)
    return plant


@pytest.mark.parametrize("cell", train_cells())
def test_sound_training_run_is_correct(cell):
    line = drive(cell, 2 ** 31 + 77)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
@pytest.mark.parametrize("cell", train_cells())
def test_broken_training_path_is_not_correct(cell, fault, monkeypatch):
    line = drive(cell, 11, before_run=fault(monkeypatch))
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("cell", train_cells())
def test_training_control_fp8_is_not_correct(cell):
    """The control: the reference with fp8 operands in the program's
    place, against the float32 reference, under the cell's limits."""
    args = run.parse_args(["--workload", cell, "--seed", "5", "--seconds",
                           "1", "--rehearse-cpu"])
    _manifest, ctx, runner = run.open_cell(args)
    tr, vocab = ctx.traffic, ctx.cfg["vocab_size"]
    rng = np.random.default_rng(5)
    batches = [tuple(rng.integers(0, vocab, (tr["batch"], tr["seq_len"]),
                                  dtype=np.int32) for _ in range(2))
               for _ in range(runner.FOLLOWED_STEPS)]
    ref = runner.reference_readings(ctx, batches)
    low = runner.reference_readings(ctx, batches, mode="fp8")
    half = runner.reference_readings(ctx, batches, rows=tr["batch"] // 2)
    ok, compared = compare.judge(compare.training_numbers(half, ref),
                                 ctx.cell["limits"])
    assert not ok, compared
    ok, compared = compare.judge(compare.training_numbers(low, ref),
                                 ctx.cell["limits"])
    assert not ok, compared
    same, compared = compare.judge(compare.training_numbers(ref, ref),
                                   ctx.cell["limits"])
    assert same and all(v == 0 for v, _ in compared.values())


# ------------------------------------------------------------------ serving
def altered_token(monkeypatch):
    """A token altered where it is produced: the sampler's choice + 1."""
    def plant(ctx, runner):
        from paddle_tpu.serving import LLMEngine
        sample = LLMEngine._sample
        vocab = ctx.cfg["vocab_size"]
        monkeypatch.setattr(
            LLMEngine, "_sample", lambda self, logits, reqs, width: [
                (t + 1) % vocab for t in sample(self, logits, reqs, width)])
    return plant


@pytest.mark.parametrize("cell", serve_cells())
def test_sound_serving_run_is_correct(cell):
    line = drive(cell, 2 ** 31 + 78, seconds=3.0)
    assert line["correct"] is True, line["compared"]
    assert line["ctx"].checked[2], "no finished greedy request was checked"


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("cell", serve_cells())
def test_serving_control_fp8_is_not_correct(cell, seed):
    """The control, at a size a test run can hold (the rehearsal's two
    narrow layers never flip a token by more than rounding; 8 layers of 512
    over 8192 tokens do): at each position the token that the reference
    with fp8 operands puts first lies further below the float32 reference's
    best than the cell's limit; the one bfloat16 operands put first does
    not."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import common as refc
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, config = run.find_cell(manifest, cell)
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = dict(json.load(f), hidden_size=512, num_hidden_layers=8,
                   num_attention_heads=8, intermediate_size=2048,
                   vocab_size=8192, padded_vocab_size=8192,
                   max_position_embeddings=128)
    limit = harness.load_json("workloads", cell + ".json")["limits"][
        "logit_gap"]
    ref = harness.load_module("models", cfg["family"]).reference
    weights = refc.make_weights(ref.weight_spec(cfg), seed, jnp.bfloat16)
    ids = np.random.default_rng(seed).integers(
        1, 8192, (4, 128)).astype(np.int32)

    def logits(mode):
        return np.asarray(jax.jit(lambda w, i: ref.logits(cfg, w, i, mode))(
            weights, ids)).reshape(-1, 8192)

    full = logits("f32")
    rows = np.arange(len(full))

    def gap(mode):
        return float(np.max(full.max(-1) - full[rows, logits(mode).argmax(-1)]))

    assert gap("fp8") > limit
    assert gap("bf16") < limit


@pytest.mark.parametrize("cell", serve_cells())
def test_altered_token_is_not_correct(cell, monkeypatch):
    line = drive(cell, 12, seconds=3.0, before_run=altered_token(monkeypatch))
    assert line["correct"] is False, line["compared"]
