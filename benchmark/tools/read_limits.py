"""The readings a cell's limits are set from, taken on the chip at the
cell's own size, many seeds in ONE process (set-up is paid once):

- the program's numbers on every seed (the lower reading is their largest),
- the control's — the reference with fp8 operands in the program's place —
  on the first ``--control`` seeds (the upper reading is their smallest),
- for a training cell, the half-batch fault planted in the reference.

    python benchmark/tools/read_limits.py --workload <cell> --seeds 12 \
        --first-seed 500 [--control 4] [--seconds 12] --out chiprun_out/x.json
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=500)
    ap.add_argument("--control", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    return ap.parse_args()


def train(ctx, runner, seeds, n_control):
    import numpy as np
    import paddle_tpu as P
    from benchmark import compare
    from benchmark.reference import common as refc
    model, opt, train_step, loader, leaves = runner.build(ctx)
    traffic, vocab = ctx.traffic, ctx.cfg["vocab_size"]
    spec = ctx.family.reference.weight_spec(ctx.cfg)
    taken = {}
    for seed in seeds:
        ctx.seed = seed
        weights = refc.make_weights(spec, seed)
        for name, p in leaves.items():
            p._set_value(weights[name])
        del weights
        for t in opt._accumulators.values():
            t._set_value(t.__dict__["_reinit"]())
        data = runner._dataset(traffic, vocab, seed)
        b = traffic["batch"]

        def feed():
            for step in range(runner.FOLLOWED_STEPS):
                rows = [data[step * b + j] for j in range(b)]
                yield (P.to_tensor(np.stack([r[0] for r in rows])),
                       P.to_tensor(np.stack([r[1] for r in rows])))
        t0 = time.perf_counter()
        taken[seed] = runner.follow_first_steps(ctx, opt, train_step, feed(),
                                                leaves)
        print(f"seed {seed}: program followed in "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
    del model, opt, train_step, loader, leaves
    gc.collect()
    rows = []
    plain = runner.reference_follower(ctx)
    low = runner.reference_follower(ctx, "fp8")
    for n, seed in enumerate(seeds):
        ctx.seed = seed
        batches, prog = taken[seed]
        ref = runner.reference_readings(ctx, batches, follower=plain)
        row = {"seed": seed, "program": compare.training_numbers(prog, ref),
               "losses": {"program": prog["loss"], "reference": ref["loss"]}}
        if n < n_control:
            for name, kw in (("control_fp8", {"follower": low}),
                             ("fault_half_batch",
                              {"follower": plain,
                               "rows": ctx.traffic["batch"] // 2})):
                row[name] = compare.training_numbers(
                    runner.reference_readings(ctx, batches, **kw), ref)
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def serve(ctx, runner, seeds, n_control, seconds):
    from benchmark import traffic as traffic_gen
    rows, kept = [], {}
    ctx.window_seconds = seconds
    for seed in seeds:
        ctx.seed = seed
        model, engine = runner.build(ctx)
        runner.warm(ctx, engine)
        requests = traffic_gen.generate(ctx.traffic, seconds, seed,
                                        ctx.cfg["vocab_size"])
        served = runner.drive(ctx, engine, requests, seconds,
                              traffic_gen.ramp(ctx.traffic, seed,
                                               ctx.cfg["vocab_size"]))
        requests = served["requests"]
        engine.shutdown()
        del model, engine
        gc.collect()
        picks = runner.checked_sample(requests, served,
                                      ctx.traffic["checked_requests"], seed)
        kept[seed] = (requests, {"tokens": served["tokens"],
                                 "finished": served["finished"]}, picks)
        print(f"seed {seed}: served, failed {served['failed']}", flush=True)
    for n, seed in enumerate(seeds):
        ctx.seed = seed
        requests, served, picks = kept[seed]
        row = {"seed": seed, "checked_tokens": sum(
            len(served["tokens"][k]) for k in picks),
            "program": runner.reference_gaps(ctx, requests, served, picks)}
        if n < n_control:
            row["control_fp8"] = runner.reference_gaps(
                ctx, requests, served, picks, mode="fp8")
            row["sanity_bf16"] = runner.reference_gaps(
                ctx, requests, served, picks, mode="bf16")
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main():
    a = parse()
    from benchmark import run
    argv = ["--workload", a.workload, "--seed", str(a.first_seed),
            "--seconds", str(a.seconds)]
    if a.rehearse_cpu:
        argv.append("--rehearse-cpu")
    args = run.parse_args(argv)
    _manifest, ctx, runner = run.open_cell(args)
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    if ctx.cell["runner"] == "train":
        rows = train(ctx, runner, seeds, a.control)
    else:
        rows = serve(ctx, runner, seeds, a.control, a.seconds)
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
