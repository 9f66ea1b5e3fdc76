"""``find_rate.py`` for a cell whose window opens on a FULL engine: each
rate is driven through the runner's own loop behind the mix's ramp (the
burst set-up offers), as the cell runs, and the requests left at the end
are expired through the engine's own deadline sweep, so the next rate
starts empty without decoding them out.  The knee is the highest rate
whose waiting queue does not grow through the window (mean depth of the
last third against the first third); a cell above its knee runs at 1.25
times it, written into the mix's file as a number.

    python benchmark/tools/find_rate_ramp.py --workload <cell> \
        --seconds 30 --rates 0.6,0.9,1.2 --out knee.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def expire_all(engine):
    """Every request still queued or running ends as past its deadline at
    the next step (the engine's own sweep releases slot and pages)."""
    for req in list(engine._requests.values()):
        req.deadline_t = float("-inf")
    while engine.has_unfinished():
        engine.step()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=4242)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    a = ap.parse_args()
    from benchmark import run, stats, traffic as traffic_gen
    argv = ["--workload", a.workload, "--seed", str(a.seed),
            "--seconds", str(a.seconds)]
    if a.rehearse_cpu:
        argv.append("--rehearse-cpu")
    _manifest, ctx, runner = run.open_cell(run.parse_args(argv))
    _model, engine = runner.build(ctx)
    runner.warm(ctx, engine)
    base = ctx.traffic["arrival"]["rate_qps"]
    vocab = ctx.cfg["vocab_size"]
    rows = []
    for rate in [float(r) for r in a.rates.split(",")]:
        reqs = traffic_gen.generate(ctx.traffic, a.seconds, a.seed, vocab,
                                    rate_scale=rate / base)
        served = runner.drive(ctx, engine, reqs, a.seconds,
                              traffic_gen.ramp(ctx.traffic, a.seed, vocab))
        third = a.seconds / 3

        def depth(lo, hi):
            d = [q for t, q in served["queue"] if lo <= t < hi]
            return sum(d) / len(d) if d else 0.0
        tokens = sum(1 for ts in served["token_times"] for t in ts
                     if 0.0 <= t <= a.seconds)
        gaps = stats.gaps_ms([[t for t in ts if t >= 0.0]
                              for ts in served["token_times"]])
        row = {"rate_qps": rate, "requests": len(reqs),
               "failed": served["failed"],
               "tokens_per_s": tokens / a.seconds,
               "finished": sum(served["finished"]),
               "itl_p50_ms": stats.median(gaps) if gaps else None,
               "itl_p95_ms": stats.percentile(gaps, 95) if gaps else None,
               "queue_first_third": depth(0, third),
               "queue_last_third": depth(2 * third, a.seconds),
               "decode_step_ms": 1e3 * stats.median(served["decode_only"])
               if served["decode_only"] else None}
        print(json.dumps(row), flush=True)
        rows.append(row)
        expire_all(engine)
    engine.shutdown()
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
