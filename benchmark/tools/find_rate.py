"""Find the knee of a serving cell once, on the chip: one boot, ascending
rates, each driven for ``--seconds`` through the runner's own loop.  The
knee is the highest rate whose waiting queue does not grow through the
window (mean depth of the last third against the first third, and the
time to first token of late arrivals against early ones).  The cell then
runs at four fifths of it, written into the mix's file as a number.

    python benchmark/tools/find_rate.py --workload <cell> --seconds 20 \
        --rates 3,4,5,6,7,8,10 --out chiprun_out/knee.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
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
    args = run.parse_args(argv)
    _manifest, ctx, runner = run.open_cell(args)
    model, engine = runner.build(ctx)
    runner.warm(ctx, engine)
    base = ctx.traffic["arrival"]["rate_qps"]
    rows = []
    for rate in [float(r) for r in a.rates.split(",")]:
        reqs = traffic_gen.generate(ctx.traffic, a.seconds, a.seed,
                                    ctx.cfg["vocab_size"],
                                    rate_scale=rate / base)
        served = runner.drive(ctx, engine, reqs, a.seconds)   # no ramp: the
        #                        sweep watches the queue build from empty
        first = [t[0] if t else None for t in served["token_times"]]
        ttft = stats.ttfts_ms([r.due_s for r in reqs], first)
        third = a.seconds / 3

        def depth(lo, hi):
            d = [q for t, q in served["queue"] if lo <= t < hi]
            return sum(d) / len(d) if d else 0.0

        def ttft_of(lo, hi):
            v = [t for r, t in zip(reqs, ttft) if lo <= r.due_s < hi]
            return stats.median(v) if v else None
        tokens = sum(1 for ts in served["token_times"] for t in ts
                     if t <= a.seconds)
        gaps = stats.gaps_ms(served["token_times"])
        row = {"rate_qps": rate, "requests": len(reqs),
               "failed": served["failed"],
               "tokens_per_s": tokens / a.seconds,
               "ttft_p50_ms": stats.median(ttft),
               "finished": sum(served["finished"]),
               "ttft_p95_ms": stats.percentile(ttft, 95),
               "itl_p50_ms": stats.median(gaps) if gaps else None,
               "itl_p95_ms": stats.percentile(gaps, 95) if gaps else None,
               "queue_first_third": depth(0, third),
               "queue_last_third": depth(2 * third, a.seconds),
               "ttft_p50_first_third_ms": ttft_of(0, third),
               "ttft_p50_last_third_ms": ttft_of(2 * third, a.seconds),
               "decode_step_ms": 1e3 * stats.median(served["decode_only"])
               if served["decode_only"] else None,
               "drained_at_s": max((t[-1] for t in served["token_times"]
                                    if t), default=None)}
        print(json.dumps(row), flush=True)
        rows.append(row)
        while engine.has_unfinished():    # a rate above the knee leaves a
            engine.step()                 # queue: the next starts empty
    engine.shutdown()
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
