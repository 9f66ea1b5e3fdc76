"""``read_limits.py`` for a serving cell whose model generates by diffusion
over blocks (runner ``serve_blocks``): the reference replays the served
TRAJECTORY, so each seed's per-position pass record is taken from the engine
before it is shut down and handed to the runner's ``reference_gaps`` with the
served tokens.  Same options, same output file; a row's ``program`` /
``control_fp8`` / ``sanity_bf16`` hold the logit gap's mean, maximum and
quantiles and the order gap's mean.

    python benchmark/tools/read_limits_blocks.py --workload <cell> --seeds 12 \
        --first-seed 500 --control 4 --seconds 12 --out chiprun_out/x.json
"""
from __future__ import annotations

import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def with_pass_records(runner):
    """`runner` whose ``drive`` keeps each seed's pass records and whose
    ``reference_gaps`` finds them again."""
    records = {}

    def drive(ctx, engine, requests, seconds, ramp=()):
        served = runner.drive(ctx, engine, requests, seconds, ramp)
        records[ctx.seed] = runner.pass_records(engine, served["requests"])
        return served

    def reference_gaps(ctx, requests, served, picks, mode="f32"):
        return runner.reference_gaps(
            ctx, requests, dict(served, pass_records=records[ctx.seed]),
            picks, mode=mode)

    return types.SimpleNamespace(**dict(
        vars(runner), drive=drive, reference_gaps=reference_gaps))


def main():
    from benchmark import harness, run
    limits = harness.load_module("tools", "read_limits")
    open_cell = run.open_cell

    def opened(args):
        manifest, ctx, runner = open_cell(args)
        return manifest, ctx, with_pass_records(runner)

    run.open_cell = opened
    limits.main()


if __name__ == "__main__":
    main()
