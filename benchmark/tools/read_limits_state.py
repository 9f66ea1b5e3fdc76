"""``read_limits.py`` for a serving cell whose layers keep a recurrent
state, with ONE more control beside fp8 on the control seeds: the reference
in float32 with its carried state rounded to bfloat16 after every position
(the reference's mode ``"f32/state_bf16"``), printed under ``state_bf16``
inside the row's ``control_fp8``.  Same options, same output file.

    python benchmark/tools/read_limits_state.py --workload <cell> --seeds 12 \
        --first-seed 500 --control 4 --seconds 12 --out chiprun_out/x.json
"""
from __future__ import annotations

import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def with_state_control(runner):
    """`runner` whose fp8 reading also carries the state control's."""
    def reference_gaps(ctx, requests, served, picks, mode="f32"):
        out = runner.reference_gaps(ctx, requests, served, picks, mode=mode)
        if mode == "fp8":
            out = dict(out, state_bf16=runner.reference_gaps(
                ctx, requests, served, picks, mode="f32/state_bf16"))
        return out
    return types.SimpleNamespace(**dict(vars(runner),
                                        reference_gaps=reference_gaps))


def main():
    from benchmark import harness, run
    limits = harness.load_module("tools", "read_limits")
    open_cell = run.open_cell

    def opened(args):
        manifest, ctx, runner = open_cell(args)
        return manifest, ctx, with_state_control(runner)

    run.open_cell = opened
    limits.main()


if __name__ == "__main__":
    main()
