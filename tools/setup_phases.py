"""Where a train cell's set-up goes, by phase, in one benchmark run.

Runs ``benchmark/run.py``'s own ``main`` for one cell (a short window: the
set-up is what is read) with a listener on jax's monitoring events, and
splits the run's ``setup_s`` into

- ``before_trace``: imports, model build, weights, loader start — up to the
  first trace of the step;
- ``passes``: every trace and lowering of the step program before its
  first call (``StaticFunction._call`` traces and lowers a discovery pass,
  finds the optimizer's accumulators registered, and traces and lowers
  again), with each ``trace`` and ``lowering`` event's own seconds;
- ``first_call``: the ``recompile`` event's ``compile_ms`` — the first
  execution of the fresh entry (a lowering for the call, the compile or
  the persistent-cache load under ``backend``, and step 1);
- ``after_first_call``: the followed steps, their readings and the warm-up.

Run from the root of the checkout to be read (the parent's copy too: the
tool imports ``benchmark`` and ``paddle_tpu`` from the working directory):

    python tools/setup_phases.py --workload gpt355m_train --seed 7 \
        [--seconds 5] [--out phases.json]
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

# shorter events are not listed (the step program's trace and lowering take
# seconds; the readings' small programs are listed all the same)
MIN_EVENT_S = 0.05

PHASE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowering",
    "/jax/core/compile/backend_compile_duration": "backend",
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="NOT A MEASUREMENT: run.py's tiny sizes on the CPU")
    args = ap.parse_args(argv)

    from benchmark import run          # its T_START is setup_s's zero
    import jax
    events = []

    def on_duration(event, duration, **_):
        if event in PHASE_OF and duration >= MIN_EVENT_S:
            events.append({"phase": PHASE_OF[event], "seconds": duration,
                           "ended_at": time.perf_counter() - run.T_START})

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        run.main(["--workload", args.workload, "--seed", str(args.seed),
                  "--seconds", str(args.seconds), "--trace", "0"]
                 + ["--rehearse-cpu"] * args.rehearse_cpu)
    line = json.loads(printed.getvalue().strip().splitlines()[-1])
    setup_s = line["metrics"].get("setup_s", {}).get("value")
    if setup_s is None:
        print(json.dumps(line))
        raise SystemExit("the run reported no setup_s")

    from paddle_tpu.observability.recompile import recompile_log
    steps = [e for e in recompile_log().events()
             if e.kind == "jit" and e.compile_ms]
    if not steps:
        raise SystemExit("no recompile event of a to_static step was logged")
    step = steps[0]
    # the event is recorded after the last lowering, before the first call
    call_start = 1e-9 * step.t_ns - run.T_START
    call_s = 1e-3 * step.compile_ms
    # the step's traces are the long ones (a kernel body traced inside one,
    # the initialisers' and the loader's programs before it take far less)
    traces = [e for e in events if e["phase"] == "trace"
              and e["ended_at"] <= call_start]
    longest = max(e["seconds"] for e in traces)
    first = next(e for e in traces if e["seconds"] >= 0.5 * longest)
    before = first["ended_at"] - first["seconds"]
    in_setup = [e for e in events if before < e["ended_at"] <= setup_s]

    def seconds(phase, least=1.0):
        return [round(e["seconds"], 3) for e in in_setup
                if e["phase"] == phase and e["seconds"] >= least]

    nested = [e["seconds"] for e in in_setup if e["phase"] == "trace"
              and e["seconds"] < 1.0 and e["ended_at"] <= call_start]
    out = {
        "workload": args.workload, "seed": args.seed,
        "correct": line["correct"], "setup_s": setup_s,
        "train_tokens_per_s": line["metrics"].get(
            "train_tokens_per_s", {}).get("value"),
        "before_trace_s": before,
        # every pass's trace and lowering, up to the first call
        "passes_s": call_start - before,
        "trace_s": seconds("trace"), "lowering_s": seconds("lowering"),
        # traces inside the step's (a kernel body, an inner jit): how many
        # and their sum
        "nested_traces": [len(nested), round(sum(nested), 3)],
        "backend_s": seconds("backend"),
        "first_call_s": call_s,
        "after_first_call_s": setup_s - call_start - call_s,
        "events": events,
    }
    text = json.dumps(out)
    print(text, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
