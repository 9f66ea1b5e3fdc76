"""What the host does in an engine step, phase by phase, and what the device
waited on.  For one capture — a traced run of a cell, or a file — prints:

- the median self time per engine step of every span, by name (steps that
  only decode and steps that hold a prefill apart);
- idle seconds by span under the linked placement (``launches``: every gap
  laid on the host's clock at the launch of the program that ended it), and
  the rows' sum against the window's idle;
- how the modules were tied to their launches, the unlinked share and the
  anchor spread;
- ``host_exposed_ms.serve``, ``pass_device_ms.serve``, ``launch_ms.serve``
  beside what the offset-corrected clock of ``hostspans`` reads, where it
  reads;
- why ``hostspans.offset_point`` reads or refuses: the counts of launches,
  modules and ``=>Done`` events, the share of modules whose ``run_id``
  matches a launch, and the bracket ``lo`` / ``hi`` over the first and the
  last third of the window.

    python benchmark/tools/host_phases.py --workload <cell> --seed 1 \
        --seconds 30 [--out chiprun_out/phases_<cell>.json]
    python benchmark/tools/host_phases.py --capture <file.xplane.pb>

A run prints the result line's metrics and the end-to-end numbers taken
under the capture too.  The capture is kept (``.cache/benchmark_trace``)
until the next traced run.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def step_phases(spans):
    """{"decode" | "prefill": {"steps", "step_ms", "self_ms": {name: median
    self ms a step}}} over the capture's ``serving.step`` spans."""
    from benchmark import hostspans, stats
    per = {"decode": [], "prefill": []}
    for s in spans:
        if s.name != "serving.step":
            continue
        own = {}
        for x in (s, *s.descendants()):
            own[x.name] = own.get(x.name, 0.0) + hostspans.self_time(x)
        per["prefill" if "serving.prefill" in own else "decode"].append(
            (s.seconds, own))
    out = {}
    for kind, steps in per.items():
        if not steps:
            continue
        names = sorted({n for _d, own in steps for n in own})
        out[kind] = {
            "steps": len(steps),
            "step_ms": 1e3 * stats.median([d for d, _own in steps]),
            "self_ms": {n: 1e3 * stats.median([own.get(n, 0.0)
                                               for _d, own in steps])
                        for n in names}}
    return out


def offset_diagnosis(profile):
    """The inputs of ``hostspans.clock_offset`` and its bracket over the
    whole window and over its first and last thirds (ms)."""
    from benchmark import hostspans as hs
    mods = hs._modules(profile)
    launches = hs._events(profile, hs.LAUNCH)
    dones = hs._events(profile, hs.DONE)
    by_run = {st["run_id"]: s for s, _e, st in launches
              if st.get("run_id") is not None}
    rep = {"launches": len(launches), "modules": len(mods),
           "dones": len(dones),
           "run_id_matched": (sum(st.get("run_id") in by_run
                                  for _s, _e, st in mods) / len(mods)
                              if mods else None)}
    bracket = hs.clock_offset(profile)
    rep["bracket_ms"] = (None if bracket is None
                         else [bracket[0] / 1e6, bracket[1] / 1e6])
    rep["offset_point"] = ("no events" if bracket is None else
                           "refused: lo > hi" if bracket[0] > bracket[1]
                           else "reads")
    if not mods or not by_run or not dones:
        return rep
    t0, t1 = mods[0][0], mods[-1][1]
    starts = [d[0] for d in dones]
    for label, a, b in (("first_third", t0, t0 + (t1 - t0) / 3),
                        ("last_third", t1 - (t1 - t0) / 3, t1)):
        idx = [i for i, m in enumerate(mods) if a <= m[0] < b]
        pairs = [by_run[mods[i][2]["run_id"]] - mods[i][0] for i in idx
                 if mods[i][2].get("run_id") in by_run]
        if not pairs:
            continue
        lo = max(pairs)
        if len(dones) == len(mods):
            hi = min(dones[i][0] - mods[i][1] for i in idx)
        else:
            slack = []
            for i in idx:
                k = bisect.bisect_left(starts, mods[i][1] + lo)
                if k < len(starts):
                    slack.append(starts[k] - mods[i][1])
            hi = min(slack) if slack else None
        rep[label] = [lo / 1e6, None if hi is None else hi / 1e6]
    return rep


def report(profile):
    """Everything this tool prints, as one dict."""
    from benchmark import harness, hostspans, launches, stats
    spans = hostspans.host_spans(profile)
    busy = hostspans.device_busy(profile)
    window = (busy[-1][1] - busy[0][0]) / 1e9 if busy else None
    idle = (window - sum(e - s for s, e in busy) / 1e9) if busy else None
    rep = {"window_s": window, "idle_s": idle,
           "phases": step_phases(spans),
           "linked_gaps": launches.idle_table(profile),
           "linkage": launches.linkage(profile),
           "offset": offset_diagnosis(profile)}
    readings = {}
    for name in ("host_exposed_ms.serve", "pass_device_ms.serve",
                 "launch_ms.serve"):
        reader = harness.load_module("layer_metrics", name)
        readings[name] = _reading(reader, profile)
    # the same two quantities on hostspans' offset-corrected clock
    steps = [s for s in spans if s.name == "serving.step"
             and not any(c.name == "serving.prefill"
                         for c in s.descendants())]
    busy_in = hostspans.busy_seconds_inside(profile, steps) if steps else None
    if busy_in:
        readings["offset: engine_host_ms"] = 1e3 * stats.median(
            [s.seconds - b for s, b in zip(steps, busy_in)])
    passes = [s for s in spans if s.name == "serving.decode"]
    busy_in = (hostspans.busy_seconds_inside(profile, passes)
               if passes else None)
    if busy_in:
        readings["offset: busy ms in serving.decode"] = 1e3 * stats.median(
            busy_in)
    rep["readings"] = readings
    return rep


def _reading(reader, profile):
    from benchmark import hostspans
    keep = hostspans.load_current
    hostspans.load_current = lambda: profile
    try:
        return reader.read({})
    finally:
        hostspans.load_current = keep


def show(rep, out=sys.stdout):
    if rep["window_s"] is None:
        print("no device operation in the capture", file=out)
        return
    print(f"traced window {rep['window_s']:.4f} s, device idle "
          f"{rep['idle_s']:.6f} s "
          f"({100 * rep['idle_s'] / rep['window_s']:.3f}%)", file=out)
    for kind, ph in rep["phases"].items():
        print(f"\n{kind} steps: {ph['steps']}, median {ph['step_ms']:.4f} ms;"
              f" median self ms a step by span:", file=out)
        for name, ms in sorted(ph["self_ms"].items(), key=lambda kv: -kv[1]):
            print(f"  {name:28s} {ms:10.4f}", file=out)
    gaps = rep["linked_gaps"]
    print("\nidle put down to (linked placement):", file=out)
    if gaps is None:
        print("  nothing read: no module tied to a launch", file=out)
    else:
        for name, secs, n, longest in gaps:
            print(f"  {name:28s} {secs:10.6f} s {n:7d} gaps, longest "
                  f"{longest:.4f} ms", file=out)
        total = sum(r[1] for r in gaps)
        print(f"  {'sum':28s} {total:10.6f} s "
              f"({100 * total / rep['idle_s']:.3f}% of the window's idle)",
              file=out)
    print(f"\nlinkage: {json.dumps(rep['linkage'])}", file=out)
    print(f"offset (hostspans): {json.dumps(rep['offset'])}", file=out)
    print("readings:", file=out)
    for name, value in rep["readings"].items():
        print(f"  {name:36s} {value}", file=out)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--capture", help="read this .xplane.pb; run nothing")
    ap.add_argument("--out", help="also write the report here as JSON")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    from benchmark import harness, hostspans
    path, run_rep = args.capture, None
    if path is None:
        from benchmark import run
        harness.Capture.discard = lambda self: None        # keep the capture
        argv = ["--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", "1"]
        manifest, ctx, runner = run.open_cell(run.parse_args(
            argv + (["--rehearse-cpu"] if args.rehearse_cpu else [])))
        out = runner.run(ctx)
        line = run.finish(ctx, manifest, out)
        run_rep = {"correct": line["correct"],
                   "end_to_end_under_capture": out["end_to_end"],
                   "metrics": {k: v["value"]
                               for k, v in line["metrics"].items()},
                   "breakdown_idle_gaps": (line.get("breakdown") or {}).get(
                       "idle_gaps")}
        print(json.dumps(run_rep), flush=True)
        path = harness.Capture(True).xplane_path()
    if path is None:
        raise SystemExit("the run left no capture")
    rep = report(hostspans.load(path))
    show(rep)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(dict(rep, run=run_rep), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
