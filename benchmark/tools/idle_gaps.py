"""One traced run of a cell, then what the host did in the device's idle
gaps: the clock offset bracket, the gap table (``breakdown.idle_gaps`` holds
its first two columns), every span's count, median and self time, and how
full the engine ran (the ``live``, ``pages_live`` and ``waiting`` that
``serving.decode`` and ``serving.step`` carry).

    python benchmark/tools/idle_gaps.py --workload <cell> --seed 1 \
        --seconds 30 [--out chiprun_out/gaps_<cell>.json]

The capture is kept (``.cache/benchmark_trace``) until the next traced run.
``--capture <file.xplane.pb>`` reads a capture that is there and runs nothing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def report(profile):
    """Everything this tool prints, as one dict."""
    from benchmark import hostspans, stats
    bracket = hostspans.clock_offset(profile)
    busy = hostspans.device_busy(profile)
    gaps = hostspans.idle_gaps(profile)
    by_name = {}
    host_spans = hostspans.host_spans(profile)
    for s in host_spans:
        rec = by_name.setdefault(s.name, {"count": 0, "ms": [], "self_s": 0.0})
        rec["count"] += 1
        rec["ms"].append(1e3 * s.seconds)
        rec["self_s"] += hostspans.self_time(s)
    spans = {name: {"count": r["count"], "median_ms": stats.median(r["ms"]),
                    "max_ms": max(r["ms"]), "total_s": sum(r["ms"]) / 1e3,
                    "self_s": r["self_s"]}
             for name, r in sorted(by_name.items())}
    window = (busy[-1][1] - busy[0][0]) / 1e9 if busy else None
    idle = (window - sum(e - s for s, e in busy) / 1e9) if busy else None
    return {"offset_ms": (None if bracket is None
                          else [bracket[0] / 1e6, bracket[1] / 1e6]),
            "window_s": window, "idle_s": idle, "idle_gaps": gaps,
            "occupancy": occupancy(host_spans), "spans": spans}


def occupancy(spans):
    """How full the engine ran, from the spans' own attributes: the share of
    ``serving.decode`` spans at the widest batch seen, the mean pages their
    attention had to read, and the mean ``waiting`` of ``serving.step`` in
    each third of the capture.  ``None`` where no engine step was traced."""
    decode = [s for s in spans if s.name == "serving.decode"]
    steps = [s for s in spans if s.name == "serving.step"]
    if not decode or not steps:
        return None
    live = [int(s.stats.get("live", 0)) for s in decode]
    pages = [int(s.stats.get("pages_live", 0)) for s in decode]
    t0, t1 = steps[0].start, steps[-1].end
    thirds = [[], [], []]
    for s in steps:
        k = min(2, int(3 * (s.start - t0) / max(t1 - t0, 1)))
        thirds[k].append(int(s.stats.get("waiting", 0)))
    widest = max(live)
    return {"decode_spans": len(decode), "widest_live": widest,
            "share_at_widest": live.count(widest) / len(live),
            "mean_live": sum(live) / len(live),
            "mean_pages_live": sum(pages) / len(pages),
            "waiting_by_thirds": [sum(t) / len(t) if t else None
                                  for t in thirds]}


def show(rep, out=sys.stdout):
    if rep["offset_ms"] is None:
        print("clock offset: not to be had from this capture", file=out)
    else:
        lo, hi = rep["offset_ms"]
        print(f"clock offset (device lines lead the host's): lo {lo:.4f} ms, "
              f"hi {hi:.4f} ms, width {hi - lo:.4f} ms; corrected by lo",
              file=out)
    if rep["window_s"] is not None:
        print(f"traced window {rep['window_s']:.4f} s, device idle "
              f"{rep['idle_s']:.6f} s "
              f"({100 * rep['idle_s'] / rep['window_s']:.3f}%)", file=out)
    gaps = rep["idle_gaps"]
    if gaps is None:
        print("idle gaps: nothing read", file=out)
    else:
        print(f"{'gap put down to':28s} {'seconds':>10s} {'count':>7s} "
              f"{'longest ms':>11s}", file=out)
        for name, secs, n, longest in gaps:
            print(f"{name:28s} {secs:10.6f} {n:7d} {longest:11.4f}", file=out)
        print(f"{'sum':28s} {sum(r[1] for r in gaps):10.6f}", file=out)
        if all(r[0] == "short" for r in gaps):
            print("no gap reaches the threshold: every idle interval is "
                  "under `short`", file=out)
    occ = rep["occupancy"]
    if occ is not None:
        print(f"engine: {occ['decode_spans']} serving.decode spans, "
              f"{100 * occ['share_at_widest']:.2f}% of them with "
              f"{occ['widest_live']} slots live (mean {occ['mean_live']:.2f}),"
              f" mean pages_live {occ['mean_pages_live']:.1f}; waiting by "
              f"thirds {occ['waiting_by_thirds']}", file=out)
    print(f"{'span':28s} {'count':>7s} {'median ms':>10s} {'max ms':>10s} "
          f"{'total s':>9s} {'self s':>9s}", file=out)
    for name, r in rep["spans"].items():
        print(f"{name:28s} {r['count']:7d} {r['median_ms']:10.4f} "
              f"{r['max_ms']:10.4f} {r['total_s']:9.4f} {r['self_s']:9.4f}",
              file=out)


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
    path = args.capture
    if path is None:
        from benchmark import run
        harness.Capture.discard = lambda self: None        # keep the capture
        argv = ["--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", "1"]
        run.main(argv + (["--rehearse-cpu"] if args.rehearse_cpu else []))
        path = harness.Capture(True).xplane_path()
    if path is None:
        raise SystemExit("the run left no capture")
    rep = report(hostspans.load(path))
    show(rep)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
