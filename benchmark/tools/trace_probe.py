"""Look at one capture by hand: runs a cell traced, keeps the capture, and
writes which planes are devices, which lines they hold, and how the
operations are named (count, summed time, one event's stats per name).

    python benchmark/tools/trace_probe.py --workload <cell> --seed 1 \
        --seconds 4 --out chiprun_out/probe.txt [--rehearse-cpu]
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def describe(path, out, top=60):
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    for plane in prof.planes:
        print(f"PLANE {plane.name!r}", file=out)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            if not plane.name.startswith("/device:"):
                continue
            by_name = {}
            for ev in events:
                rec = by_name.setdefault(ev.name, [0, 0, ev])
                rec[0] += 1
                rec[1] += ev.duration_ns
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1][1])
            for name, (n, ns, ev) in ranked[:top]:
                stats = {k: str(v)[:160] for k, v in ev.stats}
                print(f"    {ns / 1e6:10.3f} ms x{n:5d} {name[:100]!r} "
                      f"{stats}", file=out)
            for name, (n, ns, ev) in ranked[top:]:
                if "kernel" in name or "custom" in name:
                    print(f"    {ns / 1e6:10.3f} ms x{n:5d} {name[:100]!r}",
                          file=out)
            customs = [(name, rec) for name, rec in ranked
                       if " custom-call(" in name and rec[1] > 0]
            for name, (n, ns, ev) in customs[:4] + customs[-2:]:
                print(f"    FULL {name[:4000]!r} "
                      f"{ {k: str(v)[:300] for k, v in ev.stats} }", file=out)


def main():
    from benchmark import harness, run
    argv = sys.argv[1:]
    out_path = argv[argv.index("--out") + 1]
    del argv[argv.index("--out"):argv.index("--out") + 2]
    harness.Capture.discard = lambda self: None        # keep the capture
    run.main(argv + ["--trace", "1"])
    path = harness.Capture(True).xplane_path()
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as out:
        print(f"capture {path} ({os.path.getsize(path)} bytes)", file=out)
        describe(path, out)


if __name__ == "__main__":
    main()
