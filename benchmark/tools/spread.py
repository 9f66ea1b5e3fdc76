"""Spreads of a cell's runs, as the contract measures them: for each metric
and each set, the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; the wider
of the two sets is what a bound is set from (about five times it).

    python benchmark/tools/spread.py chiprun_out/sets_<cell>.jsonl

Each line of the file: {"set": 1|2, "seed": n, "line": <the run's result>}.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values):
    """The set without its run farthest from the median (the driver's
    measure of tightness)."""
    med = statistics.median(values)
    far = max(values, key=lambda v: abs(v - med))
    rest = list(values)
    rest.remove(far)
    return rest


def main(path):
    sets = {}
    for raw in open(path):
        rec = json.loads(raw)
        if not rec["line"].get("correct"):
            print("NOT CORRECT:", rec["seed"], rec["line"].get("compared"))
        for name, m in rec["line"]["metrics"].items():
            sets.setdefault(name, {}).setdefault(rec["set"], []).append(
                m["value"])
    for name, by_set in sets.items():
        row = []
        for k in sorted(by_set):
            v = by_set[k]
            first = "" if name != "setup_s" else f" first {v[0]:.1f}"
            if name == "setup_s":
                v = v[1:]
            row.append(f"set{k}: n={len(v)} median {statistics.median(v):.6g} "
                       f"spread {100 * spread(v):.3f}% trimmed "
                       f"{100 * spread(trimmed(v)):.3f}%{first}")
        print(f"{name}: " + " | ".join(row))


if __name__ == "__main__":
    main(sys.argv[1])
