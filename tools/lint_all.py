#!/usr/bin/env python
"""lint_all — the one-exit-code gate CI runs.

Chains every baseline-gated analyzer in the repo, plus the chaos suite:

  1. tracelint  --check paddle_tpu examples   (AST trace-safety, TLxxx)
  2. shardlint  --check                       (sharding/memory audit, SLxxx)
  3. racelint   --check paddle_tpu            (host concurrency audit, RLxxx)
  4. numlint    --check                       (numerics & precision-flow
                                               audit over the traced
                                               flagship + serving
                                               programs, NLxxx)
  5. kernlint   --check                       (Pallas kernel-interior
                                               audit: tile alignment,
                                               VMEM budgets, in-kernel
                                               numerics, alias hazards,
                                               grid coverage, ragged
                                               tails — KLxxx over the
                                               flagship + serving + each
                                               ops/pallas kernel traced
                                               standalone)
  6. protolint  --check paddle_tpu            (coordination-KV protocol
                                               audit: key leaks, consume-
                                               without-delete, unbounded
                                               blocking gets, cross-role
                                               wait cycles, liveness
                                               budgets, error envelopes,
                                               seq reuse — PLxxx)
  7. perfgate   --check                       (deterministic cost-model
                                               perf budgets: bytes/flops
                                               per step, padding waste,
                                               compile bounds vs
                                               tools/perf_baseline.json)
  8. api_coverage --baseline                  (public-surface regressions)
  9. pytest -m chaos                          (deterministic fault-injection
                                               acceptance proofs, run under
                                               the racelint lock-order
                                               tracer — tests/conftest.py
                                               arms it for chaos-marked
                                               tests and fails on any
                                               dynamic order violation;
                                               since PR 14 this includes
                                               the fleet suite: the
                                               threaded reconfigure ladder
                                               in tests/test_fleet.py and
                                               the REAL 3-process
                                               SIGKILL→reconfigure→resume
                                               proof in tests/
                                               test_distributed_multiprocess
                                               .py — measured ~25-35s,
                                               budgeted inside the gate's
                                               480s wall-time cap)

The static gates compare against their checked-in baselines and fail
only on REGRESSIONS; the chaos gate re-proves the resilience contracts
(torn-checkpoint + preemption training resume matches the fault-free
trajectory; serving pool-exhaustion + mid-decode-fault recovery stays
token-identical under the compile bound — docs/resilience.md; the
multi-host serving fleet keeps streams exactly-once and output
token-identical through SIGKILL and SIGSTOP-wedge failovers —
docs/serving.md "Multi-host fleet").  So
`python tools/lint_all.py` exits 0 on a healthy tree and nonzero the
moment any gate slips.  The `lint`-marked pytest test
(tests/test_lint_all.py) shells out to this script, which is how tier-1
enforces every gate at once.  The chaos gate deselects itself there via
`-m "chaos"` targeting only tests/test_resilience.py — chaos tests
carry no `lint` marker, so the recursion terminates.

Usage: python tools/lint_all.py
       [--skip tracelint shardlint racelint numlint kernlint protolint
        perfgate coverage chaos]
       [--only <gate> [<gate> ...]]
       [--json FILE|-]   one unified {"tool": "lint_all", "gates":
                         {gate: {ok, findings, elapsed_s}}} document —
                         `findings` parsed from a gate's own summary
                         line where it prints one, else null
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")

GATES = {
    "tracelint": [sys.executable, os.path.join(TOOLS, "tracelint.py"),
                  "--check", "paddle_tpu", "examples"],
    "shardlint": [sys.executable, os.path.join(TOOLS, "shardlint.py"),
                  "--check"],
    "racelint": [sys.executable, os.path.join(TOOLS, "racelint.py"),
                 "--check", "paddle_tpu"],
    "numlint": [sys.executable, os.path.join(TOOLS, "numlint.py"),
                "--check"],
    "kernlint": [sys.executable, os.path.join(TOOLS, "kernlint.py"),
                 "--check"],
    "protolint": [sys.executable, os.path.join(TOOLS, "protolint.py"),
                  "--check", "paddle_tpu"],
    "perfgate": [sys.executable, os.path.join(TOOLS, "perfgate.py"),
                 "--check"],
    "coverage": [sys.executable, os.path.join(TOOLS, "api_coverage.py"),
                 "--baseline",
                 os.path.join(TOOLS, "api_coverage_baseline.json")],
    # scoped to the chaos-bearing files: `-m chaos` over the whole tree
    # would pay full collection, and -p no:cacheprovider keeps gate
    # runs from racing tier-1's .pytest_cache
    "chaos": [sys.executable, "-m", "pytest", "-q", "-m", "chaos",
              "-p", "no:cacheprovider",
              os.path.join(REPO, "tests", "test_resilience.py"),
              os.path.join(REPO, "tests", "test_fleet.py"),
              os.path.join(REPO, "tests", "test_sentinel.py"),
              os.path.join(REPO, "tests", "test_serving_fleet.py"),
              os.path.join(REPO, "tests", "test_traffic.py"),
              os.path.join(REPO, "tests",
                           "test_distributed_multiprocess.py")],
}

# per-gate wall budgets: the static gates are seconds, but the chaos
# gate now spawns a real 3-process fleet (2 rendezvous + a SIGKILL
# detection window) — measured ~25-35s for the fleet half, capped with
# generous headroom for cold CI boxes
_GATE_TIMEOUT_S = {"chaos": 480}
_DEFAULT_TIMEOUT_S = 300

# the analyzers' shared summary line: "{tool}: N finding(s) ..."
_FINDINGS_RE = re.compile(r"^\w+: (\d+) finding\(s\)", re.MULTILINE)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="lint_all", description=__doc__)
    ap.add_argument("--skip", nargs="*", default=(),
                    choices=sorted(GATES), help="gates to skip")
    ap.add_argument("--only", nargs="*", default=None,
                    choices=sorted(GATES),
                    help="run ONLY these gates (everything else is "
                         "reported as SKIPPED)")
    ap.add_argument("--json", metavar="FILE", default=None,
                    help="also write the unified per-gate report as "
                         "JSON ('-' for stdout)")
    args = ap.parse_args(argv)

    if args.only is not None and not args.only:
        # `--only` with no gates (e.g. an empty shell variable) would
        # skip EVERYTHING and still print "all gates clean" — a false
        # green; fail fast instead
        ap.error("--only requires at least one gate")

    doc = {"tool": "lint_all", "version": 1, "gates": {}}
    failures = []
    for name, cmd in GATES.items():
        if name in args.skip or \
                (args.only is not None and name not in args.only):
            print(f"-- {name}: SKIPPED")
            doc["gates"][name] = {"ok": None, "findings": None,
                                  "elapsed_s": 0.0, "skipped": True}
            continue
        t0 = time.time()
        budget = _GATE_TIMEOUT_S.get(name, _DEFAULT_TIMEOUT_S)
        try:
            # a gate that hangs must FAIL, not hang CI
            proc = subprocess.run(cmd, cwd=REPO, capture_output=True,
                                  text=True, timeout=budget)
        except subprocess.TimeoutExpired:
            print(f"-- {name}: FAIL (timed out after {budget}s)")
            failures.append(name)
            doc["gates"][name] = {"ok": False, "findings": None,
                                  "elapsed_s": round(time.time() - t0, 2),
                                  "error": "timeout"}
            continue
        elapsed = time.time() - t0
        status = "ok" if proc.returncode == 0 else f"FAIL rc={proc.returncode}"
        print(f"-- {name}: {status} in {elapsed:.1f}s")
        m = _FINDINGS_RE.search(proc.stdout)
        doc["gates"][name] = {
            "ok": proc.returncode == 0,
            "findings": int(m.group(1)) if m else None,
            "elapsed_s": round(elapsed, 2),
        }
        if proc.returncode != 0:
            failures.append(name)
            sys.stdout.write(proc.stdout)
            sys.stderr.write(proc.stderr)

    if args.json:
        if args.json == "-":
            json.dump(doc, sys.stdout, indent=1)
            print()
        else:
            with open(args.json, "w", encoding="utf-8") as fh:
                json.dump(doc, fh, indent=1)
                fh.write("\n")

    if failures:
        print(f"lint_all: FAILED ({', '.join(failures)})")
        return 1
    print("lint_all: all gates clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
