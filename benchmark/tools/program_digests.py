"""What a serving cell's engine would compile, by identity: the digest of
every program's jaxpr (``LLMEngine.audit_programs()``: traced at the cell's
own sizes, nothing compiled or run) and the engine's AOT fingerprint.  Two
trees whose digests and fingerprint agree for a cell run the same programs
there, whatever else changed round them.

    python benchmark/tools/program_digests.py --workload <cell> \
        [--rehearse-cpu] [--out chiprun_out/x.json]

The digests are of the jaxprs' text under one jax version: compare trees in
one container.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    ap.add_argument("--rehearse-cpu", action="store_true")
    a = ap.parse_args()
    from benchmark import run
    argv = ["--workload", a.workload, "--seed", str(a.seed), "--seconds", "1"]
    if a.rehearse_cpu:
        argv.append("--rehearse-cpu")
    _manifest, ctx, runner = run.open_cell(run.parse_args(argv))
    _model, engine = runner.build(ctx)
    found = {"workload": a.workload,
             "fingerprint": engine.program_fingerprint,
             "attention_path": engine.attention_path,
             "programs": {
                 name: hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
                 for name, jaxpr in sorted(engine.audit_programs().items())}}
    whole = hashlib.sha256(json.dumps(found["programs"], sort_keys=True)
                           .encode()).hexdigest()[:16]
    found["all_programs"] = whole
    print(json.dumps(found), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(found, f, indent=1)


if __name__ == "__main__":
    main()
