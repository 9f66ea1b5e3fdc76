#!/usr/bin/env python
"""shardlint CLI — sharding, collective-safety & TPU memory/padding audit.

Unlike tracelint's AST pass, shardlint needs TRACED programs: each audit
target below builds one of the repo's real compiled programs (the GPT
hybrid-parallel train step from models/gpt.py + optimizer/, the serving
engine's bucketed prefill / single decode step from serving/engine.py),
traces it on CPU (shape-only — no TPU time, no compile), and runs the
SL-rule audit from paddle_tpu/analysis/shard_rules.py + cost_audit.py
against a HYPOTHETICAL production mesh.  Sharding facts come from the
dist_spec annotations the model/optimizer attach, so the audit is
meaningful on a single-device host.

Usage:
  python tools/shardlint.py                     # report everything
  python tools/shardlint.py --check             # vs baseline, CI gate
  python tools/shardlint.py --write-baseline
  python tools/shardlint.py --json -            # machine-readable report
  python tools/shardlint.py --rules             # SL rule catalogue
  python tools/shardlint.py --targets gpt_hybrid_train

Exit codes: 0 clean, 1 findings (plain) / NEW findings vs baseline
(--check), 2 usage error.

Suppression: the same `# tracelint: disable=SL201` per-line comments the
AST pass honors — shardlint resolves each finding back to a source line
through the eqn's jax source_info.  The checked-in baseline
(tools/shardlint_baseline.json) holds reviewed findings; `--check`
reports only regressions beyond it.  The JSON report schema is shared
with `tools/tracelint.py --json` (analysis/report.to_json).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# static analysis never takes the chip: the audit is shape-only, so
# the CPU backend is always the right one here
os.environ.setdefault("JAX_PLATFORMS", "cpu")

DEFAULT_BASELINE = os.path.join(REPO, "tools", "shardlint_baseline.json")

# the hypothetical production topology CPU-traced programs are audited
# against (a v5e-pod-slice-shaped dp x tp mesh)
AUDIT_MESH_AXES = {"dp": 8, "tp": 4}


# ------------------------------------------------------------- targets
def _audit_config(analysis, **kw):
    """Thresholds scaled to the tiny CI configs the targets build —
    small enough that the same defect classes fire on a 64-hidden model
    as would on the 1.3B config."""
    base = dict(large_replicated_bytes=1 << 20,
                opt_state_min_bytes=16 << 10,
                allgather_budget_bytes=256 << 20,
                padding_waste_threshold=0.25,
                mxu_min_bytes=16 << 10,
                f32_param_min_bytes=64 << 10)
    base.update(kw)
    return analysis.AuditConfig(**base)


def target_gpt_hybrid_train():
    """The hybrid-parallel flagship: tiny-config GPT (models/gpt.py,
    tp-annotated weights) + AdamW train step traced via to_static,
    audited against the dp x tp production mesh."""
    import numpy as np

    import paddle_tpu as P
    import paddle_tpu.nn.functional as F
    from paddle_tpu import analysis
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny

    P.seed(0)
    # the flagship config as shipped: bf16 activation residency, fused
    # single-pass AdamW, Pallas fused LN (the PR 10 bytes/step work) —
    # the audit covers the program that actually runs, so SL302 tile
    # shapes and SL303 storage findings gate the NEW paths
    cfg = gpt3_tiny(fused_ln=True)
    model = GPTForCausalLM(cfg)
    opt = P.optimizer.AdamW(learning_rate=1e-4,
                            parameters=model.parameters(), fused=True)

    @P.jit.to_static(amp_policy="bf16")
    def train_step(ids, labels):
        opt.clear_grad()
        logits = model(ids)
        loss = F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1]))
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)
    ids = P.to_tensor(rng.integers(0, cfg.vocab_size, (2, 32)),
                      dtype="int64")
    labels = P.to_tensor(rng.integers(0, cfg.vocab_size, (2, 32)),
                         dtype="int64")
    jaxpr, infos = train_step.traced_program(ids, labels)
    mesh = analysis.MeshInfo.of(axes=AUDIT_MESH_AXES)
    findings, rep = analysis.audit_jaxpr(
        jaxpr, where="<gpt_hybrid_train>", inputs=infos, mesh=mesh,
        config=_audit_config(analysis))
    return [("gpt_hybrid_train", findings, rep)]


def target_serving():
    """The serving engine's whole program set (bucketed prefill, the one
    decode step, both sampler widths) audited against the engine's own
    documented page/HBM budget."""
    import paddle_tpu as P
    from paddle_tpu import analysis, serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(0)
    mcfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, dropout=0.0,
                     attention_dropout=0.0)
    engine = serving.LLMEngine(
        GPTForCausalLM(mcfg),
        serving.EngineConfig(max_num_seqs=4, page_size=8, max_model_len=64,
                             prefill_buckets=(16, 32)))
    cfg = _audit_config(analysis,
                        hbm_budget_bytes=engine.hbm_budget_bytes)
    out = []
    for name, jaxpr in engine.audit_programs().items():
        findings, rep = analysis.audit_jaxpr(
            jaxpr, where=f"<serving {name}>", config=cfg)
        out.append((f"serving/{name}", findings, rep))
    engine.shutdown()
    return out


TARGETS = {
    "gpt_hybrid_train": target_gpt_hybrid_train,
    "serving": target_serving,
}


def run_targets(names=None):
    """[(program_name, [Finding], CostReport)] over the chosen targets."""
    results = []
    for name in (names or sorted(TARGETS)):
        if name not in TARGETS:
            raise SystemExit(f"shardlint: unknown target {name!r} "
                             f"(have: {', '.join(sorted(TARGETS))})")
        results.extend(TARGETS[name]())
    return results


def bench_report(targets=("gpt_hybrid_train", "serving")):
    """The bench.py report lane: estimated peak-HBM + MXU padding waste
    per flagship program, next to the finding count — so every BENCH
    run records the static cost picture alongside wall time."""
    t0 = time.time()
    results = run_targets(list(targets))
    out, total = {}, 0
    for name, findings, rep in results:
        total += len(findings)
        key = name.replace("/", "_").replace("gpt_hybrid_train", "gpt")
        out[f"shardlint_{key}_peak_hbm_mb"] = round(
            rep.peak_hbm_bytes / (1 << 20), 3)
        out[f"shardlint_{key}_padding_waste_pct"] = round(
            100.0 * rep.padding_waste, 2)
    out["shardlint_findings"] = total
    out["shardlint_elapsed_s"] = round(time.time() - t0, 2)
    return out


# ----------------------------------------------------------------- CLI
def main(argv=None):
    from paddle_tpu.analysis import common
    from paddle_tpu.analysis.rules import RULES, SHARDLINT_CODES

    ap = argparse.ArgumentParser(
        prog="shardlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--targets", nargs="*", default=None,
                    help=f"audit targets (default: all — "
                         f"{', '.join(sorted(TARGETS))})")
    common.add_baseline_args(ap, DEFAULT_BASELINE)
    ap.add_argument("--rules", action="store_true",
                    help="print the SL rule catalogue and exit")
    args = ap.parse_args(argv)

    if args.rules:
        return common.print_rules(RULES, codes=set(SHARDLINT_CODES))

    t0 = time.time()
    results = run_targets(args.targets)
    elapsed = time.time() - t0
    findings = [f for _, fs, _ in results for f in fs]

    if not args.write_baseline:
        for name, fs, rep in results:
            d = rep.to_dict()
            print(f"== {name}: peak HBM {d['peak_hbm_mb']} MiB (est), "
                  f"padding waste {d['padding_waste_pct']}%, "
                  f"{len(fs)} finding(s)")
    return common.run_baseline_flow(
        findings, args, tool="shardlint", repo=REPO, elapsed=elapsed,
        json_extra={"programs": {name: rep.to_dict()
                                 for name, _, rep in results}})


if __name__ == "__main__":
    sys.exit(main())
