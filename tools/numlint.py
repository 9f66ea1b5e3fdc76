#!/usr/bin/env python
"""numlint CLI — numerics & precision-flow audit of the traced programs.

shardlint asks whether the flagship programs SCALE; numlint asks
whether their NUMBERS survive: a dtype-provenance dataflow pass
(paddle_tpu/analysis/dtype_flow.py) over the same traced jaxprs, judged
by the NL rule catalog (analysis/num_rules.py) —

- NL1xx precision loss: narrow-dtype accumulation in reductions and
  dot contractions (NL101), f32->bf16->f32 double-rounding round trips
  (NL102), narrow master weights / moments without the moment_dtype
  opt-in (NL103);
- NL2xx stability: unstabilized exp/log/div/rsqrt on narrow dtypes
  (NL201), scan carries narrower than their body math (NL202);
- NL3xx quantization readiness: int8/fp8 codes consumed scale-free
  (NL301) and dequant->requant chains that should fuse (NL302) —
  written against HYPOTHETICAL quantized pools so the rules gate
  ROADMAP item 2's KV-quantization PR before it lands.

Audit targets: the optimized gpt_hybrid_train step (perfgate's shared
builder — bf16 activation residency, fused AdamW, Pallas fused LN: the
program that ships), every serving-engine program via
`LLMEngine.audit_programs()`, the same serving set at bf16-residency
pool dtype (`serving_bf16`), and the set over per-page-scaled int8 KV
pools (`serving_quant` — EngineConfig(kv_cache_dtype="int8"), the
quantized plane ROADMAP item 2 shipped; docs/quantization.md).

Usage:
  python tools/numlint.py                     # report everything
  python tools/numlint.py --check             # vs baseline, CI gate
  python tools/numlint.py --write-baseline
  python tools/numlint.py --diff              # per-rule counts vs baseline
  python tools/numlint.py --json -            # machine-readable report
  python tools/numlint.py --rules             # NL rule catalogue
  python tools/numlint.py --targets gpt_hybrid_train

Exit codes: 0 clean, 1 findings (plain) / NEW findings vs baseline
(--check), 2 usage error.

Suppression: the same `# tracelint: disable=NL101` per-line comments
the other analyzers honor (`# numlint: disable=...` is an accepted
alias, scoped to NL codes).  The checked-in baseline
(tools/numlint_baseline.json) holds the reviewed findings — today the
flagship's forward/activation-cotangent bf16 dots, which stay in
residency dtype by design (the MXU accumulates them wide in hardware;
docs/numlint.md records the rationale).  `--check` reports only
regressions beyond it.  Deliberate narrow accumulation registers once
via `core.dispatch.allow_narrow_accum`.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(1, os.path.join(REPO, "tools"))

# static analysis never takes the chip: the audit is shape-only, so
# the CPU backend is always the right one here
os.environ.setdefault("JAX_PLATFORMS", "cpu")

DEFAULT_BASELINE = os.path.join(REPO, "tools", "numlint_baseline.json")


def _audit_config(analysis):
    """Thresholds scaled to the tiny CI configs the targets build —
    the flagship contracts over 64 tokens where the 1.3B config
    contracts over thousands, so the same defect classes fire (the
    shardlint `_audit_config` pattern)."""
    return analysis.NumConfig(reduce_min_elems=32)


# ------------------------------------------------------------- targets
def target_gpt_hybrid_train():
    """The optimized flagship train step (perfgate's shared builder:
    bf16 activation residency + fused AdamW + Pallas fused LN), traced
    via traced_program — the one numlint self-audit that found (and PR
    12 fixed) the narrow weight-/bias-grad accumulations."""
    from perfgate import build_gpt_train_step

    from paddle_tpu import analysis

    train_step, ids, labels = build_gpt_train_step(optimized=True)
    jaxpr, infos = train_step.traced_program(ids, labels)
    findings = analysis.check_numerics(
        jaxpr, where="<gpt_hybrid_train>", inputs=infos,
        config=_audit_config(analysis))
    return [("gpt_hybrid_train", findings)]


def _serving_targets(dtype_name, label, kv_cache_dtype=None):
    import jax.numpy as jnp

    import paddle_tpu as P
    from paddle_tpu import analysis, serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(0)
    mcfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, dropout=0.0,
                     attention_dropout=0.0)
    engine = serving.LLMEngine(
        GPTForCausalLM(mcfg),
        serving.EngineConfig(max_num_seqs=4, page_size=8,
                             max_model_len=64, prefill_buckets=(16, 32),
                             dtype=getattr(jnp, dtype_name),
                             kv_cache_dtype=kv_cache_dtype))
    cfg = _audit_config(analysis)
    out = []
    try:
        for name, jaxpr in engine.audit_programs().items():
            findings = analysis.check_numerics(
                jaxpr, where=f"<{label} {name}>", config=cfg)
            out.append((f"{label}/{name}", findings))
    finally:
        engine.shutdown()
    return out


def target_serving():
    """Every serving program at the default f32 pool dtype."""
    return _serving_targets("float32", "serving")


def target_serving_bf16():
    """The same program set at bf16 pool residency — the dtype plane
    ROADMAP item 2's KV quantization starts from.  The attention cores
    accumulate wide under it (PR 12's serving fix); this target keeps
    that invariant gated before the quantized pools land."""
    return _serving_targets("bfloat16", "serving_bf16")


def target_serving_quant():
    """The serving program set over per-page-scaled int8 KV pools
    (EngineConfig(kv_cache_dtype="int8") — the quantized plane ROADMAP
    item 2 shipped).  The NL3xx rules were written against hypothetical
    quantized pools BEFORE this plane landed; here they audit the real
    thing: every dequant must ride adjacent to its per-page scale
    (NL301) and the only dequant->requant chain is the documented
    page-rescale-on-append (NL302-silent by construction, see
    docs/quantization.md).  Zero findings, zero baseline growth."""
    return _serving_targets("float32", "serving_quant",
                            kv_cache_dtype="int8")


TARGETS = {
    "gpt_hybrid_train": target_gpt_hybrid_train,
    "serving": target_serving,
    "serving_bf16": target_serving_bf16,
    "serving_quant": target_serving_quant,
}


def run_targets(names=None):
    """[(program_name, [Finding])] over the chosen targets."""
    results = []
    for name in (names or sorted(TARGETS)):
        if name not in TARGETS:
            raise SystemExit(f"numlint: unknown target {name!r} "
                             f"(have: {', '.join(sorted(TARGETS))})")
        results.extend(TARGETS[name]())
    return results


def bench_report(targets=None):
    """The bench.py --worker-numlint lane: finding count + per-rule
    breakdown over the flagship programs, so every BENCH run records
    the numerics-hazard picture next to the cost audit."""
    t0 = time.time()
    results = run_targets(targets)
    breakdown = {}
    for _name, findings in results:
        for f in findings:
            breakdown[f.code] = breakdown.get(f.code, 0) + 1
    return {
        "numlint_finding_count": sum(len(fs) for _, fs in results),
        "numlint_rule_breakdown": dict(sorted(breakdown.items())),
        "numlint_elapsed_s": round(time.time() - t0, 2),
    }


# ----------------------------------------------------------------- CLI
def main(argv=None):
    from paddle_tpu.analysis import common
    from paddle_tpu.analysis.rules import NUMLINT_CODES, RULES

    ap = argparse.ArgumentParser(
        prog="numlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--targets", nargs="*", default=None,
                    help=f"audit targets (default: all — "
                         f"{', '.join(sorted(TARGETS))})")
    common.add_baseline_args(ap, DEFAULT_BASELINE)
    ap.add_argument("--rules", action="store_true",
                    help="print the NL rule catalogue and exit")
    args = ap.parse_args(argv)

    if args.rules:
        return common.print_rules(RULES, codes=set(NUMLINT_CODES))

    t0 = time.time()
    results = run_targets(args.targets)
    elapsed = time.time() - t0
    findings = [f for _, fs in results for f in fs]

    if not args.write_baseline and not args.diff:
        for name, fs in results:
            print(f"== {name}: {len(fs)} finding(s)")
    return common.run_baseline_flow(
        findings, args, tool="numlint", repo=REPO, elapsed=elapsed)


if __name__ == "__main__":
    sys.exit(main())
