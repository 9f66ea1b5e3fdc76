#!/usr/bin/env python
"""kernlint CLI — KLxxx static audit of Pallas kernel INTERIORS.

Every sibling analyzer stops at the ``pallas_call`` boundary (numlint's
dtype_flow documents the body as deliberately opaque; the roofline
profiler costs call-boundary bytes only).  kernlint walks through it:
the kernel jaxpr, the grid, and every in/out BlockSpec are all in
``eqn.params``, so tile alignment, the VMEM bill, in-kernel
accumulation dtypes, alias hazards, grid coverage and ragged tails are
all decidable at trace time — before XLA or Mosaic ever see the kernel
(see paddle_tpu/analysis/kernel_rules.py and docs/kernlint.md):

- KL101 block shape not a multiple of the dtype's native TPU tile
  ((8,128) f32 / (16,128) bf16 / (32,128) int8);
- KL102 static per-call VMEM footprint (block buffers, double-buffering
  and scratch — analysis/vmem_model.py) over the ChipSpec budget;
- KL103 narrow (bf16/f16) accumulation inside the kernel body — a dot
  without preferred_element_type=f32, a narrow reduction, a narrow
  `+=` ref carry;
- KL104 input_output_aliases hazards — shape/dtype mismatch across the
  alias, aliased input read after the aliased output stored;
- KL105 grid x block under-covers an operand, or overlapping index
  maps double-write an output block on non-consecutive steps;
- KL106 a partial final block read with no @pl.when / iota guard —
  the exact hazard class ROADMAP item 1's ragged paged-attention
  kernel lives in.

Audit targets: the optimized gpt_hybrid_train step (perfgate's shared
builder — the Pallas kernels as the flagship actually invokes them),
every serving-engine program via ``LLMEngine.audit_programs()``
(pure-JAX today — pre-gating item 1's serving kernel), each
``ops/pallas`` kernel traced STANDALONE in interpret mode (flash,
block-sparse, ring, norm, optim — every code path, not just the ones
the flagship picks), and ``pallas_source`` — the trace-free AST pass
over ``ops/pallas/*.py``.

Usage:
  python tools/kernlint.py                     # report everything
  python tools/kernlint.py --check             # vs baseline, CI gate
  python tools/kernlint.py --write-baseline
  python tools/kernlint.py --diff              # per-rule counts vs baseline
  python tools/kernlint.py --json -            # machine-readable report
  python tools/kernlint.py --rules             # KL rule catalogue
  python tools/kernlint.py --targets norm optim

Exit codes: 0 clean, 1 findings (plain) / NEW findings vs baseline
(--check), 2 usage error.

Suppression: the same `# tracelint: disable=KL101` per-line comments
the other analyzers honor (`# kernlint: disable=...` is an accepted
alias, scoped to KL codes — no foreign spelling can waive a KL
finding, and a kernlint-spelled comment waives nothing else).  The
checked-in baseline (tools/kernlint_baseline.json) holds the reviewed
findings; `--check` reports only regressions beyond it.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(1, os.path.join(REPO, "tools"))

# static analysis never takes the chip: every target traces in
# interpret mode, so the CPU backend is always right here
os.environ.setdefault("JAX_PLATFORMS", "cpu")

DEFAULT_BASELINE = os.path.join(REPO, "tools", "kernlint_baseline.json")


# ------------------------------------------------------------- targets
def target_gpt_hybrid_train():
    """The optimized flagship train step (perfgate's shared builder:
    bf16 activation residency + fused AdamW + Pallas fused LN) — the
    kernels exactly as the program that ships invokes them."""
    from perfgate import build_gpt_train_step

    from paddle_tpu import analysis

    train_step, ids, labels = build_gpt_train_step(optimized=True)
    jaxpr, _infos = train_step.traced_program(ids, labels)
    return [("gpt_hybrid_train",
             analysis.check_kernels(jaxpr, where="<gpt_hybrid_train>"))]


def target_serving():
    """Every serving-engine program.  Pure-JAX today (zero pallas_call
    eqns, zero findings) — the target exists so ROADMAP item 1's ragged
    paged-attention kernel is gated the moment it lands."""
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
                             dtype=jnp.float32))
    out = []
    try:
        for name, jaxpr in engine.audit_programs().items():
            out.append((f"serving/{name}", analysis.check_kernels(
                jaxpr, where=f"<serving {name}>")))
    finally:
        engine.shutdown()
    return out


def _standalone(label, fn, *args):
    """Trace one kernel entry point standalone and audit the jaxpr."""
    import jax

    from paddle_tpu import analysis

    jaxpr = jax.make_jaxpr(fn)(*args)
    return [(label, analysis.check_kernels(jaxpr, where=f"<{label}>"))]


def target_flash_attention():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa

    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    return _standalone(
        "flash_attention",
        lambda q, k, v: fa.flash_attention_bshd(
            q, k, v, causal=True, block_q=128, block_k=128,
            interpret=True),
        q, q, q)


def target_block_sparse_attention():
    import numpy as np
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import block_sparse_attention as bsa

    q = jnp.zeros((1, 2, 256, 64), jnp.float32)
    mask = np.tril(np.ones((2, 2), bool))        # 2x2 blocks of 128
    tables = bsa.prepare_block_mask(mask, 128, 128)
    return _standalone(
        "block_sparse_attention",
        lambda q, k, v: bsa.block_sparse_flash_attention(
            q, k, v, tables, 0.125, 128, 128, True),
        q, q, q)


def target_ring_attention():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import ring_attention as ra

    q = jnp.zeros((1, 2, 256, 64), jnp.float32)
    return _standalone(
        "ring_attention",
        lambda q, k, v: ra.ring_flash_attention(
            q, k, v, causal=True, axis_size=1, block_q=128,
            block_k=128, interpret=True),
        q, q, q)


def target_norm():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import norm

    x = jnp.zeros((64, 256), jnp.float32)
    w = jnp.ones((256,), jnp.float32)
    b = jnp.zeros((256,), jnp.float32)
    out = _standalone(
        "norm/layer_norm",
        lambda x, w, b: norm.fused_layer_norm(x, w, b, interpret=True),
        x, w, b)
    out += _standalone(
        "norm/rms_norm",
        lambda x, w: norm.fused_rms_norm(x, w, interpret=True), x, w)
    return out


def target_optim():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import optim

    p = jnp.zeros((256, 512), jnp.float32)
    g = jnp.ones_like(p)
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)

    def run(p, g, m, v, guard):
        return optim.fused_adam_update(
            p, g, m, v, 1e-3, 0.9, 0.999, beta1=0.9, beta2=0.999,
            eps=1e-8, weight_decay=0.01, guard=guard, interpret=True)

    out = _standalone("optim/adamw",
                      lambda *a: run(*a, guard=False), p, g, m, v)
    out += _standalone("optim/adamw_guard",
                       lambda *a: run(*a, guard=True), p, g, m, v)
    return out


def target_pallas_source():
    """The trace-free AST pass over ops/pallas/*.py (static KL101 on
    literal block tuples, static KL103 on unwidened dot-like calls)."""
    from paddle_tpu import analysis

    return [("pallas_source", analysis.check_kernel_files())]


TARGETS = {
    "gpt_hybrid_train": target_gpt_hybrid_train,
    "serving": target_serving,
    "flash_attention": target_flash_attention,
    "block_sparse_attention": target_block_sparse_attention,
    "ring_attention": target_ring_attention,
    "norm": target_norm,
    "optim": target_optim,
    "pallas_source": target_pallas_source,
}


def run_targets(names=None):
    """[(program_name, [Finding])] over the chosen targets."""
    results = []
    for name in (names or sorted(TARGETS)):
        if name not in TARGETS:
            raise SystemExit(f"kernlint: unknown target {name!r} "
                             f"(have: {', '.join(sorted(TARGETS))})")
        results.extend(TARGETS[name]())
    return results


def bench_report(targets=None):
    """The bench.py --worker-kernlint lane: finding count + per-rule
    breakdown over every kernel target, so every BENCH run records the
    kernel-interior hazard picture next to the cost audit."""
    t0 = time.time()
    results = run_targets(targets)
    breakdown = {}
    for _name, findings in results:
        for f in findings:
            breakdown[f.code] = breakdown.get(f.code, 0) + 1
    return {
        "kernlint_finding_count": sum(len(fs) for _, fs in results),
        "kernlint_rule_breakdown": dict(sorted(breakdown.items())),
        "kernlint_elapsed_s": round(time.time() - t0, 2),
    }


# ----------------------------------------------------------------- CLI
def main(argv=None):
    from paddle_tpu.analysis import common
    from paddle_tpu.analysis.rules import KERNLINT_CODES, RULES

    ap = argparse.ArgumentParser(
        prog="kernlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--targets", nargs="*", default=None,
                    help=f"audit targets (default: all — "
                         f"{', '.join(sorted(TARGETS))})")
    common.add_baseline_args(ap, DEFAULT_BASELINE)
    ap.add_argument("--rules", action="store_true",
                    help="print the KL rule catalogue and exit")
    args = ap.parse_args(argv)

    if args.rules:
        return common.print_rules(RULES, codes=set(KERNLINT_CODES))

    t0 = time.time()
    results = run_targets(args.targets)
    elapsed = time.time() - t0
    findings = [f for _, fs in results for f in fs]

    if not args.write_baseline and not args.diff:
        for name, fs in results:
            print(f"== {name}: {len(fs)} finding(s)")
    return common.run_baseline_flow(
        findings, args, tool="kernlint", repo=REPO, elapsed=elapsed)


if __name__ == "__main__":
    sys.exit(main())
