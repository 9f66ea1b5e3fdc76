#!/usr/bin/env python
"""perfgate — machine-checked perf budgets from DETERMINISTIC cost models.

BENCH wall-times depend on the host and the chip — a CI gate can't
block on them.  What IS stable run-to-run is the cost
model: the roofline profiler's analytic bytes/flops per traced step
(observability.profile), the shardlint liveness/padding estimates
(analysis.cost_audit), and the serving engine's declared lifetime
compile bound.  perfgate traces the flagship programs on CPU (no
compile, no chip), extracts those numbers, and compares them
against the checked-in baseline (tools/perf_baseline.json) — so every
future bytes/step optimization (ROADMAP item 5: bf16 activations,
fused optimizer, Pallas LN) lands against a machine-checked budget
instead of a hand-read bench log, and an accidental +20% bytes/step
regression fails CI the day it lands.

Every metric is lower-is-better.  `--check` fails on any metric above
baseline * (1 + tolerance); improvements beyond tolerance are reported
with a hint to re-baseline (ratcheting the budget down is a reviewed
diff, like every other baseline in tools/).

Usage:
  python tools/perfgate.py                 # report current numbers
  python tools/perfgate.py --check         # vs baseline, CI gate
  python tools/perfgate.py --write-baseline
  python tools/perfgate.py --json -        # machine-readable report
  python tools/perfgate.py --targets gpt_hybrid_train

Exit codes: 0 clean, 1 regressions (--check), 2 usage error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the gate is trace-only (shape-level): the CPU backend is always the
# right one, and a gate has no business taking the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

DEFAULT_BASELINE = os.path.join(REPO, "tools", "perf_baseline.json")
DEFAULT_TOLERANCE = 0.05


# ------------------------------------------------------------- targets
def build_gpt_train_step(optimized=True, remat=None, guard=False):
    """The flagship hybrid-parallel train step — the SHARED builder
    other tools profile the same program from (tools/obs_report.py
    --roofline --demo, tests/test_profile.py), with the loss under an
    explicit profile scope so its softmax/gather traffic is attributed
    rather than bucketed <unattributed>.

    ``optimized=True`` (the shipped flagship since the PR 10 bytes/step
    work) enables the three byte-cutting fronts: bf16 activation
    residency (``to_static(amp_policy="bf16")``), the fused single-pass
    AdamW update (``fused=True``), and the Pallas fused LN/residual
    blocks (``fused_ln=True``).  ``optimized=False`` is the plain-f32
    per-op build (the remat lane's baseline and the XLA-reconciliation
    test use it).  ``remat`` threads to ``to_static(remat=...)``;
    ``guard=True`` arms the training sentinel's in-trace anomaly
    probes on both halves (``to_static(guard=True)`` +
    ``AdamW(guard=True)``) — the ``sentinel`` perfgate target measures
    their cost-model overhead against the unguarded flagship."""
    import numpy as np

    import paddle_tpu as P
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
    from paddle_tpu.observability import profile

    P.seed(0)
    cfg = gpt3_tiny(fused_ln=bool(optimized))
    model = GPTForCausalLM(cfg)
    opt = P.optimizer.AdamW(learning_rate=1e-4,
                            parameters=model.parameters(),
                            fused=bool(optimized), guard=bool(guard))

    @P.jit.to_static(amp_policy="bf16" if optimized else None,
                     remat=remat, guard=bool(guard))
    def train_step(ids, labels):
        opt.clear_grad()
        logits = model(ids)
        with profile.scope("loss"):
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
    return train_step, ids, labels


def gpt_roofline_report(optimized=True, remat=None, guard=False):
    """(RooflineReport, CostReport) for the gpt hybrid train step —
    shared by the gate metrics and the bench.py --worker-profile lane."""
    from paddle_tpu.analysis.cost_audit import audit_memory
    from paddle_tpu.observability import profile

    train_step, ids, labels = build_gpt_train_step(optimized=optimized,
                                                   remat=remat,
                                                   guard=guard)
    jaxpr, infos = train_step.traced_program(ids, labels)
    report = profile.profile_traced(jaxpr, where="<gpt_hybrid_train>",
                                    chip=profile.V5E)
    _findings, cost = audit_memory(jaxpr, where="<gpt_hybrid_train>",
                                   inputs=infos)
    return report, cost


def remat_report():
    """The bench.py --worker-remat lane: remat-on vs remat-off COST
    MODEL numbers for the gpt train step, reported honestly — remat
    re-runs each block's forward inside backward, so bytes/step go UP
    (that's the flops-for-HBM trade, not a win to hide), and on the
    param-dominated TINY CI config the liveness peak estimate can rise
    too (the anti-CSE barriers around each region count as copies).
    The old bench "remat" key was a bare bool that implied a free win;
    these numbers are what the trade actually costs on the audited
    program.  ``remat="bf16"`` halves the saved boundary activations."""
    t0 = time.time()
    rep_off, cost_off = gpt_roofline_report(optimized=False)
    rep_on, cost_on = gpt_roofline_report(optimized=False, remat="bf16")
    bytes_saved = 100.0 * (1.0 - rep_on.total_bytes
                           / max(1, rep_off.total_bytes))
    peak_saved = 100.0 * (1.0 - cost_on.peak_hbm_bytes
                          / max(1, cost_off.peak_hbm_bytes))
    return {
        "remat_bytes_per_step_off": rep_off.total_bytes,
        "remat_bytes_per_step_on": rep_on.total_bytes,
        "remat_bytes_saved_pct": round(bytes_saved, 2),
        "remat_peak_hbm_off_mb": round(
            cost_off.peak_hbm_bytes / (1 << 20), 3),
        "remat_peak_hbm_on_mb": round(
            cost_on.peak_hbm_bytes / (1 << 20), 3),
        "remat_peak_hbm_saved_pct": round(peak_saved, 2),
        "remat_elapsed_s": round(time.time() - t0, 2),
    }


def target_gpt_hybrid_train():
    report, cost = gpt_roofline_report()
    return {
        "bytes_per_step": report.total_bytes,
        "flops_per_step": report.total_flops,
        "unattributed_bytes_pct": round(
            100.0 * (1.0 - report.frac_attributed_bytes), 2),
        "unattributed_flops_pct": round(
            100.0 * (1.0 - report.frac_attributed_flops), 2),
        "padding_waste_pct": round(100.0 * cost.padding_waste, 2),
        "peak_hbm_mb": round(cost.peak_hbm_bytes / (1 << 20), 3),
    }


def target_serving():
    """The serving engine's whole program set: total/decode traffic from
    the roofline cost model plus the engine's declared lifetime compile
    bound — the number the bounded-compile contract lives or dies by."""
    import paddle_tpu as P
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import profile

    P.seed(0)
    mcfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, dropout=0.0,
                     attention_dropout=0.0)
    engine = serving.LLMEngine(
        GPTForCausalLM(mcfg),
        serving.EngineConfig(max_num_seqs=4, page_size=8, max_model_len=64,
                             prefill_buckets=(16, 32)))
    try:
        reports = profile.profile_engine(engine, chip=profile.V5E)
        decode = reports.get("decode")
        return {
            "compile_bound": engine.config.compile_bound,
            "decode_bytes_per_step": decode.total_bytes if decode else 0,
            "programs_total_bytes": sum(r.total_bytes
                                        for r in reports.values()),
        }
    finally:
        engine.shutdown()


def _quant_engines():
    """(engine factory, shared model) for the quantization target and
    the bench --worker-quant lane — the SAME tiny geometry as
    target_serving, so the kv numbers compare apples to apples."""
    import paddle_tpu as P
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(0)
    mcfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, dropout=0.0,
                     attention_dropout=0.0)
    model = GPTForCausalLM(mcfg)

    def build(**kw):
        return serving.LLMEngine(
            model, serving.EngineConfig(
                max_num_seqs=4, page_size=8, max_model_len=64,
                prefill_buckets=(16, 32), **kw))

    return build


def target_quantization():
    """Both quantized memory planes, deterministically accounted.

    Plane 1 — int8 KV pages: pool-storage bytes per token of capacity
    and the ratios vs the bf16/f32 pools at identical geometry (the
    acceptance bar is <= 0.55x vs bf16), plus the cost-model peak HBM
    of the int8 decode program — proof the narrow storage reaches the
    SL301 liveness estimate, not just the allocator.  Plane 2 — the
    EQuARX all-reduce wire model for a reference 1M-element gradient at
    axis size 8 (analytic, device-count-independent; the traced
    cross-check lives in tests/test_quantized_kv.py).  Every metric is
    lower-is-better."""
    import jax.numpy as jnp

    from paddle_tpu.analysis.cost_audit import audit_memory
    from paddle_tpu.quantization.collectives import \
        quantized_all_reduce_wire_bytes

    build = _quant_engines()
    out = {}
    engines = {}
    try:
        engines["f32"] = build()
        engines["bf16"] = build(dtype=jnp.bfloat16)
        engines["int8"] = build(kv_cache_dtype="int8")
        bpt = {k: e.kv_bytes_per_token for k, e in engines.items()}
        out["kv_bytes_per_token"] = round(bpt["int8"], 3)
        out["kv_quant_vs_bf16_ratio"] = round(bpt["int8"] / bpt["bf16"], 4)
        out["kv_quant_vs_f32_ratio"] = round(bpt["int8"] / bpt["f32"], 4)
        progs = engines["int8"].audit_programs()
        _f, cost = audit_memory(progs["decode"],
                                where="<quant decode>")
        out["quant_decode_peak_hbm_mb"] = round(
            cost.peak_hbm_bytes / (1 << 20), 3)
        _f, cost_f32 = audit_memory(
            engines["f32"].audit_programs()["decode"],
            where="<f32 decode>")
        out["quant_vs_f32_decode_peak_ratio"] = round(
            cost.peak_hbm_bytes / max(1, cost_f32.peak_hbm_bytes), 4)
    finally:
        for e in engines.values():
            e.shutdown()
    wire = quantized_all_reduce_wire_bytes(1 << 20, axis_size=8)
    out["allreduce_bytes"] = wire["allreduce_bytes"]
    out["allreduce_quant_vs_wide_ratio"] = \
        wire["allreduce_quant_vs_wide_ratio"]
    return out


def target_sentinel():
    """The training sentinel's detection-cost contract, measured on the
    SAME optimized flagship the gpt_hybrid_train target gates: trace
    the guarded build (``to_static(guard=True)`` +
    ``AdamW(guard=True)``) and compare its cost-model bytes/step
    against the unguarded one.  The headline metric is
    ``guard_bytes_overhead_pct`` — the <2% acceptance bar of the
    in-trace-probes design (the fused Adam kernel reduces grad
    sum-of-squares while g is already in registers, so the probe's
    bytes are the tiny partials/summary plumbing plus the rank-1
    unfused reductions).  The zero-extra-compiles half of the contract
    is a recompile-log proof, pinned in tests/test_sentinel.py."""
    import gc

    rep_off, _cost_off = gpt_roofline_report()
    # the unguarded build's model holds reference cycles; un-collected,
    # its state tensors are still registry-live and ride into the
    # guarded trace as extra lifted inputs, inflating the liveness
    # peak estimate by a whole phantom model
    gc.collect()
    rep_on, cost_on = gpt_roofline_report(guard=True)
    overhead = 100.0 * (rep_on.total_bytes
                        / max(1, rep_off.total_bytes) - 1.0)
    return {
        "guard_bytes_per_step": rep_on.total_bytes,
        "guard_bytes_overhead_pct": round(max(0.0, overhead), 3),
        "guard_flops_overhead_pct": round(max(0.0, 100.0 * (
            rep_on.total_flops / max(1, rep_off.total_flops) - 1.0)), 3),
        "guard_peak_hbm_mb": round(cost_on.peak_hbm_bytes / (1 << 20),
                                   3),
    }


def target_traffic():
    """The traffic harness's SLO contract on the VIRTUAL clock: a burst
    trace against a router with one active replica and one parked
    spare, the SLO autoscaler in the loop.  Every number is a property
    of the deterministic schedule (seeded trace + virtual time), not of
    the host, so the gate pins behavior, not wall time.  All metrics
    are lower-is-better: ``goodput_shortfall_pct`` is 100x(1 -
    goodput-under-SLO fraction), ``scaleup_reaction_ticks`` is the
    burst-onset -> spare-admitting reaction time in driver ticks (the
    warm-AOT-respawn payoff the autoscaler rides), and
    ``slo_violations`` / ``ttft_p99_ms`` pin the tail."""
    import shutil
    import tempfile

    import paddle_tpu as P
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import traffic
    from paddle_tpu.serving.router import Router, RouterConfig

    P.seed(0)
    mcfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, dropout=0.0,
                     attention_dropout=0.0)
    ecfg = serving.EngineConfig(max_num_seqs=4, page_size=8,
                                max_model_len=64, prefill_buckets=(16, 32),
                                crash_safe_decode=False)
    model = GPTForCausalLM(mcfg)
    spec = traffic.TrafficSpec(
        name="perfgate", seed=11,
        arrival={"kind": "onoff", "base_qps": 2.0, "burst_qps": 40.0,
                 "period_s": 2.0, "duty": 0.35},
        duration_s=2.0, prompt_len=((1.0, 4, 16),),
        output_tokens=((1.0, 4, 8),),
        classes=(traffic.DeadlineClass("interactive", ttft_slo_s=0.5),))
    quantum = 0.01
    cache = tempfile.mkdtemp(prefix="ptpu_perfgate_traffic_")
    clock = traffic.VirtualClock()
    try:
        router = Router(model, ecfg, num_replicas=2,
                        config=RouterConfig(sleep=lambda s: None),
                        program_cache=cache, clock=clock)
        router.park(1)
        router.step()
        scaler = traffic.SLOAutoscaler(
            router,
            slo=traffic.SLO(ttft_p99_s=0.5, queue_high=3.0,
                            queue_low=0.5),
            config=traffic.AutoscalerConfig(min_replicas=1, up_after=2,
                                            down_after=30, cooldown=5),
            clock=clock, name="perfgate")
        driver = traffic.TrafficDriver(
            router, spec, clock, quantum_s=quantum, name="perfgate",
            on_tick=lambda d: scaler.observe())
        rep = driver.run()
        snap = scaler.snapshot()
        reaction_ticks = (max(int(round(t / quantum))
                              for t in snap["reaction_times_s"])
                          if snap["reaction_times_s"] else 10 ** 6)
        out = {
            "goodput_shortfall_pct": round(
                100.0 * (1.0 - rep["goodput_frac"]), 3),
            "slo_violations": rep["violations"] + rep["expired"],
            "ttft_p99_ms": rep["ttft_p99_ms"],
            "scaleup_reaction_ticks": reaction_ticks,
            "token_loss": rep["token_loss"],
        }
        driver.release()
        scaler.release()
        router.shutdown()
    finally:
        shutil.rmtree(cache, ignore_errors=True)
    return out


TARGETS = {
    "gpt_hybrid_train": target_gpt_hybrid_train,
    "serving": target_serving,
    "quantization": target_quantization,
    "sentinel": target_sentinel,
    "traffic": target_traffic,
}


def run_targets(names=None):
    out = {}
    for name in (names or sorted(TARGETS)):
        if name not in TARGETS:
            raise SystemExit(f"perfgate: unknown target {name!r} "
                             f"(have: {', '.join(sorted(TARGETS))})")
        out[name] = TARGETS[name]()
    return out


def bench_report():
    """The bench.py --worker-profile lane: roofline headline numbers
    merged into every BENCH report next to the measured wall-time
    lanes."""
    t0 = time.time()
    report, cost = gpt_roofline_report()
    return {
        "profile_bytes_per_step": report.total_bytes,
        "profile_flops_per_step": report.total_flops,
        "profile_top_layer": report.top_layer,
        "profile_bound_fraction": round(report.bound_fraction, 4),
        "profile_attributed_bytes_pct": round(
            100.0 * report.frac_attributed_bytes, 2),
        "profile_padding_waste_pct": round(100.0 * cost.padding_waste, 2),
        "profile_elapsed_s": round(time.time() - t0, 2),
    }


# --------------------------------------------------------------- gate
def compare(current, baseline, tolerance):
    """(regressions, improvements, notes) — every metric lower-is-
    better; a metric present in the baseline but missing from the
    current run is gate erosion and counts as a regression."""
    regressions, improvements, notes = [], [], []
    base_targets = baseline.get("targets", {})
    for tname, base_metrics in sorted(base_targets.items()):
        cur_metrics = current.get(tname)
        if cur_metrics is None:
            regressions.append((tname, "<target>", None, None,
                                "target missing from current run"))
            continue
        for m, base in sorted(base_metrics.items()):
            cur = cur_metrics.get(m)
            where = f"{tname}.{m}"
            if cur is None:
                regressions.append((tname, m, base, None,
                                    "metric missing (gate erosion)"))
            elif base == 0:
                if cur > 0:
                    regressions.append((tname, m, base, cur,
                                        "grew from a zero baseline"))
            elif cur > base * (1.0 + tolerance):
                regressions.append(
                    (tname, m, base, cur,
                     f"+{100.0 * (cur / base - 1.0):.1f}% over baseline "
                     f"(tolerance {100.0 * tolerance:.0f}%)"))
            elif cur < base * (1.0 - tolerance):
                improvements.append(
                    (tname, m, base, cur,
                     f"-{100.0 * (1.0 - cur / base):.1f}% under baseline"))
        for m in sorted(set(cur_metrics) - set(base_metrics)):
            notes.append(f"{tname}.{m}: new metric (not gated yet — "
                         f"--write-baseline to start gating it)")
    for tname in sorted(set(current) - set(base_targets)):
        notes.append(f"{tname}: new target (not gated yet)")
    return regressions, improvements, notes


def render_diff(current, baseline):
    """Print the old-vs-new per-metric table (--diff) and return the
    rows as dicts (for --json).  Purely informational: the % delta
    column is signed (negative = improvement, every metric is
    lower-is-better); metrics present on only one side are labeled.
    The table renderer itself was promoted to analysis/common.py so
    tracelint/shardlint/racelint/numlint share the format for their
    own ``--diff`` modes."""
    from paddle_tpu.analysis.common import render_diff_table
    rows = []
    base_targets = baseline.get("targets", {})
    for tname in sorted(set(base_targets) | set(current)):
        sub = render_diff_table(base_targets.get(tname, {}),
                                current.get(tname, {}), title=tname,
                                label="metric")
        for r in sub:
            rows.append({"target": tname, "metric": r["metric"],
                         "baseline": r["baseline"],
                         "current": r["current"], "delta": r["delta"]})
    return rows


# ----------------------------------------------------------------- CLI
def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="perfgate", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--targets", nargs="*", default=None,
                    help=f"targets to run (default: all — "
                         f"{', '.join(sorted(TARGETS))})")
    ap.add_argument("--check", action="store_true",
                    help="compare against the baseline; exit 1 on any "
                         "regression beyond tolerance")
    ap.add_argument("--diff", action="store_true",
                    help="render an old-vs-new per-metric table with % "
                         "deltas against the baseline (informational: "
                         "metric values never affect the exit code; an "
                         "unreadable baseline is still usage-error 2)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the current numbers as the new baseline")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline path (default tools/perf_baseline.json)")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="relative slack before a metric regresses "
                         f"(default: baseline's, else {DEFAULT_TOLERANCE})")
    ap.add_argument("--json", metavar="FILE", default=None,
                    help="also write the report as JSON ('-' = stdout)")
    args = ap.parse_args(argv)

    t0 = time.time()
    current = run_targets(args.targets)
    elapsed = time.time() - t0

    if not args.diff:
        for tname, metrics in sorted(current.items()):
            print(f"== {tname}")
            for m, v in sorted(metrics.items()):
                print(f"   {m:28s} {v}")

    doc = {"tool": "perfgate", "version": 1, "elapsed_s": round(elapsed, 2),
           "targets": current}

    rc = 0
    if args.diff:
        try:
            with open(args.baseline, encoding="utf-8") as fh:
                baseline = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"perfgate: cannot read baseline {args.baseline}: {e}",
                  file=sys.stderr)
            return 2
        doc["diff"] = render_diff(current, baseline)
    if args.write_baseline:
        base_doc = {"tool": "perfgate", "version": 1,
                    "tolerance": (args.tolerance
                                  if args.tolerance is not None
                                  else DEFAULT_TOLERANCE),
                    "targets": current}
        with open(args.baseline, "w", encoding="utf-8") as fh:
            json.dump(base_doc, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"perfgate: baseline written to {args.baseline}")
    elif args.check:
        try:
            with open(args.baseline, encoding="utf-8") as fh:
                baseline = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"perfgate: cannot read baseline {args.baseline}: {e}",
                  file=sys.stderr)
            return 2
        tol = (args.tolerance if args.tolerance is not None
               else baseline.get("tolerance", DEFAULT_TOLERANCE))
        regressions, improvements, notes = compare(current, baseline, tol)
        doc["regressions"] = [
            {"target": t, "metric": m, "baseline": b, "current": c,
             "why": why} for t, m, b, c, why in regressions]
        for t, m, b, c, why in regressions:
            print(f"REGRESSION {t}.{m}: {b} -> {c} ({why})")
        for t, m, b, c, why in improvements:
            print(f"improved   {t}.{m}: {b} -> {c} ({why}) — consider "
                  f"--write-baseline to ratchet the budget")
        for n in notes:
            print(f"note       {n}")
        if regressions:
            print(f"perfgate: FAILED ({len(regressions)} regression(s) "
                  f"vs {os.path.relpath(args.baseline, REPO)})")
            rc = 1
        else:
            print(f"perfgate: clean vs "
                  f"{os.path.relpath(args.baseline, REPO)} "
                  f"(tolerance {100.0 * tol:.0f}%)")

    if args.json:
        payload = json.dumps(doc, indent=1, sort_keys=True)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w", encoding="utf-8") as fh:
                fh.write(payload + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
