#!/usr/bin/env python
"""obs_report — render paddle_tpu.observability telemetry for humans.

Reads a JSONL dump written by ``observability.export.dump_jsonl`` (or
captures one live with ``--demo``) and renders:

- the RECOMPILE LOG: every compile event with its attribution — which
  argument's shape/dtype/static leaf (or the state registry) changed,
  and the wall-clock trace + compile cost;
- the SPAN TIMELINE: the ring buffer of nested trace spans, indented by
  nesting depth, with durations;
- the METRICS snapshot: every Counter/Gauge/Histogram in the registry.

With ``--roofline`` it instead renders RooflineReport records
(observability.profile): the per-layer bytes/flops attribution table
sorted by bytes, with compute- vs memory-bound classification — from a
JSONL dump's ``roofline`` records, or captured live from the gpt
hybrid train target with ``--demo`` (traces, runs two steps for the
measured span time, and reconciles predicted vs measured).

With ``--fleet <spool_dir>`` it instead merges every per-rank
telemetry spool (observability.fleettrace) into one fleet view: the
per-process inventory on aligned clocks, per-request distributed
timelines with the TTFT stage decomposition (``--request <id>``
focuses one request by router rid / engine rid / trace id), the
rank-labeled merged metrics exposition (``--prom``), and a merged
Chrome trace (``--trace FILE``).

Usage:
  python tools/obs_report.py obs.jsonl           # render a dump
  python tools/obs_report.py --demo              # gpt-hybrid forced-
                                                 # retrace demo, live
  python tools/obs_report.py obs.jsonl --json -  # machine-readable
  python tools/obs_report.py --demo --prom       # Prometheus text
  python tools/obs_report.py --demo --roofline   # live roofline table
  python tools/obs_report.py obs.jsonl --roofline  # from dump records
  python tools/obs_report.py obs.jsonl --capacity  # CapacityReport
                                                 # tables from a dump
  python tools/obs_report.py --fleet spools/     # merged fleet view
  python tools/obs_report.py --fleet spools/ --request rr-3
  python tools/obs_report.py --fleet spools/ --trace fleet.json

The demo compiles the tiny-config GPT hybrid train step, perturbs ONE
input's shape to force a retrace, and shows the resulting recompile
event naming the perturbed argument — the "why did this recompile"
workflow end to end (CPU-only; never touches the chip).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


# ------------------------------------------------------------------ demo
def run_demo():
    """Forced retrace of the gpt hybrid train step: perturb one input
    shape, leave every other argument alone."""
    import numpy as np

    import paddle_tpu as P
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny

    P.seed(0)
    cfg = gpt3_tiny()
    model = GPTForCausalLM(cfg)
    opt = P.optimizer.AdamW(learning_rate=1e-4,
                            parameters=model.parameters())

    # ONE tensor input: next-token labels are derived from `ids` by
    # shifting inside the step, so perturbing the input shape names
    # exactly one argument in the recompile attribution
    @P.jit.to_static
    def train_step(ids):
        opt.clear_grad()
        logits = model(ids)
        loss = F.cross_entropy(
            logits[:, :-1].reshape([-1, cfg.vocab_size]),
            ids[:, 1:].reshape([-1]))
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)
    ids = P.to_tensor(rng.integers(0, cfg.vocab_size, (2, 32)),
                      dtype="int64")
    train_step(ids)                               # first compile
    train_step(ids)                               # cache hit
    # perturb the ONE argument's shape: seq len 32 -> 48
    ids_wide = P.to_tensor(rng.integers(0, cfg.vocab_size, (2, 48)),
                           dtype="int64")
    train_step(ids_wide)                          # forced retrace


def live_roofline():
    """Roofline-profile the gpt hybrid train target live: trace for the
    cost model, run two real steps so the span layer has a measured
    wall time, reconcile the two in one report."""
    import perfgate  # sibling tools/ module (sys.path[0] is tools/)

    from paddle_tpu.observability import profile

    train_step, ids, labels = perfgate.build_gpt_train_step()
    train_step(ids, labels)                 # compile + step 1
    train_step(ids, labels)                 # warm step 2
    jaxpr, _ = train_step.traced_program(ids, labels)
    report = profile.profile_traced(jaxpr, where="<gpt_hybrid_train>",
                                    chip=profile.V5E,
                                    include_interiors=True)
    return profile.reconcile(report, "jit.train_step")


def render_rooflines(reports):
    for d in reports:
        chip = d.get("chip", {})
        print(f"== roofline {d.get('where', '?')} — chip "
              f"{chip.get('name', '?')} ({chip.get('peak_tflops', '?')} "
              f"TF/s, {chip.get('hbm_gbs', '?')} GB/s, ridge "
              f"{chip.get('ridge_flop_per_byte', '?')} flop/B) " + "=" * 8)
        total_b = d.get("total_bytes") or 1
        print(f"  {'layer':<52s} {'KiB':>10s} {'MFLOP':>9s} "
              f"{'flop/B':>7s} {'bound':>8s} {'%bytes':>7s}")
        for row in d.get("layers", []):
            print(f"  {row['name'][:52]:<52s} "
                  f"{row['bytes'] / 1024:>10.1f} "
                  f"{row['flops'] / 1e6:>9.3f} "
                  f"{row.get('intensity', 0):>7.2f} "
                  f"{row.get('bound', '?'):>8s} "
                  f"{100.0 * row['bytes'] / total_b:>6.1f}%")
        line = (f"  total {d['total_bytes'] / 1024:.1f} KiB, "
                f"{d['total_flops'] / 1e6:.3f} MFLOP; attributed "
                f"{d.get('attributed_bytes_pct', '?')}% bytes / "
                f"{d.get('attributed_flops_pct', '?')}% flops; "
                f"memory-bound fraction {d.get('bound_fraction', '?')}; "
                f"predicted {d.get('predicted_ms', 0):.4f} ms")
        if d.get("measured_ms") is not None:
            line += (f"; measured {d['measured_ms']} ms "
                     f"({d.get('measured_source', '')}) — on a CPU host "
                     f"the ratio is diagnostic only")
        print(line)
        if d.get("xla"):
            print(f"  xla cost_analysis: flops {d['xla']['flops']:.4g}, "
                  f"bytes accessed {d['xla']['bytes_accessed']:.4g}")
        if d.get("interiors"):
            print(f"  -- kernel interiors (per-grid-step VMEM traffic "
                  f"vs the call-boundary row) --")
            print(f"  {'kernel':<28s} {'grid':>6s} {'KiB/step':>9s} "
                  f"{'MFLOP':>9s} {'flop/B':>7s} {'bound':>8s} "
                  f"{'reuse':>6s} {'VMEM KiB':>9s}")
            for k in d["interiors"]:
                print(f"  {k['kernel'][:28]:<28s} "
                      f"{k['grid_steps']:>6d} "
                      f"{k['vmem_step_bytes'] / 1024:>9.1f} "
                      f"{k['flops'] / 1e6:>9.3f} "
                      f"{k.get('interior_intensity', 0):>7.2f} "
                      f"{k.get('bound', '?'):>8s} "
                      f"{k.get('reuse_factor', 0):>5.1f}x "
                      f"{k.get('vmem_total_bytes', 0) / 1024:>9.1f}")
        print()


def live_doc():
    from paddle_tpu import observability as obs
    return {
        "meta": {"version": 1, "capture": "live"},
        "spans": [s.to_dict() for s in obs.recorder().spans()],
        "recompiles": [e.to_dict()
                       for e in obs.recompile_log().events()],
        "metrics": [
            {"name": m.name, "type": m.kind, "labels": m.labels,
             "value": (m.summary() if m.kind == "histogram" else m.value)}
            for m in obs.registry().collect()],
    }


# ----------------------------------------------------------------- fleet
def render_fleet(tel, limit):
    s = tel.summary()
    print(f"== fleet telemetry ({s['processes']} processes, ranks "
          f"{s['ranks']}) " + "=" * 12)
    print(f"  spans {s['spans']}  recompiles {s['recompiles']}  "
          f"metric snapshots {s['metric_snapshots']}  torn lines "
          f"{s['torn_lines']}")
    print(f"  traces {s['traces']}  ref rank {s['ref_rank']}  "
          f"clock skew bound {s['clock_skew_ms']} ms")
    for p in tel.processes:
        off = "?" if p.clock is None else f"{p.offset_ns / 1e6:+.3f}"
        print(f"  {p.label:<24s} {len(p.spans):>6d} spans  "
              f"{len(p.recompiles):>3d} recompiles  "
              f"{len(p.metrics):>3d} snapshots  offset {off} ms"
              + (f"  [{p.torn_lines} torn]" if p.torn_lines else ""))
    print()


def render_timeline(tl, limit):
    print(f"== request {tl['request']} (trace {tl['trace']}) " + "=" * 8)
    print(f"  complete={tl['complete']}  admissions={tl['admissions']}"
          f"  finishes={tl['finishes']}  migrations={tl['migrations']}"
          f"  handoffs={tl['handoffs']}  processes={tl['processes']}")
    for k in ("queue_wait_s", "prefill_s", "handoff_s", "adoption_s",
              "decode_s", "total_s"):
        if k in tl["stages"]:
            print(f"  {k:<13s} {tl['stages'][k] * 1e3:10.3f} ms")
    spans = tl["spans"][:limit]
    t0 = spans[0]["start_ns"] if spans else 0
    for e in spans:
        attrs = e.get("attrs") or {}
        attr_s = ("  " + " ".join(f"{k}={v}"
                                  for k, v in sorted(attrs.items()))
                  if attrs else "")
        print(f"  +{(e['start_ns'] - t0) / 1e6:9.3f}ms "
              f"r{e['rank'] if e['rank'] is not None else '?'} "
              f"{e['name']:<28s} {e['dur_ns'] / 1e6:9.3f} ms{attr_s}")
    print()


def run_fleet(args, ap):
    from paddle_tpu.observability import fleettrace
    if not os.path.isdir(args.fleet):
        ap.error(f"--fleet: {args.fleet} is not a directory")
    tel = fleettrace.merge_spools(args.fleet)
    if not tel.processes:
        print(f"obs_report: no spool-*.jsonl files in {args.fleet}",
              file=sys.stderr)
        return 1
    if args.prom:
        sys.stdout.write(tel.prometheus_text())
        return 0
    render_fleet(tel, args.limit)
    timelines = []
    if args.request:
        tl = tel.timeline(args.request)
        if tl is None:
            print(f"obs_report: no trace for request {args.request!r} "
                  f"in {args.fleet}", file=sys.stderr)
            return 1
        timelines = [tl]
    else:
        # no --request: render every complete distributed timeline
        # (bounded by --limit), most-travelled first
        tls = [tel.timeline(t) for t in tel.traces()]
        tls = [t for t in tls if t and t["complete"]]
        tls.sort(key=lambda t: (-t["migrations"], str(t["request"])))
        timelines = tls[:max(1, args.limit // 8)]
    for tl in timelines:
        render_timeline(tl, args.limit)
    if args.trace:
        tel.write_chrome_trace(args.trace)
        print(f"merged chrome trace -> {args.trace}")
    if args.json:
        payload = json.dumps(
            {"summary": tel.summary(), "timelines": timelines,
             "recompiles_by_rank": tel.recompiles_by_rank()},
            indent=1, sort_keys=True, default=str)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w", encoding="utf-8") as fh:
                fh.write(payload + "\n")
    return 0


# ---------------------------------------------------------------- render
def render_recompiles(recompiles, limit):
    print(f"== recompile log ({len(recompiles)} events) " + "=" * 24)
    if not recompiles:
        print("  (no compile events recorded)")
    for e in recompiles[-limit:]:
        timing = []
        if e.get("trace_ms") is not None:
            timing.append(f"trace {e['trace_ms']:.0f}ms")
        if e.get("compile_ms") is not None:
            timing.append(f"compile {e['compile_ms']:.0f}ms")
        print(f"  #{e['seq']:<3d} [{e['kind']}] {e['fn']}: {e['cause']}"
              + (f"  ({', '.join(timing)})" if timing else ""))
        for c in e.get("changes", []):
            print(f"        {c['arg']}: {c['kind']} "
                  f"{c['before']} -> {c['after']}")
    print()


def render_spans(spans, limit):
    print(f"== span timeline (last {min(limit, len(spans))} of "
          f"{len(spans)} buffered) " + "=" * 12)
    if not spans:
        print("  (no spans recorded)")
    shown = sorted(spans, key=lambda s: s["start_ns"])[-limit:]
    t0 = shown[0]["start_ns"] if shown else 0
    for s in shown:
        indent = "  " * s.get("depth", 0)
        attrs = s.get("attrs") or {}
        attr_s = ("  " + " ".join(f"{k}={v}" for k, v in attrs.items())
                  if attrs else "")
        print(f"  +{(s['start_ns'] - t0) / 1e6:9.3f}ms "
              f"{indent}{s['name']:<32s} {s['dur_ns'] / 1e6:9.3f} ms"
              f"{attr_s}")
    print()


def render_metrics(metric_rows):
    print(f"== metrics ({len(metric_rows)}) " + "=" * 34)
    for m in metric_rows:
        label = "" if not m.get("labels") else "{" + ",".join(
            f"{k}={v}" for k, v in sorted(m["labels"].items())) + "}"
        v = m["value"]
        if isinstance(v, dict):
            v = " ".join(f"{k}={x}" for k, x in v.items())
        print(f"  {m['type']:<9s} {m['name']}{label} = {v}")
    print()


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="obs_report", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("dump", nargs="?", default=None,
                    help="JSONL file from observability.export.dump_jsonl")
    ap.add_argument("--demo", action="store_true",
                    help="run the gpt-hybrid forced-retrace demo and "
                         "report its live telemetry (CPU-only)")
    ap.add_argument("--limit", type=int, default=40,
                    help="max spans/events to render (default 40)")
    ap.add_argument("--json", metavar="FILE", default=None,
                    help="also write the report as JSON ('-' = stdout)")
    ap.add_argument("--prom", action="store_true",
                    help="print the Prometheus text exposition instead")
    ap.add_argument("--roofline", action="store_true",
                    help="render roofline reports (per-layer bytes/flops "
                         "attribution) instead: from the dump's roofline "
                         "records, or live from the gpt target with --demo")
    ap.add_argument("--capacity", action="store_true",
                    help="render serving CapacityReport tables (max "
                         "sustained QPS at the TTFT SLO per replica "
                         "count) from the dump's capacity records "
                         "(dump_jsonl(..., capacities=[report]))")
    ap.add_argument("--fleet", metavar="SPOOL_DIR", default=None,
                    help="merge per-rank telemetry spools "
                         "(PTPU_OBS_SPOOL_DIR) into one fleet view")
    ap.add_argument("--request", metavar="ID", default=None,
                    help="with --fleet: focus one request's distributed "
                         "timeline (router rid, engine rid, or trace id)")
    ap.add_argument("--trace", metavar="FILE", default=None,
                    help="with --fleet: write the merged multi-process "
                         "Chrome trace here")
    args = ap.parse_args(argv)

    if args.fleet:
        return run_fleet(args, ap)

    if args.capacity:
        if not args.dump:
            ap.error("--capacity needs a JSONL dump path")
        from paddle_tpu.observability import export
        from paddle_tpu.serving.traffic import CapacityReport
        reports = export.load_jsonl(args.dump).get("capacities", [])
        if not reports:
            print(f"obs_report: no capacity records in {args.dump} "
                  f"(dump_jsonl(..., capacities=[report]) writes them)",
                  file=sys.stderr)
            return 1
        for d in reports:
            print(CapacityReport.from_dict(d).render())
            print()
        if args.json:
            payload = json.dumps({"capacities": reports}, indent=1,
                                 sort_keys=True)
            if args.json == "-":
                print(payload)
            else:
                with open(args.json, "w", encoding="utf-8") as fh:
                    fh.write(payload + "\n")
        return 0

    if args.roofline:
        if args.demo:
            reports = [live_roofline().to_dict()]
        elif args.dump:
            from paddle_tpu.observability import export
            reports = export.load_jsonl(args.dump).get("rooflines", [])
            if not reports:
                print(f"obs_report: no roofline records in {args.dump} "
                      f"(dump_jsonl(..., rooflines=[report]) writes them)",
                      file=sys.stderr)
                return 1
        else:
            ap.error("--roofline needs a JSONL dump path or --demo")
        render_rooflines(reports)
        if args.json:
            payload = json.dumps({"rooflines": reports}, indent=1,
                                 sort_keys=True)
            if args.json == "-":
                print(payload)
            else:
                with open(args.json, "w", encoding="utf-8") as fh:
                    fh.write(payload + "\n")
        return 0

    if args.demo:
        run_demo()
        doc = live_doc()
    elif args.dump:
        from paddle_tpu.observability import export
        doc = export.load_jsonl(args.dump)
    else:
        ap.error("give a JSONL dump path or --demo")

    if args.prom:
        if args.dump and not args.demo:
            print("obs_report: --prom renders the LIVE registry; "
                  "combine it with --demo", file=sys.stderr)
            return 2
        from paddle_tpu.observability import export
        sys.stdout.write(export.prometheus_text())
        return 0

    render_recompiles(doc.get("recompiles", []), args.limit)
    render_spans(doc.get("spans", []), args.limit)
    render_metrics(doc.get("metrics", []))

    if args.json:
        payload = json.dumps(doc, indent=1, sort_keys=True)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w", encoding="utf-8") as fh:
                fh.write(payload + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
