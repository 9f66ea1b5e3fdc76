"""One run of one cell: ``python benchmark/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

Everything that belongs to one cell is data found by name: the cell's
entry in ``BENCHMARK.json`` names its configuration and traffic mix,
``workloads/<cell>.json`` names its runner and holds its limits,
``traffic/<mix>.json`` its sizes and rates, ``layer_metrics/<metric>.py``
the reader of each per-layer metric.  This file holds no cell's name, size
or rate.

Without a TPU whose kind is in ``peaks.py`` (and as many chips as the cell
asks for) it exits non-zero and prints no result.  ``--rehearse-cpu`` is
for typos only: tiny sizes from the files' ``rehearsal`` blocks through the
same runners on the CPU, every number marked as no measurement.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import compare, harness, hostspans  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="NOT A MEASUREMENT: tiny sizes on the CPU backend")
    return ap.parse_args(argv)


def find_cell(manifest, name):
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            config = next(c for c in manifest["configs"]
                          if c["name"] == cell["config"])
            return cell, config
    raise SystemExit(f"BENCHMARK.json has no workload {name!r}")


def metrics_of(manifest, kind, cell_name, reported):
    """The manifest's metrics of one kind that this cell reports: those
    that list it, and those with no list whose end-to-end metric it has."""
    out = []
    for m in manifest[kind]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def make_context(args, cell_entry, config_entry):
    rehearse = args.rehearse_cpu
    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    chips = cell_entry["chips"]
    devs = jax.devices()
    peak = None
    if not rehearse:
        if devs[0].platform != "tpu":
            raise SystemExit(f"the benchmark needs a TPU; jax found "
                             f"{devs[0].platform!r} (no result printed)")
        if len(devs) < chips:
            raise SystemExit(f"cell needs {chips} chip(s), jax found "
                             f"{len(devs)}")
    if devs[0].platform == "tpu":
        from benchmark.peaks import peak_for
        peak = peak_for(devs[0].device_kind)

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()   # <checkout>/.cache/jax, or where
    #                          JAX_COMPILATION_CACHE_DIR says
    rel = config_entry["file"]
    with open(os.path.join(harness.ROOT, rel)) as f:
        cfg = harness.with_rehearsal(json.load(f), rehearse)
    cell = harness.load_json("workloads", cell_entry["name"] + ".json")
    traffic = harness.with_rehearsal(
        harness.load_json("traffic", cell_entry["traffic"] + ".json"),
        rehearse)
    notes = []

    def note(text):
        notes.append(text)
        print(f"[bench] {text}", file=sys.stderr, flush=True)

    ctx = types.SimpleNamespace(
        cell_name=cell_entry["name"], chips=chips, cfg=cfg, cell=cell,
        traffic=traffic, seed=args.seed, trace=bool(args.trace),
        rehearse=rehearse, peak=peak, t_start=T_START,
        window_seconds=(min(args.seconds, cell["trace_seconds"])
                        if args.trace else args.seconds),
        spans=harness.Spans(), capture=harness.Capture(bool(args.trace)),
        compiles=harness.XlaCompileCounter(),
        family=harness.load_module("models", cfg["family"]),
        memory_peak=lambda: harness.memory_peak_bytes(chips),
        note=note, notes=notes)
    ctx.spans.annotate = ctx.trace
    return ctx


def open_cell(args):
    """(manifest, context, runner) of the cell ``args`` names — what
    ``main``, the tools and the tests all start from."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    ctx = make_context(args, *find_cell(manifest, args.workload))
    return manifest, ctx, harness.load_module("runners", ctx.cell["runner"])


def breakdown_of(summary, profile):
    """The result line's ``breakdown``: the ten device operations that took
    most time, and the device's idle seconds by what the host was doing
    (``hostspans.idle_gaps``: the program's innermost span, ``outside`` all
    of them, or ``short`` for gaps too short to place), ten rows at most."""
    gaps = hostspans.idle_gaps(profile) or []
    return {"device_ops": summary["top_ops"][:10],
            "idle_gaps": [row[:2] for row in gaps[:10]]}


def finish(ctx, manifest, out):
    """Choose the metrics of this run's kind, read the trace, judge the
    numbers compared, and build the result line."""
    device = harness.device_info(ctx.chips)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    values = dict(out["end_to_end"], setup_s=out["setup_s"])
    failed = out["failed"]
    if out["new_compiles_in_window"]:
        ctx.note(f"FAULT: {out['new_compiles_in_window']} XLA compile(s) "
                 f"inside the measured window")
        failed += out["new_compiles_in_window"]
    ok, compared = compare.judge(out["numbers"], ctx.cell["limits"])
    correct = bool(ok and out["numbers"] and failed == 0)

    metrics, breakdown = {}, None
    if ctx.trace:
        from benchmark import xplane
        # parsed once: the per-layer readers get the same capture from
        # ``hostspans.load_current``
        profile = hostspans.load(ctx.capture.xplane_path())
        summary = (xplane.reduce_events(xplane.device_events(profile))
                   if profile is not None else None)
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = breakdown_of(summary, profile)
        record = dict(out["record"], trace=summary, spans=ctx.spans.durations,
                      window_s=out["window_s"], cfg=ctx.cfg,
                      traffic=ctx.traffic, peak=ctx.peak, chips=ctx.chips,
                      end_to_end=out["end_to_end"])
        for m in metrics_of(manifest, "per_layer", ctx.cell_name, values):
            reader = harness.load_module("layer_metrics", m["name"])
            value = reader.read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        ctx.capture.discard()
    else:
        for m in metrics_of(manifest, "end_to_end", ctx.cell_name, values):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if ctx.rehearse:
        line["not_a_measurement"] = "CPU rehearsal at tiny sizes"
    line["notes"] = ctx.notes[-6:]
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in compared.items()}
    return line


def main(argv=None):
    args = parse_args(argv)
    manifest, ctx, runner = open_cell(args)
    try:
        out = runner.run(ctx)
        line = finish(ctx, manifest, out)
    except Exception:  # noqa: BLE001 — a fault of the run is a result
        import traceback
        traceback.print_exc()
        ctx.note("the run raised; no metric is reported")
        device = harness.device_info(ctx.chips)
        device["memory_peak_bytes"] = ctx.memory_peak()
        line = {"correct": False, "attempted": 0, "failed": 1, "metrics": {},
                "device": device, "notes": ctx.notes[-6:], "compared": {}}
    for name, c in line["compared"].items():
        print(f"[bench] compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(f"[bench] correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
