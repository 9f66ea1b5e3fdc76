"""Profile the bench's ResNet-50 train step on the real TPU.

Reports, per step: wall time, XLA cost-analysis FLOPs (so MFU can be
cross-checked against bench.py's analytic 3x4.1GF/img estimate), the
compiled HLO's convolution dtypes (fp32 pockets under O1 would show up
here), and optionally a jax.profiler trace for timeline inspection.

Usage: python tools/profile_resnet.py [--trace DIR] [--batch N] [--iters N]
"""
from __future__ import annotations

import argparse
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--data-format", default="NHWC")
    ap.add_argument("--no-amp", action="store_true")
    args = ap.parse_args()

    import jax

    import paddle_tpu as P
    import paddle_tpu.nn.functional as F
    from paddle_tpu.observability.profile import attached_chip
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    from paddle_tpu.vision.models import resnet50

    dev, chip = attached_chip()            # no TPU, unknown kind: error
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    enable_compile_cache()

    P.seed(0)
    model = resnet50(num_classes=1000, data_format=args.data_format)
    opt = P.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                               parameters=model.parameters())

    @P.jit.to_static
    def train_step(x, y):
        opt.clear_grad()
        if args.no_amp:
            logits = model(x)
        else:
            with P.amp.auto_cast(level="O1", dtype="bfloat16"):
                logits = model(x)
        loss = F.cross_entropy(logits, y)
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)
    shape = ((args.batch, 224, 224, 3) if args.data_format == "NHWC"
             else (args.batch, 3, 224, 224))
    x = P.to_tensor(rng.standard_normal(shape).astype(np.float32))
    y = P.to_tensor(rng.integers(0, 1000, (args.batch,)), dtype="int64")

    # warmup + grab the cached compiled executable for cost analysis
    loss = train_step(x, y)
    loss.block_until_ready()

    entry = next(iter(train_step._compiled.values()))
    compiled = entry.jitted.lower([t._value for t in entry.state_list],
                                  [x._value, y._value]).compile()
    cost = compiled.cost_analysis()
    print("xla cost_analysis flops:", cost["flops"])
    print("  bytes accessed:", cost["bytes accessed"])
    hlo = compiled.as_text()
    dt = {}
    for sig, _ in re.findall(r"= ((?:bf16|f32|f16|s8|s32)[^ ]*) "
                             r"(convolution|dot)\(", hlo):
        dt[sig.split("[")[0]] = dt.get(sig.split("[")[0], 0) + 1
    print("conv/dot output dtypes:", dt)
    n_f32_conv = len(re.findall(r"= f32[^=]*convolution\(", hlo))
    print("f32 convolutions:", n_f32_conv)
    print("fusions:", hlo.count(" fusion("),
          " all-reduce:", hlo.count("all-reduce("),
          " copies:", hlo.count(" copy("))

    # per-step timing: individually synced (exposes per-call overhead) ...
    ts = []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        loss = train_step(x, y)
        loss.block_until_ready()
        ts.append(time.perf_counter() - t0)
    per_step_synced = float(np.median(ts))

    # ... vs free-running (the bench's measurement mode)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        loss = train_step(x, y)
    loss.block_until_ready()
    per_step_stream = (time.perf_counter() - t0) / args.iters

    import importlib.util as _u
    _spec = _u.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    _bench = _u.module_from_spec(_spec)
    _spec.loader.exec_module(_bench)
    flops_img = _bench._RESNET50_TRAIN_FLOPS  # FLOPs (2x MACs), like bench
    for name, t in [("synced", per_step_synced), ("stream", per_step_stream)]:
        img_s = args.batch / t
        print(f"{name}: {t*1e3:.1f} ms/step  {img_s:.0f} img/s  "
              f"mfu={img_s*flops_img/chip.peak_flops:.3f}")

    if args.trace:
        with jax.profiler.trace(args.trace):
            for _ in range(3):
                loss = train_step(x, y)
            loss.block_until_ready()
        print("trace written to", args.trace)


if __name__ == "__main__":
    main()
