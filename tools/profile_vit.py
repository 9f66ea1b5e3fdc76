"""ViT-B/16 train step on real TPU: throughput + MFU.

Completes the BASELINE configs[1] lane ("PaddleClas ResNet-50 / ViT-B
(to_static whole-graph -> XLA)") — bench.py owns the ResNet half; this
is the ViT half. bf16 autocast, to_static whole-graph compile,
cost-analysis-backed MFU.

Run on the chip:
  python tools/profile_vit.py [--batch 128] [--iters 8]
Tiny CPU smoke (prints no device metric):
  python tools/profile_vit.py --tiny --iters 1
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--tiny", action="store_true",
                    help="tiny config smoke (CPU)")
    args = ap.parse_args()

    import jax

    import paddle_tpu as P
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.vit import (VisionTransformer, ViTConfig,
                                      vit_b_16)
    from paddle_tpu.observability.profile import chip_spec
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    # the full-size run is a device measurement: an unknown device is
    # an error; the tiny smoke computes no MFU
    chip = None if args.tiny else chip_spec(dev.device_kind)
    enable_compile_cache()

    P.seed(0)
    if args.tiny:
        cfg = ViTConfig(image_size=32, patch_size=8, hidden_size=64,
                        num_layers=2, num_heads=4, num_classes=10,
                        dropout=0.0, attention_dropout=0.0)
        args.batch = min(args.batch, 4)
    else:
        cfg = vit_b_16(dropout=0.0, attention_dropout=0.0)
    model = VisionTransformer(cfg)
    opt = P.optimizer.AdamW(learning_rate=1e-4,
                            parameters=model.parameters())
    n_params = sum(int(np.prod(q.shape)) for q in model.parameters())
    print(f"params: {n_params/1e6:.1f}M", flush=True)

    @P.jit.to_static
    def train_step(x, y):
        opt.clear_grad()
        with P.amp.auto_cast(level="O1", dtype="bfloat16"):
            logits = model(x)
        loss = F.cross_entropy(logits, y)
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)
    x = P.to_tensor(rng.standard_normal(
        (args.batch, cfg.in_channels, cfg.image_size,
         cfg.image_size)).astype(np.float32))
    y = P.to_tensor(rng.integers(0, cfg.num_classes, (args.batch,)),
                    dtype="int64")

    t0 = time.time()
    loss = train_step(x, y)
    loss.block_until_ready()
    print(f"compile+first step {time.time()-t0:.1f}s "
          f"loss={float(loss.numpy()):.3f}", flush=True)

    t0 = time.perf_counter()
    for _ in range(args.iters):
        loss = train_step(x, y)
    loss.block_until_ready()       # steps chain through optimizer state
    dt = (time.perf_counter() - t0) / args.iters
    img_s = args.batch / dt

    if chip is None:
        # a CPU timing is never written under the device metric's name
        print(json.dumps({"tiny_smoke_ok": True,
                          "loss": round(float(loss.numpy()), 4)}))
        return 0
    entry = next(iter(train_step._compiled.values()))
    cost = entry.jitted.lower(
        [t._value for t in entry.state_list],
        [x._value, y._value]).compile().cost_analysis()
    fpi = cost["flops"] / args.batch
    out = {"metric": "vit_b16_train_throughput", "value": round(img_s, 2),
           "unit": "images/sec/chip", "platform": dev.platform,
           "device_kind": dev.device_kind,
           "params_m": round(n_params / 1e6, 1), "batch": args.batch,
           "ms_per_step": round(dt * 1e3, 1),
           "xla_flops_per_img_g": round(fpi / 1e9, 2),
           "mfu": round(img_s * fpi / chip.peak_flops, 4)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
