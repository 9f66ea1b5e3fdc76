"""Flagship GPT train step on real TPU: single-chip throughput + MFU.

Complements bench.py's ResNet/BERT headlines with the GPT family the
BASELINE.json Fleet configs center on. Default config is a ~350M-param
GPT (hidden 1024, 24 layers) at seq 2048 with recompute — the largest
that fits v5e HBM (16 GB) comfortably with AdamW fp32 states.

Run on the chip (fails without one):
  python tools/profile_gpt.py [--hidden 1024] [--layers 24]
      [--batch 4] [--seq 2048] [--iters 6]

GPT-3 1.3B (BASELINE configs[3], hidden 2048 / 24 layers / seq 2048) on
ONE 16 GB v5e needs the fit levers the pod-mesh reference gets from
sharding stage2/3: bf16 params + bf16 Adam moments + remat + the
chunked fused LM-head CE (no [b,s,V] logits) + donation:
  python tools/profile_gpt.py --preset 1p3b [--batch 8]
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
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--no-recompute", action="store_true")
    ap.add_argument("--fused-head", action="store_true",
                    help="chunked fused LM-head+CE: no [b,s,V] logits")
    ap.add_argument("--param-dtype", default=None,
                    help="cast model params (e.g. bfloat16)")
    ap.add_argument("--moment-dtype", default=None,
                    help="Adam moment storage dtype (e.g. bfloat16)")
    ap.add_argument("--preset", default=None, choices=[None, "1p3b"],
                    help="1p3b = GPT-3 1.3B single-chip fit recipe")
    ap.add_argument("--ce-chunk", type=int, default=8192,
                    help="fused LM-head CE chunk size (memory/occupancy "
                         "tradeoff: smaller = less transient HBM)")
    args = ap.parse_args()
    if args.preset == "1p3b":
        args.hidden, args.layers, args.heads = 2048, 24, 16
        args.seq = 2048
        args.fused_head = True
        args.param_dtype = args.param_dtype or "bfloat16"
        args.moment_dtype = args.moment_dtype or "bfloat16"

    import paddle_tpu as P
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       GPTPretrainingCriterion)
    from paddle_tpu.observability.profile import attached_chip
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    dev, chip = attached_chip()            # no TPU, unknown kind: error
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    enable_compile_cache()

    P.seed(0)
    cfg = GPTConfig(vocab_size=50304, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=args.heads,
                    max_seq_len=args.seq, dropout=0.0,
                    attention_dropout=0.0,
                    use_recompute=not args.no_recompute)
    model = GPTForCausalLM(cfg)
    if args.param_dtype:
        model.to(dtype=args.param_dtype)
    crit = GPTPretrainingCriterion()
    opt = P.optimizer.AdamW(learning_rate=1e-4,
                            parameters=model.parameters(),
                            moment_dtype=args.moment_dtype)
    n_params = sum(int(np.prod(q.shape)) for q in model.parameters())
    print(f"params: {n_params/1e6:.1f}M", flush=True)

    @P.jit.to_static
    def train_step(ids, labels):
        opt.clear_grad()
        with P.amp.auto_cast(level="O1", dtype="bfloat16"):
            if args.fused_head:
                loss = model.loss_with_fused_head(
                    ids, labels, chunk_size=args.ce_chunk)
            else:
                logits = model(ids)
                loss = crit(logits, labels)
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)
    ids = P.to_tensor(rng.integers(0, cfg.vocab_size,
                                   (args.batch, args.seq)), dtype="int64")
    labels = P.to_tensor(rng.integers(0, cfg.vocab_size,
                                      (args.batch, args.seq)),
                         dtype="int64")

    t0 = time.time()
    loss = train_step(ids, labels)
    loss.block_until_ready()
    print(f"compile+first step {time.time()-t0:.1f}s "
          f"loss={float(loss.numpy()):.3f}", flush=True)

    t0 = time.perf_counter()
    for _ in range(args.iters):
        loss = train_step(ids, labels)
    loss.block_until_ready()   # steps chain through optimizer state
    dt = (time.perf_counter() - t0) / args.iters

    tokens = args.batch * args.seq
    tok_s = tokens / dt
    # PaLM-style accounting: 6N matmul flops/token (fwd+bwd) plus causal
    # attention 6*L*h*s flops/token (dense would be 12*L*h*s; causal
    # halves it). Recompute re-runs the fwd, so HARDWARE flops are ~33%
    # higher — this reports MODEL mfu (useful work), like the bench.
    flops_per_token = 6.0 * n_params + \
        6.0 * args.layers * args.hidden * args.seq
    mfu = tok_s * flops_per_token / chip.peak_flops
    out = {"metric": "gpt_train_tokens_s", "value": round(tok_s, 1),
           "unit": "tokens/sec/chip", "platform": dev.platform,
           "device_kind": dev.device_kind,
           "params_m": round(n_params / 1e6, 1),
           "batch": args.batch, "seq": args.seq,
           "ms_per_step": round(dt * 1e3, 1),
           "recompute": cfg.use_recompute,
           "fused_head": bool(args.fused_head),
           "param_dtype": args.param_dtype or "float32",
           "moment_dtype": args.moment_dtype or "float32",
           "ce_chunk": args.ce_chunk if args.fused_head else None,
           "flops_per_token_g": round(flops_per_token / 1e9, 2),
           "mfu": round(mfu, 4)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
