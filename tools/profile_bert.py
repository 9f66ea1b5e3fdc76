"""Roofline profile of the bench's BERT-base train step on real TPU.

Answers "where does the other ~70% of MFU go" with data rather than
guesswork: XLA cost analysis of the compiled step gives flops and HBM
bytes; bytes/step over the measured step time vs the chip's HBM peak
tells whether the step is bandwidth-bound (like ResNet) or occupancy-
bound; the dot-shape census from the compiled HLO shows how much of the
time sits in GEMMs too narrow to fill the 128x128 MXU.

Usage: python tools/profile_bert.py [--batch N] [--iters N]
"""
from __future__ import annotations

import argparse
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fused_bert(P, cfg):
    """BERT-base MLM stack from the incubate fused blocks: each layer is
    FusedMultiHeadAttention (qkv+attn+proj+residual+LN in one region) +
    FusedFeedForward — the attention-epilogue-fusion A/B the r4 verdict
    asked for (#4). Same dims/flops as BertForPretraining; weights are
    freshly initialized (throughput comparison, not numerics)."""
    from paddle_tpu import nn
    from paddle_tpu.incubate.nn import (FusedFeedForward,
                                        FusedMultiHeadAttention)
    from paddle_tpu.models.bert import BertEmbeddings, BertLMHead

    class FusedBertMLM(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embeddings = BertEmbeddings(cfg)
            self.blocks = nn.LayerList()
            for _ in range(cfg.num_layers):
                self.blocks.append(FusedMultiHeadAttention(
                    cfg.hidden_size, cfg.num_heads, dropout_rate=0.0,
                    attn_dropout_rate=0.0, epsilon=cfg.layer_norm_epsilon))
                self.blocks.append(FusedFeedForward(
                    cfg.hidden_size, cfg.ffn_hidden_size,
                    dropout_rate=0.0, activation="gelu",
                    epsilon=cfg.layer_norm_epsilon))
            self.cls = BertLMHead(
                cfg, self.embeddings.word_embeddings.weight)

        def forward(self, ids):
            h = self.embeddings(ids)
            for blk in self.blocks:
                h = blk(h)
            return self.cls(h)

    return FusedBertMLM()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--fused", action="store_true",
                    help="A/B: encoder built from incubate "
                         "FusedMultiHeadAttention + FusedFeedForward "
                         "(attention-epilogue fusion experiment for the "
                         "mfu 0.35 push)")
    args = ap.parse_args()

    import paddle_tpu as P
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu.observability.profile import attached_chip
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    dev, chip = attached_chip()            # no TPU, unknown kind: error
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    enable_compile_cache()

    P.seed(0)
    cfg = BertConfig(dropout=0.0, attention_dropout=0.0)
    if args.fused:
        model = _fused_bert(P, cfg)
        print("encoder: incubate fused (MHA+FFN epilogue fusion)")
    else:
        model = BertForPretraining(cfg)
    opt = P.optimizer.AdamW(learning_rate=1e-4,
                            parameters=model.parameters())

    @P.jit.to_static
    def train_step(ids, labels):
        opt.clear_grad()
        with P.amp.auto_cast(level="O1", dtype="bfloat16"):
            out = model(ids)
            pred = out[0] if isinstance(out, tuple) else out
        loss = F.cross_entropy(
            pred.reshape([-1, cfg.vocab_size]), labels.reshape([-1]))
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)
    ids = P.to_tensor(rng.integers(0, cfg.vocab_size,
                                   (args.batch, args.seq)), dtype="int64")
    labels = P.to_tensor(rng.integers(0, cfg.vocab_size,
                                      (args.batch, args.seq)),
                         dtype="int64")
    loss = train_step(ids, labels)
    loss.block_until_ready()

    entry = next(iter(train_step._compiled.values()))
    compiled = entry.jitted.lower([t._value for t in entry.state_list],
                                  [ids._value, labels._value]).compile()
    cost = compiled.cost_analysis()
    flops = float(cost["flops"])
    bytes_acc = float(cost["bytes accessed"])
    print(f"xla flops/step: {flops:.3e}  bytes/step: {bytes_acc:.3e}")
    # dot-shape census: which GEMM shapes carry the flops
    hlo = compiled.as_text()
    shapes = {}
    for m in re.finditer(
            r"= (bf16|f32)\[([0-9,]+)\][^=]*? dot\(", hlo):
        key = f"{m.group(1)}[{m.group(2)}]"
        shapes[key] = shapes.get(key, 0) + 1
    top = sorted(shapes.items(), key=lambda kv: -kv[1])[:12]
    print("dot output shapes (count):")
    for k, c in top:
        print(f"  {c:4d}x {k}")
    print("fusions:", hlo.count(" fusion("),
          " custom-calls:", hlo.count("custom-call("),
          " copies:", hlo.count(" copy("))

    # free-running step time (bench's mode: serial dependence via state)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        loss = train_step(ids, labels)
    loss.block_until_ready()
    dt = (time.perf_counter() - t0) / args.iters

    tok_s = args.batch * args.seq / dt
    print(f"step {dt*1e3:.1f} ms  {tok_s:.0f} tokens/s")
    print(f"mfu (xla flops): {flops / dt / chip.peak_flops:.3f}")
    bw = bytes_acc / dt / 1e9
    print(f"hbm: {bytes_acc/1e9:.2f} GB/step -> {bw:.0f} GB/s "
          f"({bw / chip.hbm_gbs:.1%} of {chip.hbm_gbs:.0f})")
    ai = flops / bytes_acc
    print(f"arithmetic intensity {ai:.0f} flop/byte "
          f"({chip.name} ridge ~{chip.ridge:.0f}) -> "
          f"{'COMPUTE' if ai > chip.ridge else 'MEMORY'}"
          "-bound in the roofline sense")


if __name__ == "__main__":
    main()
