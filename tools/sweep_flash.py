"""Flash-attention block-size sweep.

Measures fwd TFLOP/s of ops/pallas/flash_attention._flash_bhsd across
(block_q, block_k) at the headline shape (16k seq, d=128, bf16) plus a
BERT-shaped case, dense and causal, and prints the table.
Run on the chip:  python tools/sweep_flash.py [--quick] [--bwd]

Measurement design: chain the kernel inside ONE jit with lax.scan
(output feeds the next input — no CSE, no overlap), sync by fetching a
scalar, and time the SAME computation at two scan lengths; the length
difference cancels every constant (dispatch, launch, transfer) and the
delta is pure device time — single-call timing undercounts small
kernels, whose time is mostly those constants.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _timed_scalar(fn, *args, reps=3):
    """Compile fn (returns a scalar), run once to warm, then take the
    min wall time of `reps` synced calls (min cuts host jitter)."""
    import jax
    f = jax.jit(fn)
    float(f(*args))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(f(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def delta_time(make_chained, args, n1, n2):
    """Pure per-iteration device time via two-length subtraction:
    (t(n2-iter chain) - t(n1-iter chain)) / (n2 - n1)."""
    d1 = _timed_scalar(make_chained(n1), *args)
    d2 = _timed_scalar(make_chained(n2), *args)
    return max(d2 - d1, 1e-9) / (n2 - n1)


def vpu_probe(jax, jnp):
    """Measure the VPU's elementwise/transcendental throughput — the
    flash softmax (max, sub, exp2, sum, cast ≈ 6-8 VPU ops per score
    element) competes with the MXU dots (4·d flops per element). The
    attention ceiling is MXU_t / (MXU_t + VPU_t); whether ~26% kernel
    efficiency at d=128 is a defect or the roofline depends entirely on
    the real VPU rate, so measure it."""
    from jax import lax

    out = {}
    x0 = jnp.linspace(-4, 4, 4096 * 4096).reshape(4096, 4096)
    cases = (
        # clip keeps the scan chain bounded; counted as part of the
        # "exp2-class" op mix (softmax also pairs exp2 with a sub)
        ("exp2_f32", jnp.float32,
         lambda a: jnp.exp2(jnp.clip(a, -4.0, 4.0))),
        ("exp2_bf16", jnp.bfloat16,
         lambda a: jnp.exp2(jnp.clip(a, -4.0, 4.0))),
        ("addmul_f32", jnp.float32, lambda a: a * 1.5 + 0.5),
    )
    for name, dtype, op in cases:
        a0 = x0.astype(dtype)

        def make(n, op=op):
            def chained(a):
                def step(c, _):
                    return op(c), ()
                c, _ = lax.scan(step, a, None, length=n)
                return jnp.sum(c.astype(jnp.float32))
            return chained

        t_iter = delta_time(make, (a0,), 8, 520)
        out[name] = round(a0.size / t_iter / 1e9, 1)  # Gop/s
    return out


def bwd_sweep(jax, jnp, lax, _flash_bhsd, dev):
    """fwd+bwd (training-path) block sweep at the 16k headline shape.
    FLOP accounting from the kernel structure: fwd 2 dots + dq-kernel 3 +
    dkv-kernel 4 = 9 dots of 2·s²·d each per (b,h); causal halves."""
    b, h, s, d = 1, 4, 16384, 128
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    scale = float(d) ** -0.5
    rows = []
    for causal in (False, True):
        flops = 18.0 * b * h * s * s * d * (0.5 if causal else 1.0)
        for bq in (512, 1024, 2048):
            for bk in (512, 1024, 2048):
                if bq == 2048 and bk == 2048:
                    continue  # fwd kernel VMEM-OOMs at this combo
                try:
                    def make(n, bq=bq, bk=bk, c=causal):
                        def chained(q, k, v):
                            def loss(qq, kk, vv):
                                o = _flash_bhsd(qq, kk, vv, c, scale,
                                                bq, bk, False)
                                return jnp.sum(o.astype(jnp.float32))

                            def step(carry, _):
                                qc, aux = carry
                                val, (dq, dk, dv) = jax.value_and_grad(
                                    loss, argnums=(0, 1, 2))(qc, k, v)
                                # dq feeds the next query; dk/dv fold into
                                # the carried scalar so DCE keeps them
                                qn = jnp.clip(dq, -3.0, 3.0).astype(
                                    qc.dtype)
                                aux = aux + val + jnp.sum(
                                    dk.astype(jnp.float32)) + jnp.sum(
                                    dv.astype(jnp.float32))
                                return (qn, aux), ()

                            (qf, aux), _ = lax.scan(
                                step, (q, jnp.float32(0.0)), None,
                                length=n)
                            return jnp.sum(qf.astype(jnp.float32)) + aux
                        return chained

                    t_iter = delta_time(make, (q, k, v), 1, 9)
                    tf = flops / t_iter / 1e12
                    rows.append(("16k-train", causal, bq, bk,
                                 round(tf, 1)))
                    print(f"16k fwd+bwd causal={causal} bq={bq} bk={bk}: "
                          f"{tf:.1f} TFLOP/s", flush=True)
                except Exception as e:  # noqa: BLE001
                    rows.append(("16k-train", causal, bq, bk,
                                 f"ERR {type(e).__name__}"))
                    print(f"16k fwd+bwd causal={causal} bq={bq} bk={bk}: "
                          f"ERROR {e}", flush=True)
    best = {}
    for name, causal, bq, bk, tf in rows:
        if isinstance(tf, float):
            key = causal
            if key not in best or tf > best[key][2]:
                best[key] = (bq, bk, tf)
    lines = [f"\n## Flash fwd+bwd block sweep ({dev.device_kind}, "
             "two-length delta timing; 9 dots = 18·bh·s²·d flops)\n"]
    for causal, (bq, bk, tf) in sorted(best.items()):
        lines.append(f"- 16k train causal={causal}: best {tf} TFLOP/s at "
                     f"block_q={bq}, block_k={bk}\n")
    lines.append("- full grid: " + json.dumps(rows) + "\n")
    print("".join(lines))
    return 0


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.observability.profile import attached_chip
    from paddle_tpu.ops.pallas.flash_attention import _flash_bhsd
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    dev, chip = attached_chip()            # no TPU, unknown kind: error
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    peak = chip.peak_flops
    enable_compile_cache()
    quick = "--quick" in sys.argv
    if "--bwd" in sys.argv:
        return bwd_sweep(jax, jnp, lax, _flash_bhsd, dev)

    vpu = vpu_probe(jax, jnp)
    print("VPU probe (Gop/s):", json.dumps(vpu), flush=True)
    # predicted attention ceiling at d=128 against the chip's bf16 MXU
    # peak, ~7 VPU ops per score element at the measured exp2-class rate
    vpu_rate = vpu["exp2_f32"] * 1e9
    mxu_t = 4 * 128 / peak
    vpu_t = 7 / vpu_rate
    ceiling = mxu_t / (mxu_t + vpu_t)
    print(f"predicted d=128 attention ceiling ≈ {ceiling:.2%} of MXU "
          f"peak ({ceiling * peak / 1e12:.0f} TFLOP/s)", flush=True)

    # (label, b, h, s, d, scan-length pair): the length delta targets
    # ~50-150 ms of pure kernel time so host jitter (~ms) is noise
    shapes = [("16k", 1, 4, 16384, 128, (2, 18)),
              ("bert", 16, 12, 512, 64, (16, 272))]
    blocks = [256, 512, 1024] if quick else [128, 256, 512, 1024, 2048]
    rows = []
    for name, b, h, s, d, lens in shapes:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
        scale = float(d) ** -0.5
        for causal in (False, True):
            # FLOPs: 2 matmuls of 2*s*s*d each per (b, h); causal halves
            flops = 4.0 * b * h * s * s * d * (0.5 if causal else 1.0)
            for bq in blocks:
                for bk in blocks:
                    if bq > s or bk > s:
                        continue
                    try:
                        def make(n, bq=bq, bk=bk, c=causal):
                            def chained(q, k, v):
                                # output feeds the next query: serial on
                                # the device stream, immune to CSE
                                def step(qc, _):
                                    o = _flash_bhsd(qc, k, v, c, scale,
                                                    bq, bk, False)
                                    return o.astype(qc.dtype), ()
                                qf, _ = lax.scan(step, q, None, length=n)
                                return jnp.sum(qf.astype(jnp.float32))
                            return chained

                        t_iter = delta_time(make, (q, k, v), *lens)
                        tf = flops / t_iter / 1e12
                        rows.append((name, causal, bq, bk, round(tf, 1)))
                        print(f"{name} causal={causal} bq={bq} bk={bk}: "
                              f"{tf:.1f} TFLOP/s", flush=True)
                    except Exception as e:  # noqa: BLE001
                        rows.append((name, causal, bq, bk,
                                     f"ERR {type(e).__name__}"))
                        print(f"{name} causal={causal} bq={bq} bk={bk}: "
                              f"ERROR {e}", flush=True)

    best = {}
    for name, causal, bq, bk, tf in rows:
        if isinstance(tf, float):
            key = (name, causal)
            if key not in best or tf > best[key][2]:
                best[key] = (bq, bk, tf)
    lines = [f"\n## Flash block sweep ({dev.device_kind}, "
             "scan-chained two-length delta timing)\n",
             f"- VPU probe (Gop/s): {json.dumps(vpu)}\n",
             f"- measured-VPU roofline: d=128 attention ceiling ≈ "
             f"{ceiling:.2%} of MXU peak "
             f"({ceiling * peak / 1e12:.0f} TFLOP/s) — softmax VPU ops vs "
             f"4d MXU flops per score element\n"]
    for (name, causal), (bq, bk, tf) in sorted(best.items()):
        lines.append(f"- {name} causal={causal}: best {tf} TFLOP/s at "
                     f"block_q={bq}, block_k={bk}\n")
    lines.append("- full grid: " + json.dumps(rows) + "\n")
    print("".join(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
