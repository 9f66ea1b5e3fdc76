"""Flash-attention schedule sweep.

Measures ops/pallas/flash_attention._flash_bhsd on the chip:

- the two benchmark cells' attention shapes (`gpt355m_train`: (4,16,2048,64)
  causal; `bert_base_train`: (48,12,512,64) dense) and the 16k / d128 guard
  shape, each under the schedule `_pick_blocks` gives it and under the
  explicit 1024 x 1024 blocks, with what the schedule runs / masks / skips
  (`schedule_counts`), ms a call and TFLOP/s;
- the (block_q, block_k) grid at 16k / d128 (dense and causal).

Run on the chip:
  python tools/sweep_flash.py --cells [--bwd]    the cells' rows only
  python tools/sweep_flash.py [--quick] [--bwd]  rows + the 16k grid
  python tools/sweep_flash.py --explore          candidate schedules at the
      cells' shapes, forward / dq / dkv apart (what `_pick_blocks` was
      tuned from; each candidate stands in for its answer)

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

# (label, (batch, heads, seq, head_dim), causal, forward scan lengths,
# forward+backward scan lengths): the length delta targets ~100 ms of
# pure kernel time so host jitter (~ms) is noise
CELL_SHAPES = [
    ("gpt355m_train", (4, 16, 2048, 64), True, (8, 136), (4, 36)),
    ("bert_base_train", (48, 12, 512, 64), False, (8, 104), (4, 36)),
    ("16k", (1, 4, 16384, 128), True, (2, 18), (1, 9)),
    # in no cell: shapes between the three above, so that the rule
    # `_pick_blocks` draws through them is seen where it was not tuned
    ("s1024_d64", (8, 16, 1024, 64), True, (8, 136), (4, 36)),
    ("s4096_d64", (2, 16, 4096, 64), True, (8, 72), (4, 20)),
    ("s2048_d128", (2, 16, 2048, 128), True, (8, 136), (4, 36)),
    ("s8192_d64", (1, 8, 8192, 64), True, (4, 36), (2, 10)),
]

# a schedule `_pick_blocks` turned down, timed beside its pick: at s1024 /
# d64 the resident walk of 512 x 512 tiles (the single tile is taken)
TURNED_DOWN = {"s1024_d64": (512, 512, 2, True)}


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


def _qkv(jnp, shape, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                 for _ in range(3))


def fwd_chain(jax, jnp, lax, flash, causal, scale, bq, bk):
    """make(n): n forward calls, each output the next call's query."""
    def make(n):
        def chained(q, k, v):
            def step(qc, _):
                o = flash(qc, k, v, causal, scale, bq, bk, False)
                return o.astype(qc.dtype), ()
            qf, _ = lax.scan(step, q, None, length=n)
            return jnp.sum(qf.astype(jnp.float32))
        return chained
    return make


def bwd_chain(jax, jnp, lax, flash, causal, scale, bq, bk, wrt=(0, 1, 2)):
    """make(n): n forward+backward calls; the query's gradient (or, where
    `wrt` leaves it out, the output) feeds the next query, every other
    gradient folds into the carried scalar so DCE keeps it.  `wrt` (0,)
    leaves flash_dkv dead and (1, 2) flash_dq: XLA drops the unused call,
    so the three kernels can be timed apart."""
    def make(n):
        def chained(q, k, v):
            def loss(qq, kk, vv):
                o = flash(qq, kk, vv, causal, scale, bq, bk, False)
                return jnp.sum(o.astype(jnp.float32))

            def step(carry, _):
                qc, aux = carry
                val, grads = jax.value_and_grad(loss, argnums=wrt)(qc, k, v)
                grads = dict(zip(wrt, grads))
                if 0 in grads:
                    qn = jnp.clip(grads.pop(0), -3.0, 3.0).astype(qc.dtype)
                else:
                    qn = jnp.clip(qc + grads[1] * 1e-3, -3.0,
                                  3.0).astype(qc.dtype)
                aux = aux + val + sum(jnp.sum(g.astype(jnp.float32))
                                      for g in grads.values())
                return (qn, aux), ()

            (qf, aux), _ = lax.scan(step, (q, jnp.float32(0.0)), None,
                                    length=n)
            return jnp.sum(qf.astype(jnp.float32)) + aux
        return chained
    return make


def _force_schedule(jax, fa, pick):
    """Replace `_pick_blocks` (the caller puts the module's own back the
    same way); the kernels are traced once a shape, so what was traced
    under the other schedule is dropped."""
    fa._pick_blocks = pick
    jax.clear_caches()


def _counts_line(shape, causal, bq, bk):
    """The schedule helper's line for a row (absent on a tree that has no
    such helper: the sweep also runs against the parent's kernels)."""
    try:
        from paddle_tpu.ops.pallas.flash_attention import schedule_counts
    except ImportError:
        return "schedule n/a"
    b, h, s, d = shape
    c = schedule_counts(s, s, d, causal, "bfloat16", bq, bk)
    return ("%s grid %s/%s tiles run %d masked %d skipped %d" % (
        tuple(c["schedule"]), c["grid_fwd_dq"], c["grid_dkv"],
        c["tiles_run"], c["tiles_masked"], c["tiles_skipped"]))


def cell_rows(jax, jnp, lax, fa, bwd):
    """The cells' shapes under the picked schedule, under explicit 1024 x
    1024 blocks and under the schedule `_pick_blocks` turned down, if one
    is listed: ms a call and TFLOP/s of the needed FLOPs (2 products
    forward, 9 with the backward's 7; causal halves)."""
    rows = []
    flash, picked = fa._flash_bhsd, fa._pick_blocks
    for name, shape, causal, lens_f, lens_b in CELL_SHAPES:
        b, h, s, d = shape
        q, k, v = _qkv(jnp, shape)
        scale = float(d) ** -0.5
        dots = 9 if bwd else 2
        flops = dots * 2.0 * b * h * s * s * d * (0.5 if causal else 1.0)
        chain = bwd_chain if bwd else fwd_chain
        # (label, block_q, block_k, forced schedule)
        candidates = [("picked", None, None, None),
                      ("1024x1024", 1024, 1024, None)]
        if name in TURNED_DOWN and hasattr(fa, "Schedule"):
            sched = fa.Schedule(*TURNED_DOWN[name])
            candidates.append(("turned down %dx%d span %d" % sched[:3],
                               None, None, sched))
        for label, bq, bk, sched in candidates:
            head = f"{name} {'fwd+bwd' if bwd else 'fwd'} {label}: "
            if sched:
                _force_schedule(jax, fa, lambda *a, s=sched, **kw: s)
            try:
                t = delta_time(chain(jax, jnp, lax, flash, causal, scale,
                                     bq, bk), (q, k, v),
                               *(lens_b if bwd else lens_f))
                line = (f"{head}{t * 1e3:.3f} ms  "
                        f"{flops / t / 1e12:.1f} TFLOP/s  "
                        f"[{_counts_line(shape, causal, bq, bk)}]")
            except Exception as e:  # noqa: BLE001
                line = f"{head}ERROR {type(e).__name__}: {str(e)[:200]}"
            if sched:
                _force_schedule(jax, fa, picked)
            rows.append(line)
            print(line, flush=True)
    return rows


# (block_q, block_k, span, key_major) candidates per cell shape; span is
# clamped to the tiles each walk has, key_major is the forward's layout
EXPLORE = {
    "gpt355m_train": [
        (1024, 1024, 1, True), (1024, 1024, 2, True), (1024, 1024, 2, False),
        (512, 512, 1, True), (512, 512, 4, True), (512, 512, 4, False),
        (256, 256, 8, True), (512, 256, 8, True), (256, 512, 4, True),
        (512, 1024, 2, True), (1024, 512, 4, True), (2048, 512, 4, True),
    ],
    "bert_base_train": [
        (512, 512, 1, True), (512, 512, 1, False), (256, 512, 2, True),
        (512, 256, 2, True), (256, 256, 2, True),
    ],
    "16k": [(1024, 1024, 1, True), (1024, 1024, 1, False)],
}


def explore(jax, jnp, lax, fa):
    """Candidate schedules at the cells' shapes, the three kernels apart:
    forward alone; forward + dq (grad wrt q only); forward + dkv."""
    rows = []
    picked = fa._pick_blocks
    for name, shape, causal, lens_f, lens_b in CELL_SHAPES:
        if name not in EXPLORE:
            continue
        b, h, s, d = shape
        q, k, v = _qkv(jnp, shape)
        scale = float(d) ** -0.5
        for cand in EXPLORE[name]:
            _force_schedule(
                jax, fa, lambda *a, sched=fa.Schedule(*cand), **kw: sched)
            ms = {}
            for what, make, lens in (
                    ("fwd", fwd_chain(jax, jnp, lax, fa._flash_bhsd, causal,
                                      scale, None, None), lens_f),
                    ("fwd+dq", bwd_chain(jax, jnp, lax, fa._flash_bhsd,
                                         causal, scale, None, None, (0,)),
                     lens_b),
                    ("fwd+dkv", bwd_chain(jax, jnp, lax, fa._flash_bhsd,
                                          causal, scale, None, None, (1, 2)),
                     lens_b)):
                try:
                    ms[what] = round(delta_time(make, (q, k, v), *lens)
                                     * 1e3, 4)
                except Exception as e:  # noqa: BLE001
                    ms[what] = f"ERR {type(e).__name__}: {str(e)[:120]}"
            row = (name, cand, ms)
            rows.append(row)
            print("explore", json.dumps(row), flush=True)
    _force_schedule(jax, fa, picked)
    return rows


def _grid_rows(jax, jnp, lax, flash, bwd, blocks, label, shape, lens,
               skip=()):
    """(label, causal, block_q, block_k, TFLOP/s) over the block grid."""
    b, h, s, d = shape
    q, k, v = _qkv(jnp, shape)
    scale = float(d) ** -0.5
    chain = bwd_chain if bwd else fwd_chain
    what = "fwd+bwd" if bwd else "fwd"
    rows = []
    for causal in (False, True):
        # needed FLOPs: 2 products of 2*s*s*d a (b, h) forward, 9 with the
        # backward (dq-kernel 3 + dkv-kernel 4); causal halves
        flops = ((18.0 if bwd else 4.0) * b * h * s * s * d
                 * (0.5 if causal else 1.0))
        for bq in blocks:
            for bk in blocks:
                if bq > s or bk > s or (bq, bk) in skip:
                    continue
                try:
                    t = delta_time(chain(jax, jnp, lax, flash, causal, scale,
                                         bq, bk), (q, k, v), *lens)
                    tf = round(flops / t / 1e12, 1)
                    print(f"{label} {what} causal={causal} bq={bq} bk={bk}: "
                          f"{tf:.1f} TFLOP/s", flush=True)
                except Exception as e:  # noqa: BLE001
                    tf = f"ERR {type(e).__name__}"
                    print(f"{label} {what} causal={causal} bq={bq} bk={bk}: "
                          f"ERROR {e}", flush=True)
                rows.append((label, causal, bq, bk, tf))
    return rows


def _best_lines(rows):
    best = {}
    for name, causal, bq, bk, tf in rows:
        if isinstance(tf, float):
            key = (name, causal)
            if key not in best or tf > best[key][2]:
                best[key] = (bq, bk, tf)
    return [f"- {name} causal={causal}: best {tf} TFLOP/s at "
            f"block_q={bq}, block_k={bk}\n"
            for (name, causal), (bq, bk, tf) in sorted(best.items())]


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.observability.profile import attached_chip
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    dev, chip = attached_chip()            # no TPU, unknown kind: error
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    peak = chip.peak_flops
    enable_compile_cache()
    flash = fa._flash_bhsd
    bwd = "--bwd" in sys.argv
    if "--explore" in sys.argv:
        explore(jax, jnp, lax, fa)
        return 0
    cells = cell_rows(jax, jnp, lax, fa, bwd)
    lines = [f"\n## Flash schedule sweep ({dev.device_kind}, scan-chained "
             "two-length delta timing)\n"]
    lines += [f"- {row}\n" for row in cells]
    if "--cells" in sys.argv:
        print("".join(lines))
        return 0

    if bwd:
        # fwd+bwd (training-path) block grid at the 16k headline shape;
        # 2048 x 2048 is left out: the fwd kernel VMEM-OOMs at this combo
        rows = _grid_rows(jax, jnp, lax, flash, True, (512, 1024, 2048),
                          "16k-train", (1, 4, 16384, 128), (1, 9),
                          skip=((2048, 2048),))
        lines += _best_lines(rows)
        lines.append("- full grid: " + json.dumps(rows) + "\n")
        print("".join(lines))
        return 0

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

    blocks = ([256, 512, 1024] if "--quick" in sys.argv
              else [128, 256, 512, 1024, 2048])
    rows = _grid_rows(jax, jnp, lax, flash, False, blocks, "16k",
                      (1, 4, 16384, 128), (2, 18))
    lines += [f"- VPU probe (Gop/s): {json.dumps(vpu)}\n",
              f"- measured-VPU roofline: d=128 attention ceiling ≈ "
              f"{ceiling:.2%} of MXU peak "
              f"({ceiling * peak / 1e12:.0f} TFLOP/s) — softmax VPU ops vs "
              f"4d MXU flops per score element\n"]
    lines += _best_lines(rows)
    lines.append("- full grid: " + json.dumps(rows) + "\n")
    print("".join(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
