"""Grouped-query decode sweep: the XLA read at table width
(``grouped_paged_attend``: the whole table gathered, masked) against the
Pallas kernel ``grouped_paged_decode`` at several compute blocks, at the
full layers of the two cells that decode through it, on the chip.

Each row: cell, path, pages per compute block, ms a call (one layer), the
live K/V bytes a call needs (each live page's K and V rows once) over that
time against the 819 GB/s peak, and the kernel's largest gap to the XLA
read over the slots (bf16).

Lengths are drawn from the seed as the cells' mixes make them: a prompt
log-uniform over the mix's range plus a uniform share of the answer.

Measurement: the call runs ``n`` times in one jitted loop whose query
moves each turn (no hoisting), ``n`` a traced operand (one compile);
(t(n2) - t(n1)) / (n2 - n1), the least of ``--reps``, is device time.

Run on the chip:  python tools/sweep_grouped_decode.py
                  [--out chiprun_out/sweep_grouped_decode.jsonl]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

HBM_GBS = 819.0
PAGE = 16

# cell: (slots, query heads, K/V heads, pages a table, score scale,
#        prompt range, answer range)
CELLS = {
    "smallthinker": (32, 28, 4, 1024, 128 ** -0.5, (256, 14336), (512, 1536)),
    "granite": (64, 32, 8, 128, 1 / 128, (128, 1024), (64, 256)),
}


def lengths(rng, slots, prompt, answer):
    """Each slot's stored positions: a log-uniform prompt and a uniform
    share of a uniform answer."""
    p = np.exp(rng.uniform(np.log(prompt[0]), np.log(prompt[1]), slots))
    a = rng.uniform(0, 1, slots) * rng.uniform(*answer, slots)
    return (p + a).astype(np.int32)


def timed(step, args, reps):
    """Device ms a call of ``step(q, *rest) -> out [like q]``."""
    import jax

    @jax.jit
    def loop(n, q, *rest):
        def body(_, q):
            return q + 0 * step(q, *rest).astype(q.dtype)
        return jax.lax.fori_loop(0, n, body, q)

    n1, n2 = 4, 24
    loop(n1, *args).block_until_ready()
    best = []
    for n in (n1, n2):
        ts = []
        for _ in range(reps):
            t = time.perf_counter()
            loop(n, *args).block_until_ready()
            ts.append(time.perf_counter() - t)
        best.append(min(ts))
    return 1e3 * (best[1] - best[0]) / (n2 - n1)


def sweep(cell, reps, seed):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.nn.paged_attention import grouped_paged_attend
    from paddle_tpu.ops.pallas.paged_attention import grouped_paged_decode
    slots, hq, hk, width, scale, prompt, answer = CELLS[cell]
    rng = np.random.default_rng(seed)
    lens = lengths(rng, slots, prompt, answer)
    n = slots * width + 1
    key = jax.random.PRNGKey(seed)
    kp = jax.random.normal(key, (n, PAGE, hk * 128), jnp.bfloat16)
    vp = jax.random.normal(jax.random.fold_in(key, 1), kp.shape, jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 2), (slots, hq, 128),
                          jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(n - 1).reshape(slots, width),
                         jnp.int32)
    lens_a = jnp.asarray(lens)
    live_pages = int(np.sum(-(-lens // PAGE)))
    need = 2 * live_pages * PAGE * hk * 128 * 2

    def xla(q, kp, vp, t, n):
        return grouped_paged_attend(q[:, None], kp, vp, t, n, scale)[:, 0]

    ref = np.asarray(jax.jit(xla)(q, kp, vp, tables, lens_a), np.float32)
    rows = [{"cell": cell, "path": "xla", "ppb": None,
             "ms": timed(xla, (q, kp, vp, tables, lens_a), reps)}]
    for ppb in (8, 16, 32, 64):
        def kern(q, kp, vp, t, n, ppb=ppb):
            return grouped_paged_decode(q, kp, vp, t, n, scale=scale,
                                        pages_per_block=ppb)
        out = np.asarray(jax.jit(kern)(q, kp, vp, tables, lens_a),
                         np.float32)
        rows.append({"cell": cell, "path": "grouped_paged_decode",
                     "ppb": ppb, "maxerr": float(np.max(np.abs(out - ref))),
                     "ms": timed(kern, (q, kp, vp, tables, lens_a), reps)})
    for r in rows:
        r.update(live_pages=live_pages, mean_len=float(lens.mean()),
                 hbm_pct=100.0 * need / (r["ms"] * 1e-3) / (HBM_GBS * 1e9))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=20260)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    rows = []
    for cell in args.cells.split(","):
        for r in sweep(cell, args.reps, args.seed):
            rows.append(r)
            print(json.dumps(r), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)


if __name__ == "__main__":
    main()
