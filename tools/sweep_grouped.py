"""Grouped-product sweep: ``jax.lax.ragged_dot`` against the Pallas
kernel ``grouped_matmul`` (``ops/pallas/grouped_matmul.py``) at its tile
candidates, at every grouped-product shape the three expert cells'
serving programs trace (decode or block pass, and each prefill bucket in
use), on the chip.  Its table is the evidence of ``pick_tiles``.

Each row: cell, program, rows ``m``, groups held, K, N, the path and its
tiles, the visits it makes, ms a product, the bytes it needs (each hit
group's weights once, the rows in, the f32 result out) over that time
against the 819 GB/s peak, and the FLOPs of the rows that lie in a group
against the 197 TFLOP/s peak.  ``pick`` marks what ``pick_tiles`` chooses;
``maxerr`` is the kernel's largest gap to ``ragged_dot`` over the rows in
a group (the compiled check).

Routing is drawn from the seed as the cells' seeded routers make it:
standard-normal logits plus an expert bias of std 0.5 (the heaviest
expert then gets ~3.5x the mean, as ``moe_block_imbalance.serve`` reads
3.6-4.0), top-k of the router's width; a holder of a share (Granite:
experts 0-35 of 72) sorts the others' rows past its last group.

Measurement: the product runs ``n`` times in one jitted loop whose input
moves each turn (no hoisting), with ``n`` a traced operand (one compile);
(t(n2) - t(n1)) / (n2 - n1), the least of ``--reps``, is device time.

Run on the chip:  python tools/sweep_grouped.py [--cells sdar,kanana]
                  [--out chiprun_out/sweep_grouped.jsonl]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

HBM_GBS = 819.0
MXU_TFLOPS = 197.0

# cell: (router width, experts held, top-k, hidden, expert width, decode
# tokens, prefill buckets in use)
CELLS = {
    "sdar": (128, 128, 8, 2048, 768, 32 * 4, (256, 512, 1024)),
    "kanana": (128, 128, 6, 2048, 768, 32, (128, 256, 512, 1024)),
    "granite": (72, 36, 10, 4096, 768, 64, (128, 256, 512, 1024)),
}


def routed_counts(rng, tokens, width, held, top_k):
    """Rows a held expert gets, and the rows in all (tokens x top_k)."""
    logits = rng.standard_normal((tokens, width)) + 0.5 * rng.standard_normal(
        (1, width))
    idx = np.argsort(-logits, axis=1)[:, :top_k].reshape(-1)
    counts = np.bincount(idx[idx < held], minlength=held)[:held]
    return counts.astype(np.int32), tokens * top_k


def products(cells):
    """(cell, program, tokens, m, groups, K, N) of every grouped product."""
    for cell in cells:
        width, held, top_k, d, f, dec, buckets = CELLS[cell]
        for program, tokens in [("decode", dec)] + [
                (f"prefill{b}", b) for b in buckets]:
            yield cell, program, tokens, held, d, 2 * f     # w13
            yield cell, program, tokens, held, f, d          # w2


def candidates(m, groups, k, n):
    """Row tiles round the rows a group gets; at a few rows a group (bound
    by the weights' bytes) N whole and halved as well."""
    if m > 64 * groups:
        return [(tm, n) for tm in (128, 256, 512)]
    tns = [n] + ([n // 2] if (n // 2) % 128 == 0 else [])
    return [(tm, tn) for tm in (64, 128, 256) for tn in tns]


def timer(jax, jnp, product, x, w, c):
    """Device ms of one product: a loop of n products, the input nudged
    each turn by the last result's first element."""
    def chained(x, w, c, n):
        def body(_, carry):
            xx, s = carry
            y = product(xx, w, c)
            return xx + (y[0, 0] * 0).astype(xx.dtype), s + y[0, 0]
        return jax.lax.fori_loop(0, n, body, (x, jnp.float32(0)))[1]

    f = jax.jit(chained)
    float(f(x, w, c, 1))

    def at(n, reps):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(f(x, w, c, n))
            best = min(best, time.perf_counter() - t0)
        return best

    return at


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="sdar,kanana,granite")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=20261015)
    ap.add_argument("--budget-ms", type=float, default=25.0,
                    help="device time of the longer loop's extra turns")
    ap.add_argument("--out", default="chiprun_out/sweep_grouped.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.grouped_matmul import (_visits,
                                                      grouped_matmul,
                                                      pick_tiles)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"sweep_grouped times the chip; this is "
                         f"{dev.platform}")
    print(f"device {dev.device_kind}, jax {jax.__version__}")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    rng = np.random.default_rng(args.seed)
    hdr = (f"{'cell':8} {'program':11} {'m':>6} {'G':>4} {'K':>5} {'N':>5} "
           f"{'path':16} {'visits':>6} {'ms':>8} {'GB/s':>6} {'%HBM':>5} "
           f"{'%MXU':>5} {'maxerr':>8}")
    print(hdr)
    with open(args.out, "w") as out:
        for cell, program, tokens, groups, k, n in products(
                args.cells.split(",")):
            width, held, top_k = CELLS[cell][:3]
            counts, m = routed_counts(rng, tokens, width, held, top_k)
            x = jnp.asarray(rng.standard_normal((m, k)) * 0.5, jnp.bfloat16)
            w = jnp.asarray(rng.standard_normal((groups, k, n)) * 0.02,
                            jnp.bfloat16)
            c = jnp.asarray(counts)
            inside = int(counts.sum())
            hit = int((counts > 0).sum())
            need = hit * k * n * 2 + m * k * 2 + m * n * 4
            flops = 2 * inside * k * n
            want = np.asarray(grouped_matmul(x, w, c))[:inside]
            pick = pick_tiles(m, groups, k, n, jnp.bfloat16, kernel=True)
            ms_ragged = None
            for tiles in [None] + candidates(m, groups, k, n):
                at = timer(jax, jnp, lambda a, b, cc, t=tiles:
                           grouped_matmul(a, b, cc, t), x, w, c)
                one = at(2, 1) - at(1, 1)
                n2 = 2 + max(4, int(args.budget_ms / max(one * 1e3, 0.05)))
                ms = (at(n2, args.reps) - at(2, args.reps)) / (n2 - 2) * 1e3
                err = 0.0
                if tiles is not None:
                    got = np.asarray(grouped_matmul(x, w, c, tiles))[:inside]
                    err = float(np.abs(got - want).max()) if inside else 0.0
                else:
                    ms_ragged = ms
                row = {
                    "cell": cell, "program": program, "m": m, "groups": groups,
                    "k": k, "n": n, "rows_in_groups": inside, "hit": hit,
                    "path": "ragged_dot" if tiles is None else "kernel",
                    "tiles": tiles,
                    "visits": (None if tiles is None else int(_visits(
                        c, -(-m // tiles[0]) * tiles[0], tiles[0])[3][0])),
                    "ms": ms, "gbs": need / ms / 1e6,
                    "hbm_pct": 100 * need / ms / 1e6 / HBM_GBS,
                    "mxu_pct": 100 * flops / ms / 1e9 / MXU_TFLOPS,
                    "speedup": ms_ragged / ms if ms_ragged else 1.0,
                    "maxerr": err, "pick": tiles == pick,
                }
                out.write(json.dumps(row) + "\n")
                out.flush()
                path = ("ragged_dot" if tiles is None
                        else f"gmm {tiles[0]}x{tiles[1]}")
                print(f"{cell:8} {program:11} {m:6d} {groups:4d} {k:5d} "
                      f"{n:5d} {path + (' *' if row['pick'] else ''):16} "
                      f"{row['visits'] or '':>6} {ms:8.4f} {row['gbs']:6.1f} "
                      f"{row['hbm_pct']:5.1f} {row['mxu_pct']:5.1f} "
                      f"{err:8.2e}", flush=True)


if __name__ == "__main__":
    main()
