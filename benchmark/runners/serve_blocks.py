"""Runner ``serve_blocks``: runner ``serve``'s build, warm-up and open loop
(by import: the loop, the window and the stamps are unchanged) for a model
that generates by DIFFUSION OVER BLOCKS, with the tail of ``serve.run`` as
its own:

- the window's FLOPs are counted by the FAMILY's ``serve_flops`` (one
  forward a position, ``models/<family>.py``);
- ``runners/serve.py``'s ``reference_gaps`` compares a served token with a
  CAUSAL forward over the final ids.  Here a token fixed at denoising pass
  ``t`` was chosen from a block that still held masks, so the reference
  REPLAYS THE SERVED TRAJECTORY: for every delivered position the engine
  keeps the pass within its block that fixed it (``Request.fixed_at``; the
  finished requests are matched to the mix's by their prompts before the
  engine is shut down), and the reference's ``denoise_logits`` runs pass
  ``t`` of every checked block in one forward — each block in the state it
  had before that pass (positions fixed earlier hold the served tokens, the
  rest the mask token).  A request's first block, if it opened on leftover
  prompt tokens, and a last block delivered in part are left out (they took
  fewer passes, or their undelivered positions are not known).

Compared numbers, over the mix's ``checked_requests`` finished greedy
requests, from what the timed path served:

- ``logit_gap_mean``: per served token, in the reference's logits at the
  pass that fixed it, the best logit at that position less the served
  token's; the mean (the maximum is printed);
- ``order_gap_mean``: per pass of a block, the highest reference confidence
  among the positions then masked less that of the position the engine
  fixed, over the highest; the mean.  Held only if the cell's file gives it
  a limit.

``serve._sampling`` passes no decoding rule, so the mix runs under the
engine's DEFAULTS; the mix's ``sampling.denoising_steps`` / ``remasking``
state them and are checked against the engine's.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import stats, traffic as traffic_gen
from benchmark.reference import common as refc
from benchmark.runners import serve
from benchmark.runners.serve import (build, checked_sample,  # noqa: F401
                                     drive, warm)
from benchmark.runners.serve_family_flops import window_flops


def check_defaults(ctx, engine):
    """The mix states the decoding rule the engine's defaults give."""
    from paddle_tpu import serving
    sampling, gen = ctx.traffic["sampling"], engine._gen
    sp = serving.SamplingParams()
    steps = sp.denoising_steps or gen.rows
    rule = sp.remasking or serving.SamplingParams.REMASKING[0]
    if (sampling["denoising_steps"], sampling["remasking"]) != (steps, rule) \
            or gen.rows != ctx.cfg["block_length"]:
        raise ValueError(
            f"the mix states {sampling['denoising_steps']} passes / "
            f"{sampling['remasking']} over blocks of "
            f"{ctx.cfg['block_length']}; the engine's defaults are {steps} "
            f"/ {rule} over blocks of {gen.rows}")


def pass_records(engine, requests):
    """Per request of the mix, the pass that fixed each of its delivered
    positions — ``Request.fixed_at`` of the engine's finished request with
    the same prompt (None where it is not among them)."""
    by_prompt = {tuple(r.prompt_token_ids): r
                 for r in engine.finished_requests.values()}
    out = []
    for req in requests:
        done = by_prompt.get(tuple(req.prompt))
        out.append(None if done is None else
                   (list(done.output_token_ids), list(done.fixed_at)))
    return out


def checked_blocks(cfg, prompt, tokens, fixed_at):
    """(first, stop, passes): the positions ``first .. stop - 1`` of the
    whole generated blocks that can be replayed, and for each the pass that
    fixed it."""
    B = cfg["block_length"]
    L = len(prompt)
    first = L // B * B + (B if L % B else 0)
    stop = (L + len(tokens)) // B * B
    if stop <= first:
        return first, first, np.zeros((0,), np.int64)
    return first, stop, np.asarray(fixed_at[first - L:stop - L], np.int64)


def trajectory_gaps(ctx, requests, served, records, picks, mode="f32"):
    """{"logit": per served token its logit gap, "order": per pass of a
    block its order gap} over the checked blocks of ``picks``.  With
    ``mode`` other than f32 (the control) the 'served' token at a position
    is the one that precision of the reference puts first at the pass that
    fixed it, and the position 'fixed' at a pass the one it gives the
    highest confidence among those then masked."""
    import jax
    import jax.numpy as jnp
    ref, cfg, mix = ctx.family.reference, ctx.cfg, ctx.traffic
    B, M = cfg["block_length"], cfg["mask_token_id"]
    steps = mix["sampling"]["denoising_steps"]
    weights = refc.make_weights(ref.weight_spec(cfg), ctx.seed,
                                jnp.dtype(mix["engine"]["dtype"]))
    pad = mix["reference_pad_to"]
    rows = -(-mix["output_len"]["hi"] // B) * B + B

    @jax.jit
    def one_pass(weights, clean, noisy, first, served_ids):
        full = ref.denoise_logits(cfg, weights, clean, noisy, first,
                                  "f32")[0]
        low = full if mode == "f32" else ref.denoise_logits(
            cfg, weights, clean, noisy, first, mode)[0]
        chosen = served_ids if mode == "f32" else jnp.argmax(low, -1)
        gap = jnp.max(full, -1) - jnp.take_along_axis(
            full, chosen[:, None], -1)[:, 0]

        def conf(z):
            return jnp.exp(jnp.max(z, -1) - jax.nn.logsumexp(z, -1))
        return gap, conf(full), conf(low)

    logit, order = [], []
    for k in picks:
        prompt, (tokens, fixed_at) = requests[k].prompt, records[k]
        assert tokens == served["tokens"][k]
        first, stop, fixed = checked_blocks(cfg, prompt, tokens, fixed_at)
        n = stop - first
        if not n:
            continue
        seq = np.asarray(prompt + tokens, np.int32)
        clean = np.zeros((1, pad), np.int32)
        clean[0, :len(seq)] = seq
        mine = np.zeros((rows,), np.int32)
        mine[:n] = seq[first:stop]
        for t in range(steps):
            noisy = np.full((1, rows), M, np.int32)
            noisy[0, :n] = np.where(fixed < t, seq[first:stop], M)
            gap, conf, conf_low = (np.asarray(x)[:n] for x in one_pass(
                weights, clean, noisy, np.int32(first), mine))
            logit.append(gap[fixed == t])
            for b0 in range(0, n, B):
                block = slice(b0, b0 + B)
                masked = fixed[block] >= t
                if not masked.any():
                    continue
                now = (fixed[block] == t if mode == "f32" else
                       np.arange(B) == np.argmax(
                           np.where(masked, conf_low[block], -1.0)))
                if not now.any():
                    continue
                best = conf[block][masked].max()
                order.append((best - conf[block][now].min()) / best)
    return {"logit": np.concatenate(logit) if logit else np.zeros((0,)),
            "order": np.asarray(order)}


def reference_gaps(ctx, requests, served, picks, mode="f32"):
    """What ``tools/read_limits_blocks.py`` prints for a seed."""
    g = trajectory_gaps(ctx, requests, served, served["pass_records"], picks,
                        mode)
    if not g["logit"].size:
        return {}
    lg = g["logit"]
    return {"mean": float(lg.mean()), "max": float(lg.max()),
            "q50": float(np.quantile(lg, 0.5)),
            "q99": float(np.quantile(lg, 0.99)),
            "over_0.1": float((lg > 0.1).mean()), "tokens": int(lg.size),
            "order_mean": float(g["order"].mean()),
            "order_passes": int(g["order"].size)}


def block_gap_modes(ctx, served, gaps):
    """Where ``itl_p95_ms`` sits: a block's tokens share one stamp, so of a
    stream's gaps three in four are 0 and the rest are the gaps between its
    BLOCKS (five passes and the prefills inside them)."""
    B = ctx.cfg["block_length"]
    between = [g for g in gaps if g > 0.0]
    if not between:
        return
    p95 = stats.percentile(gaps, 95)
    ctx.note(f"token gaps: {len(gaps)} in all, "
             f"{100 * (1 - len(between) / len(gaps)):.1f}% are 0 (inside a "
             f"block of {B}); between blocks: median "
             f"{stats.median(between):.2f} ms, p80 "
             f"{stats.percentile(between, 80):.2f} ms, p95 "
             f"{stats.percentile(between, 95):.2f} ms; pooled p95 "
             f"{p95:.3f} ms lies at the "
             f"{100 * sum(g <= p95 for g in between) / len(between):.1f}th "
             f"percentile of the block gaps")


def run(ctx):
    mix = ctx.traffic
    model, engine = build(ctx)
    check_defaults(ctx, engine)
    failed_setup = 0
    try:
        warm(ctx, engine)
    except Exception as e:  # noqa: BLE001
        ctx.note(f"warm-up failed: {type(e).__name__}: {e}")
        failed_setup = 1
    vocab = ctx.cfg["vocab_size"]
    requests = traffic_gen.generate(mix, ctx.window_seconds, ctx.seed, vocab)
    ramp = traffic_gen.ramp(mix, ctx.seed, vocab)
    if len(requests) + len(ramp) > engine.config.finished_retention:
        raise ValueError("finished_retention is too small for a window")
    compiles_before = ctx.compiles.new_compiles
    seconds = ctx.window_seconds
    served = drive(ctx, engine, requests, seconds, ramp)
    requests = served["requests"]
    served["pass_records"] = pass_records(engine, requests)
    blocks = engine.metrics.snapshot().get("blocks", {})
    setup_s = ctx.setup_done - ctx.t_start
    new_compiles = ctx.compiles.new_compiles - compiles_before
    memory_peak = ctx.memory_peak()
    try:
        engine.shutdown()
    except Exception as e:  # noqa: BLE001
        ctx.note(f"engine.shutdown failed: {type(e).__name__}: {e}")
    del model, engine
    gc.collect()

    window_end = served["window_end"]
    in_window = sum(1 for times in served["token_times"]
                    for t in times if 0.0 <= t <= window_end)
    due_in_window = [k for k, r in enumerate(requests) if r.due_s >= 0.0]
    first = [served["token_times"][k][0] if served["token_times"][k] else None
             for k in due_in_window]
    cap_ms = 1e3 * (seconds + mix["drain_seconds"])   # never answered
    ttft = [min(t, cap_ms) for t in stats.ttfts_ms(
        [requests[k].due_s for k in due_in_window], first)]
    gaps = stats.gaps_ms([[t for t in times if t >= 0.0]
                          for times in served["token_times"]])
    late = served["late"]
    ctx.note(f"{len(ramp)} in the ramp, {len(due_in_window)} requests due, "
             f"{sum(served['finished'])} finished, {served['steps']} engine "
             f"steps; generator late by mean "
             f"{1e3 * np.mean(late) if late else 0:.2f} ms, max "
             f"{1e3 * max(late) if late else 0:.2f} ms; blocks {blocks}")
    serve.note_window(ctx, served, [])
    block_gap_modes(ctx, served, gaps)

    picks = checked_sample(requests, served, mix["checked_requests"],
                           ctx.seed)
    ctx.checked = (requests, served, picks)      # for tools and tests
    numbers = {}
    if picks:
        t0 = time.perf_counter()
        g = trajectory_gaps(ctx, requests, served, served["pass_records"],
                            picks)
        lg, og = g["logit"], g["order"]
        if lg.size:
            numbers["logit_gap_mean"] = (
                float(lg.mean()), f"{lg.size} tokens, max {lg.max():.3f}, "
                f"{100 * float((lg > 0.1).mean()):.2f}% over 0.1")
            numbers["order_gap_mean"] = (float(og.mean()),
                                         f"{og.size} passes")
        ctx.note(f"reference replayed {lg.size} greedy tokens of requests "
                 f"{picks} ({og.size} passes) in "
                 f"{time.perf_counter() - t0:.1f} s: logit_gap_mean "
                 f"{lg.mean() if lg.size else float('nan'):.5f} (max "
                 f"{lg.max() if lg.size else float('nan'):.3f}), "
                 f"order_gap_mean "
                 f"{og.mean() if og.size else float('nan'):.5f}")
    return {
        "attempted": len(due_in_window),
        "failed": served["failed"] + failed_setup,
        "setup_s": setup_s, "window_s": window_end,
        "new_compiles_in_window": new_compiles,
        "memory_peak_bytes": memory_peak,
        "end_to_end": {
            "serve_tokens_per_s": in_window / window_end,
            "ttft_p95_ms": stats.percentile(ttft, 95),
            "itl_p95_ms": stats.percentile(gaps, 95) if gaps else cap_ms,
        },
        "numbers": numbers,
        "record": {"steps": served["steps"], "tokens": in_window,
                   "flops_done": window_flops(
                       ctx.family.serve_flops, ctx.cfg, requests, served,
                       window_end),
                   "decode_only_steps_s": served["decode_only"],
                   "ttft_ms": ttft, "blocks": blocks},
    }
