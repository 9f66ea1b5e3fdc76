"""Runner ``serve_family_flops``: runner ``serve`` unchanged — the same
build, warm-up, open loop, window and ``logit_gap`` — with two additions
for a family GPT's arithmetic does not fit:

- the window's FLOPs are recounted by the FAMILY's own ``serve_flops(cfg,
  prompt_len, new_tokens)`` (``models/<family>.py``), so that
  ``serve_mfu_pct`` reads a count that fits the architecture:
  ``flops.serve_flops`` is GPT's formula (every layer a dense MLP of
  ``intermediate_size``, full-width heads);
- a second compared number, ``logit_gap_mean``: the MEAN over the checked
  greedy tokens of the gap that ``logit_gap`` takes the maximum of.  In a
  model with a top-k router a near-tie between the k-th and the next
  expert flips under any rounding of the hidden state, and a flipped
  expert moves that token's logits by a large step: the maximum over a few
  thousand tokens then reads the largest flip, which bfloat16 operands in
  the reference show as well (PERF.md §6, PR 30), while the mean reads how
  many tokens moved and by how much.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark.reference import common as refc
from benchmark.runners import serve
from benchmark.runners.serve import (build, checked_sample,  # noqa: F401
                                     drive, warm)


def window_flops(serve_flops, cfg, requests, served, window_end):
    """FLOPs of the tokens delivered inside the window: per request, its
    work up to the last token delivered by ``window_end`` less its work
    before the window opened (the ramp's prefill and first token)."""
    total = 0.0
    for r, times in zip(requests, served["token_times"]):
        done = sum(1 for t in times if t <= window_end)
        before = sum(1 for t in times if t < 0.0)
        if done > before:
            total += serve_flops(cfg, len(r.prompt), done) - (
                serve_flops(cfg, len(r.prompt), before) if before else 0.0)
    return total


def token_gaps(ctx, requests, served, picks, mode="f32"):
    """Per checked token, the gap of ``serve.reference_gaps`` (how far the
    served token's reference logit lies below the reference's best; with a
    ``mode`` other than f32 the 'served' token is the one that precision
    of the reference puts first), as one array over all of ``picks``."""
    import jax
    import jax.numpy as jnp
    ref, cfg = ctx.family.reference, ctx.cfg
    weights = refc.make_weights(ref.weight_spec(cfg), ctx.seed,
                                jnp.dtype(ctx.traffic["engine"]["dtype"]))
    pad = ctx.traffic["reference_pad_to"]

    @jax.jit
    def gaps_of(weights, ids, first, served_ids):
        full = ref.logits(cfg, weights, ids, "f32")[0]
        take = jnp.clip(first + jnp.arange(served_ids.shape[0]), 0,
                        ids.shape[1] - 1)
        chosen = served_ids if mode == "f32" else jnp.argmax(
            ref.logits(cfg, weights, ids, mode)[0][take], axis=-1)
        return jnp.max(full[take], axis=-1) - full[take, chosen]

    out = []
    for k in picks:
        tokens = served["tokens"][k]
        seq = requests[k].prompt + tokens
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(seq)] = seq
        mine = np.zeros((pad,), np.int32)
        mine[:len(tokens)] = tokens
        out.append(np.asarray(gaps_of(
            weights, ids, len(requests[k].prompt) - 1, mine))[:len(tokens)])
    return np.concatenate(out) if out else np.zeros((0,))


def reference_gaps(ctx, requests, served, picks, mode="f32"):
    """What ``tools/read_limits.py`` prints for a seed: the mean (this
    runner's second number), the maximum (``logit_gap``) and how the gaps
    are spread."""
    g = token_gaps(ctx, requests, served, picks, mode)
    if not g.size:
        return {}
    q50, q90, q99 = (float(x) for x in np.quantile(g, [0.5, 0.9, 0.99]))
    return {"mean": float(g.mean()), "max": float(g.max()), "q50": q50,
            "q90": q90, "q99": q99, "over_0.1": float((g > 0.1).mean()),
            "tokens": int(g.size)}


def run(ctx):
    out = serve.run(ctx)
    requests, served, picks = ctx.checked
    out["record"]["flops_done"] = window_flops(
        ctx.family.serve_flops, ctx.cfg, requests, served, out["window_s"])
    if picks:
        t0 = time.perf_counter()
        g = token_gaps(ctx, requests, served, picks)
        out["numbers"]["logit_gap_mean"] = (
            float(g.mean()), f"{g.size} tokens, "
            f"{100 * float((g > 0.1).mean()):.2f}% over 0.1")
        ctx.note(f"logit_gap_mean {g.mean():.5f} over {g.size} tokens "
                 f"({100 * float((g > 0.1).mean()):.2f}% over 0.1, max "
                 f"{g.max():.3f}) in {time.perf_counter() - t0:.1f} s")
    return out
