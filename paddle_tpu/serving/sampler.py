"""Traced token sampling: greedy / temperature / top-k / top-p.

One pure function over `[B, V]` jnp arrays, jitted by the engine at
exactly two shapes (prefill width 1, decode width B) — it never
recompiles per request because every knob (temperature, top_k, top_p,
seed) is a TRACED operand, not a static argument.

Determinism contract: the key for a draw is
``fold_in(fold_in(PRNGKey(seed), position))`` where `position` is the
ABSOLUTE index of the token being sampled.  Batch composition, slot
assignment, and eviction/replay history cannot change a request's
tokens.

No sort.  Both cut-offs are order statistics, found by a search over
the order-preserving uint32 image of f32 (one fused compare + reduce
over `[B, V]` a pass), and only for a batch that asks: `lax.cond` at
BATCH level skips the draw when every row is greedy, the k search when
no sampled row has `top_k > 0`, the p search when none has `top_p < 1`.
The top-k cut-off is the k-th largest scaled logit, bit for bit, ties
kept.  The top-p cut-off keeps the tokens whose mass strictly above is
under `top_p`, like the sorted cumulative sum it replaces, with two
differences: the mass is summed in vocabulary order, not sorted order,
so a token whose inclusion hangs on the last ulp of the running mass
may flip; and `top_p >= 1` keeps every token (a sorted cumulative sum
can round to 1.0 before the tail and drop ~1e-7 of mass).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["sample_tokens", "sampler_path", "SAMPLER_REVISION"]

# part of `engine_fingerprint`: bump with any change to what the
# `sample/<width>` programs compute, so no stored executable of another
# revision is ever loaded
SAMPLER_REVISION = 2

_NEG_INF = jnp.finfo(jnp.float32).min


def _key(x):
    """f32 -> uint32, order-preserving: negatives flip every bit, the
    rest set the sign bit."""
    b = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def _unkey(u):
    return lax.bitcast_convert_type(
        jnp.where(u >> 31 == 1, u ^ jnp.uint32(1 << 31), ~u), jnp.float32)


def _cutoff(above, target, lo, hi):
    """Per row the smallest f32 `t` in [lo, hi] with ``above(t) <
    target``, `hi` where none has.  `above` maps thresholds [B, 3] to
    what lies strictly above each and must not grow with `t`.  A pass
    reads `[B, V]` once for three thresholds, the last keys of the
    interval's first three quarters, and keeps one quarter: 16 passes
    bring 2**32 keys down to one (measured on a v5e: bandwidth-bound,
    a third faster than 32 passes of one threshold)."""
    def quarter(_, c):
        lo, hi = c                                          # [B, 1]
        step = (hi - lo) // 4 + 1
        mids = jnp.minimum(
            lo + step * jnp.arange(1, 4, dtype=jnp.uint32) - 1, hi)
        ok = (above(_unkey(mids)) < target[:, None]) | (mids == hi)
        first = jnp.sum(~ok, -1, keepdims=True)     # not ok, then ok
        edges = jnp.concatenate([lo - 1, mids, hi], -1)
        return (jnp.take_along_axis(edges, first, -1) + 1,
                jnp.take_along_axis(edges, first + 1, -1))
    ends = _key(lo)[:, None], _key(hi)[:, None]
    return _unkey(lax.fori_loop(0, 16, quarter, ends)[1][:, 0])


def sampler_path(temperatures, top_ks, top_ps):
    """Which work `sample_tokens` does for these rows (numpy or jnp):
    (any row draws, any draws with top-k, any draws with top-p)."""
    draws = temperatures > 0.0
    return (draws.any(), (draws & (top_ks > 0)).any(),
            (draws & (top_ps < 1.0)).any())


def sample_tokens(logits, seeds, positions, temperatures, top_ks, top_ps,
                  passes=None):
    """Batched sampling (pure, trace-safe).

    logits [B, V] f32; seeds/positions/top_ks [B] int32;
    temperatures/top_ps [B] f32 -> token ids [B] int32.

    With ``passes [B]`` int32 (generation by diffusion over blocks: a
    position is sampled once a denoising pass) the key of a draw also
    folds in the row's pass, and the result is ``(token ids, confidence)``:
    ``confidence [B]`` f32 is the probability of the chosen token under the
    distribution it was chosen from — the softmax of the logits for a
    greedy row, the filtered and renormalised one for a drawn row.
    """
    B, V = logits.shape
    logits = logits.astype(jnp.float32)
    temperatures = temperatures.astype(jnp.float32)
    top_ks, top_ps = top_ks.astype(jnp.int32), top_ps.astype(jnp.float32)
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    any_draw, any_k, any_p = sampler_path(temperatures, top_ks, top_ps)
    if passes is not None:
        greedy = (greedy, jnp.exp(
            jnp.max(logits, -1) - jax.nn.logsumexp(logits, -1)))

    def draw():
        # temperature; <=0 means greedy (selected at the end)
        scaled = logits / jnp.maximum(temperatures, 1e-6)[:, None]

        # top-k: mask everything below the k-th largest logit (k<=0: off)
        def mask_k(x):
            k = jnp.where(top_ks <= 0, V, jnp.clip(top_ks, 1, V))
            inf = jnp.full((B,), jnp.inf, jnp.float32)
            kth = _cutoff(
                lambda t: jnp.sum(x[:, None] > t[..., None], -1), k, -inf, inf)
            return jnp.where(x < kth[:, None], _NEG_INF, x)
        scaled = lax.cond(any_k, mask_k, lambda x: x, scaled)

        # top-p (nucleus) over the top-k-filtered distribution: keep the
        # tokens with less than p of the mass strictly above them, the
        # argmax always
        probs = jax.nn.softmax(scaled, -1)

        def nucleus(p):
            pmin = _cutoff(
                lambda t: jnp.sum(
                    jnp.where(p[:, None] > t[..., None], p[:, None], 0.0), -1),
                top_ps, jnp.zeros((B,), jnp.float32), jnp.max(p, -1))
            return jnp.where(top_ps < 1.0, pmin, 0.0)
        pmin = lax.cond(any_p, nucleus,
                        lambda p: jnp.zeros((B,), jnp.float32), probs)
        log_probs = jnp.where(probs >= pmin[:, None], jnp.log(probs),
                              _NEG_INF)

        # Gumbel-max draw from the filtered distribution
        if passes is not None:
            gumbel = jax.vmap(lambda seed, position, pass_: jax.random.gumbel(
                jax.random.fold_in(jax.random.fold_in(
                    jax.random.PRNGKey(seed), position), pass_),
                (V,), jnp.float32))(seeds.astype(jnp.int32),
                                    positions.astype(jnp.int32),
                                    passes.astype(jnp.int32))
            sampled = jnp.argmax(log_probs + gumbel, -1).astype(jnp.int32)
            kept = jnp.sum(jnp.where(probs >= pmin[:, None], probs, 0.0), -1)
            conf = jnp.take_along_axis(probs, sampled[:, None], -1)[:, 0] / kept
            return (jnp.where(temperatures <= 0.0, greedy[0], sampled),
                    jnp.where(temperatures <= 0.0, greedy[1], conf))
        gumbel = jax.vmap(lambda seed, position: jax.random.gumbel(
            jax.random.fold_in(jax.random.PRNGKey(seed), position),
            (V,), jnp.float32))(seeds.astype(jnp.int32),
                                positions.astype(jnp.int32))
        sampled = jnp.argmax(log_probs + gumbel, -1).astype(jnp.int32)
        return jnp.where(temperatures <= 0.0, greedy, sampled)

    return lax.cond(any_draw, draw, lambda: greedy)
