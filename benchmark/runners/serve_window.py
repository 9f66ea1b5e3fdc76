"""Runner ``serve_window``: runner ``serve_family_flops`` (``serve``'s open
loop, window and stamps by import; the family's own ``serve_flops``; the
compared numbers ``logit_gap`` and ``logit_gap_mean``) for a model served at
sequences of up to 16,384 positions, with the tail of ``serve.run`` as its
own:

- the engine's prefill buckets are the traffic file's
  ``engine.prefill_buckets`` where it gives them (the buckets the mix's
  lengths use: warm-up compiles no program the window never runs);
- the reference is asked for its logits at the SERVED positions alone
  (``reference.logits_at``): ``serve.reference_gaps`` takes them over the
  whole padded sequence, which at 16,384 x 151,936 is a 10 GB float32
  table;
- the checked requests are greedy requests with at least one token served,
  finished or not, the LONGEST sequence first (prompt and served tokens), so
  that a request past the model's window is checked whenever a greedy one
  was served: ``serve.checked_sample`` takes finished requests alone, which
  a 30-second window of 512-1,536-token answers leaves few of.  How far the
  longest checked sequence reaches is in the run's notes.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import stats, traffic as traffic_gen
from benchmark.reference import common as refc
from benchmark.runners import serve
from benchmark.runners.serve import drive, warm  # noqa: F401
from benchmark.runners.serve_family_flops import window_flops


def build(ctx):
    """``serve.build``'s engine, at the mix's own prefill buckets."""
    import jax.numpy as jnp

    from paddle_tpu import serving
    from paddle_tpu.serving.aot_cache import AOTProgramCache
    from paddle_tpu.utils.compile_cache import serving_aot_dir
    import paddle_tpu as P
    cfg, family, eng = ctx.cfg, ctx.family, ctx.traffic["engine"]
    P.seed(ctx.seed % (1 << 31))
    model = family.build(cfg, training=False)
    model.to(dtype=eng["dtype"])
    model.eval()
    dtype = jnp.dtype(eng["dtype"])
    weights = refc.make_weights(family.reference.weight_spec(cfg), ctx.seed,
                                dtype)
    params = dict(model.named_parameters())
    for mine, theirs in family.leaf_names(cfg).items():
        params[theirs]._set_value(weights[mine])
    del weights
    engine = serving.LLMEngine(
        model, serving.EngineConfig(
            max_num_seqs=eng["max_num_seqs"], page_size=eng["page_size"],
            max_model_len=eng["max_model_len"], dtype=dtype,
            prefill_buckets=eng.get("prefill_buckets")),
        program_cache=AOTProgramCache(serving_aot_dir()))
    return model, engine


def checked_sample(requests, served, how_many, seed):
    """Greedy requests with a token served, the longest sequence first,
    the rest drawn from the seed."""
    done = [k for k, r in enumerate(requests)
            if r.greedy and served["tokens"][k]]
    if not done:
        return []
    longest = max(done, key=lambda k: len(requests[k].prompt)
                  + len(served["tokens"][k]))
    rest = [k for k in done if k != longest]
    rng = np.random.default_rng((int(seed), 0xC0FFEE))
    picks = rng.permutation(len(rest))[:max(0, how_many - 1)]
    return [longest] + [rest[j] for j in picks]


def token_gaps(ctx, requests, served, picks, mode="f32"):
    """Per checked token, how far the served token's reference logit lies
    below the reference's best at its position (with a ``mode`` other than
    f32 the 'served' token is the one that precision of the reference puts
    first), as one array over all of ``picks`` — the reference's head run
    at the served positions alone."""
    import jax
    import jax.numpy as jnp
    ref, cfg, mix = ctx.family.reference, ctx.cfg, ctx.traffic
    weights = refc.make_weights(ref.weight_spec(cfg), ctx.seed,
                                jnp.dtype(mix["engine"]["dtype"]))
    pad, rows = mix["reference_pad_to"], mix["output_len"]["hi"]

    @jax.jit
    def gaps_of(weights, ids, at, served_ids):
        full = ref.logits_at(cfg, weights, ids, at[None], "f32")[0]
        chosen = served_ids if mode == "f32" else jnp.argmax(
            ref.logits_at(cfg, weights, ids, at[None], mode)[0], axis=-1)
        return jnp.max(full, axis=-1) - jnp.take_along_axis(
            full, chosen[:, None], -1)[:, 0]

    out = []
    for k in picks:
        tokens = served["tokens"][k]
        seq = requests[k].prompt + tokens
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(seq)] = seq
        n = len(tokens)
        at = np.clip(len(requests[k].prompt) - 1 + np.arange(rows), 0,
                     pad - 1).astype(np.int32)
        mine = np.zeros((rows,), np.int32)
        mine[:n] = tokens
        out.append(np.asarray(gaps_of(weights, ids, at, mine))[:n])
    return np.concatenate(out) if out else np.zeros((0,))


def reference_gaps(ctx, requests, served, picks, mode="f32"):
    """What ``tools/read_limits.py`` prints for a seed: the mean (the
    second compared number), the maximum (``logit_gap``), how the gaps are
    spread, and how long the longest checked sequence is."""
    g = token_gaps(ctx, requests, served, picks, mode)
    if not g.size:
        return {}
    q50, q90, q99 = (float(x) for x in np.quantile(g, [0.5, 0.9, 0.99]))
    return {"mean": float(g.mean()), "max": float(g.max()), "q50": q50,
            "q90": q90, "q99": q99, "over_0.1": float((g > 0.1).mean()),
            "tokens": int(g.size),
            "longest": max(len(requests[k].prompt) + len(served["tokens"][k])
                           for k in picks)}


class Stalls:
    """What can hold the open loop up for a whole second: jax's own events
    on the path to a program (tracing, lowering, a backend compile, a
    persistent-cache read: seen whether or not a persistent compile cache
    is on) and the garbage collector's collections of its oldest
    generation, each with its start on the host's clock, from the ramp's
    offer on; :meth:`note` keeps those inside the window."""

    PATHS = ("/jax/core/compile/", "/jax/compilation_cache/")

    def __init__(self):
        import jax
        self.on = True
        self.events, self.collections = [], []
        self._gc_start = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        gc.callbacks.append(self._on_gc)

    def close(self):
        self.on = False
        gc.callbacks.remove(self._on_gc)

    def _on_event(self, event, duration, **_):
        if self.on and event.startswith(self.PATHS):
            self.events.append((time.perf_counter() - duration, duration,
                                event.rsplit("/", 1)[-1]))

    def _on_gc(self, phase, info):
        if not self.on or info.get("generation") != 2:
            return
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.collections.append((self._gc_start,
                                     time.perf_counter() - self._gc_start,
                                     "gc2"))
            self._gc_start = None

    def note(self, ctx, served):
        """One line: the longest time between two steps' ends (idle time
        between them included), when it ended and how many prefills that
        step ran, beside what jax did on the way to a program and what the
        oldest generation's collections took inside the window (count and
        seconds by kind)."""
        t0, end = ctx.capture.t0, served["window_end"]
        kinds = {}
        for at, d, kind in self.events + self.collections:
            if 0.0 <= at - t0 <= end:
                n, total = kinds.get(kind, (0, 0.0))
                kinds[kind] = (n + 1, round(total + d, 3))
        ends = [t for t, _depth in served["queue"]]
        if len(ends) > 1:
            k = int(np.argmax(np.diff(ends))) + 1
            longest = (f"longest time between two steps' ends "
                       f"{1e3 * (ends[k] - ends[k - 1]):.1f} ms, ending at "
                       f"{ends[k]:.2f} s, {served['step_prefills'][k]} "
                       f"prefill(s) in that step")
        else:
            longest = "fewer than two steps"
        ctx.note(f"{longest}; in the window, program-path events and "
                 f"oldest-generation collections (count, s): {kinds}")


def run(ctx):
    mix = ctx.traffic
    model, engine = build(ctx)
    failed_setup = 0
    try:
        warm(ctx, engine)
    except Exception as e:  # noqa: BLE001
        ctx.note(f"warm-up failed: {type(e).__name__}: {e}")
        failed_setup = 1
    vocab = ctx.cfg["vocab_size"]
    requests = traffic_gen.generate(mix, ctx.window_seconds, ctx.seed, vocab)
    ramp = traffic_gen.ramp(mix, ctx.seed, vocab)
    compiles_before = ctx.compiles.new_compiles
    seconds = ctx.window_seconds
    stalls = Stalls()
    served = drive(ctx, engine, requests, seconds, ramp)
    stalls.close()
    requests = served["requests"]
    window = engine.metrics.snapshot().get("window", {})
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
             f"{1e3 * max(late) if late else 0:.2f} ms; window pool "
             f"{window}")
    serve.note_window(ctx, served, gaps)
    stalls.note(ctx, served)

    picks = checked_sample(requests, served, mix["checked_requests"],
                           ctx.seed)
    ctx.checked = (requests, served, picks)      # for tools and tests
    numbers = {}
    if picks:
        t0 = time.perf_counter()
        g = token_gaps(ctx, requests, served, picks)
        longest = max(len(requests[k].prompt) + len(served["tokens"][k])
                      for k in picks)
        worst = int(np.argmax(g))
        numbers["logit_gap"] = (float(g[worst]), f"token{worst}")
        numbers["logit_gap_mean"] = (
            float(g.mean()), f"{g.size} tokens, "
            f"{100 * float((g > 0.1).mean()):.2f}% over 0.1")
        ctx.note(f"reference checked {g.size} greedy tokens of requests "
                 f"{picks}, the longest sequence {longest} positions: "
                 f"logit_gap_mean {g.mean():.5f}, max {g.max():.3f}, in "
                 f"{time.perf_counter() - t0:.1f} s")
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
                   "ttft_ms": ttft, "window": window},
    }
