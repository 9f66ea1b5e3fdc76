"""Runner ``serve``: ``LLMEngine.add_request`` / ``LLMEngine.step`` driven
from the benchmark's own wall-clock loop, open loop, arrivals at the rate
fixed in the mix's file.  The loop stamps every token itself when the step
that produced it returns; time to first token runs from when a request was
DUE.  Arrivals are offered for the whole window, then admitted requests
finish (at most ``drain_seconds`` more) — a late answer is late, not wrong.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import stats, traffic as traffic_gen
from benchmark.reference import common as refc


def build(ctx):
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
            max_model_len=eng["max_model_len"], dtype=dtype),
        program_cache=AOTProgramCache(serving_aot_dir()))
    return model, engine


def _sampling(req):
    from paddle_tpu import serving
    return serving.SamplingParams(
        max_new_tokens=req.max_new_tokens, temperature=req.temperature,
        top_p=req.top_p, seed=req.seed)


def warm(ctx, engine):
    """Boot every program, then run each shape this mix uses once: one
    prompt per prefill bucket in use, decoded together (full-width and
    single-row sampler, decode step)."""
    boot = engine.warmup()
    ctx.note(f"engine boot: {boot}")
    rng = np.random.default_rng(0)
    mix = ctx.traffic
    prompts = [rng.integers(1, ctx.cfg["vocab_size"], n).tolist()
               for n in mix["warm_prompts"]]
    reqs = [traffic_gen.Request(0.0, p, 4, i % 2 == 0,
                                0.0 if i % 2 == 0 else 0.8,
                                1.0 if i % 2 == 0 else 0.95, i)
            for i, p in enumerate(prompts)]
    engine.generate(prompts, [_sampling(r) for r in reqs])


def offer_ramp(ctx, engine, ramp):
    """Set-up's last act: the mix's burst is offered at once and the engine
    stepped until every one of them holds a slot; they decode on into the
    window.  Returns {request id: index} of those admitted."""
    index_of = {}
    for k, req in enumerate(ramp):
        index_of[engine.add_request(req.prompt, _sampling(req))] = k
    events = []
    while engine.queue_depth:
        events.extend(engine.step())
    return index_of, events


def drive(ctx, engine, requests, seconds, ramp=(), clock=time.perf_counter,
          sleep=time.sleep):
    """The open loop.  ``requests`` = the ramp's (offered before the window
    opens) followed by those due in the window.  Returns per-request stamps
    (seconds from the window's start) and the served tokens."""
    requests = list(ramp) + list(requests)
    n = len(requests)
    sent = [None] * n
    token_times = [[] for _ in range(n)]
    tokens = [[] for _ in range(n)]
    finished = [False] * n
    failed, late = 0, []
    decode_only, steps, queue, step_prefills = [], 0, [], []
    drain = ctx.traffic["drain_seconds"]
    hard_stop = seconds + drain
    index_of, events = offer_ramp(ctx, engine, ramp)
    for rid, tok, fin in events:          # first tokens, before the window
        token_times[index_of[rid]].append(-1.0)
        tokens[index_of[rid]].append(int(tok))
    ctx.setup_done = time.perf_counter()
    ctx.capture.start()
    t0 = ctx.capture.t0
    i = len(ramp)
    for k in range(i):
        sent[k] = -1.0
    closed = False
    window_end = seconds
    while True:
        now = clock() - t0
        if not closed and now >= seconds:
            # the window lasts ``seconds`` and closes when the step then in
            # flight has delivered: a rate over [0, window_end] does not
            # jump by a whole step's tokens with the last step's timing
            closed = True
            window_end = max([seconds] + [t for t, _ in queue[-1:]])
            ctx.capture.stop()             # the traced window is the window
        while i < n and requests[i].due_s <= now:
            req = requests[i]
            try:
                with ctx.spans.span("serve.add_request"):
                    index_of[engine.add_request(req.prompt,
                                                _sampling(req))] = i
                sent[i] = now
                late.append(now - req.due_s)
            except Exception as e:  # noqa: BLE001 — a refusal is a failure
                ctx.note(f"request {i} refused: {type(e).__name__}: {e}")
                failed += 1
            i += 1
        if closed and not drain:
            break                 # a saturated mix waits for no one
        if engine.has_unfinished():
            prefills = engine.metrics.prefill_steps
            t_s = clock()
            try:
                with ctx.spans.span("serve.step"):
                    events = engine.step()
            except Exception as e:  # noqa: BLE001 — the engine's fault
                ctx.note(f"engine.step failed: {type(e).__name__}: {e}")
                failed += sum(1 for k in index_of.values()
                              if not finished[k])
                break
            t_e = clock()
            steps += 1
            queue.append((t_e - t0, engine.queue_depth))
            step_prefills.append(engine.metrics.prefill_steps - prefills)
            if not step_prefills[-1] and not closed:
                decode_only.append(t_e - t_s)
            for rid, tok, fin in events:
                k = index_of.get(rid)
                if k is None or tok is None:
                    continue
                token_times[k].append(t_e - t0)
                tokens[k].append(int(tok))
                finished[k] = finished[k] or bool(fin)
        elif i >= n:
            break
        else:
            sleep(max(0.0, min(requests[i].due_s - (clock() - t0), 0.002)))
        if drain and clock() - t0 > hard_stop:
            ctx.note(f"stopped {drain} s past the "
                     f"window's close with requests unfinished")
            break
    if not closed:
        ctx.capture.stop()
    never = sum(1 for k in range(n)
                if drain and sent[k] is not None and not finished[k])
    return {"requests": requests, "sent": sent, "token_times": token_times,
            "tokens": tokens, "finished": finished,
            "failed": failed + never, "late": late,
            "window_end": window_end, "decode_only": decode_only,
            "steps": steps, "queue": queue, "step_prefills": step_prefills}


def gap_modes(served):
    """Where the tail of the token gaps sits: every gap of the window with
    the number of prefills in the engine steps it spans, as {0 | 1 | 2:
    [gaps in ms]} (2 = two or more).  A running stream gets one token a
    step, so a gap is one step: a plain decode step, or one that one or
    more admitted prompts' prefills stalled."""
    step_of = {t: i for i, (t, _depth) in enumerate(served["queue"])}
    before = np.concatenate([[0], np.cumsum(served["step_prefills"])])
    modes = {0: [], 1: [], 2: []}
    for times in served["token_times"]:
        times = [t for t in times if t >= 0.0]
        for a, b in zip(times, times[1:]):
            n = int(before[step_of[b] + 1] - before[step_of[a] + 1])
            modes[min(n, 2)].append(1e3 * (b - a))
    return modes


def note_window(ctx, served, gaps):
    """What kind of window it was, for whoever reads the run: how the
    waiting queue moved and which steps the gaps' 95th percentile lies in."""
    third = served["window_end"] / 3
    depth = [[q for t, q in served["queue"] if lo <= t < lo + third]
             for lo in (0.0, third, 2 * third)]
    ctx.note("waiting queue, mean by thirds of the window: " + " -> ".join(
        f"{np.mean(d):.1f}" if d else "-" for d in depth))
    if not gaps:
        return
    modes, p95 = gap_modes(served), stats.percentile(gaps, 95)
    parts = []
    for n, label in ((0, "0"), (1, "1"), (2, "2+")):
        v = modes[n]
        if v:
            parts.append(f"{label}: {100 * len(v) / len(gaps):.2f}% "
                         f"(median {stats.median(v):.2f} ms, "
                         f"{100 * sum(g <= p95 for g in v) / len(v):.1f}% "
                         f"of them <= p95)")
    ctx.note(f"token gaps by prefills in their step: {'; '.join(parts)}; "
             f"p95 {p95:.3f} ms of {len(gaps)} gaps")


def checked_sample(requests, served, how_many, seed):
    """Greedy requests that finished, the longest first, the rest drawn
    from the seed."""
    done = [k for k, r in enumerate(requests)
            if r.greedy and served["finished"][k]
            and len(served["tokens"][k]) == r.max_new_tokens]
    if not done:
        return []
    longest = max(done, key=lambda k: len(requests[k].prompt)
                  + requests[k].max_new_tokens)
    rest = [k for k in done if k != longest]
    rng = np.random.default_rng((int(seed), 0xC0FFEE))
    picks = rng.permutation(len(rest))[:max(0, how_many - 1)]
    return [longest] + [rest[j] for j in picks]


def reference_gaps(ctx, requests, served, picks, mode="f32"):
    """For each sampled request: the reference's logits over prompt +
    served tokens, once; at each served position the gap by which the
    served token's logit lies below the reference's best.  With ``mode``
    other than f32 (the control) the 'served' token at each position is
    the one the lower precision puts first."""
    import jax
    import jax.numpy as jnp
    ref, cfg = ctx.family.reference, ctx.cfg
    eng = ctx.traffic["engine"]
    weights = refc.make_weights(ref.weight_spec(cfg), ctx.seed,
                                jnp.dtype(eng["dtype"]))
    pad = ctx.traffic["reference_pad_to"]

    @jax.jit
    def gaps_of(weights, ids, first, count, served_ids):
        full = ref.logits(cfg, weights, ids, "f32")[0]
        rows = jnp.arange(served_ids.shape[0])
        take = jnp.clip(first + rows, 0, ids.shape[1] - 1)
        best = jnp.max(full[take], axis=-1)
        if mode == "f32":
            chosen = served_ids
        else:
            low = ref.logits(cfg, weights, ids, mode)[0]
            chosen = jnp.argmax(low[take], axis=-1)
        got = full[take, chosen]
        return jnp.where(rows < count, best - got, 0.0)

    worst, where = 0.0, None
    for k in picks:
        seq = requests[k].prompt + served["tokens"][k]
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(seq)] = seq
        out = np.zeros((pad,), np.int32)
        n_out = len(served["tokens"][k])
        out[:n_out] = served["tokens"][k]
        g = np.asarray(gaps_of(weights, ids, len(requests[k].prompt) - 1,
                               n_out, out))
        j = int(np.argmax(g))
        if not float(g[j]) <= worst:
            worst, where = float(g[j]), f"request{k}.token{j}"
    return worst, where


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
    served = drive(ctx, engine, requests, seconds, ramp)
    requests = served["requests"]
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
    # gaps between tokens delivered from the window's start on (the ramp's
    # first tokens, stamped -1, open no gap)
    gaps = stats.gaps_ms([[t for t in times if t >= 0.0]
                          for times in served["token_times"]])
    late = served["late"]
    ctx.note(f"{len(ramp)} in the ramp, {len(due_in_window)} requests due, "
             f"{sum(served['finished'])} "
             f"finished, {served['steps']} engine steps; generator late by "
             f"mean {1e3 * np.mean(late) if late else 0:.2f} ms, max "
             f"{1e3 * max(late) if late else 0:.2f} ms")

    note_window(ctx, served, gaps)
    picks = checked_sample(requests, served, mix["checked_requests"], ctx.seed)
    ctx.checked = (requests, served, picks)      # for tools and tests
    numbers = {}
    if picks:
        t0 = time.perf_counter()
        numbers["logit_gap"] = reference_gaps(ctx, requests, served, picks)
        n_tok = sum(len(served["tokens"][k]) for k in picks)
        ctx.note(f"reference checked {n_tok} greedy tokens of requests "
                 f"{picks} in {time.perf_counter() - t0:.1f} s")
    flops_done = 0.0
    from benchmark import flops
    for k, r in enumerate(requests):
        times = served["token_times"][k]
        done = sum(1 for t in times if t <= window_end)
        before = sum(1 for t in times if t < 0.0)
        if done > before:       # the work of the window, not of the ramp
            flops_done += flops.serve_flops(ctx.cfg, len(r.prompt), done) - (
                flops.serve_flops(ctx.cfg, len(r.prompt), before)
                if before else 0.0)
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
                   "flops_done": flops_done,
                   "decode_only_steps_s": served["decode_only"],
                   "ttft_ms": ttft},      # every request due in the window
    }
