"""The serving engine's one seam for the KIND OF GENERATION.

How many positions a sequence feeds a pass, what a prefill stores and
whether it yields a token, what the programs compute round the model, what
the sampler's output means (tokens fixed, tokens delivered, whether the
length moves) is ONE decision, made here.  :func:`make_generation` picks
the kind once, from the model's ``generation_spec()`` (absent: next-token);
:class:`~paddle_tpu.serving.engine.LLMEngine` keeps the slots, the pages,
the scheduler, the compiled programs and the request lifecycle, and calls
the object where the kinds differ — it holds no branch on the kind.

- :class:`NextToken` — one token a sequence a step: a prefill samples the
  first token from the prompt's last logits, a decode step feeds ``[slots,
  1]`` ids and advances every length by one.  Its passes RUN AHEAD: a pass
  takes its rows' ids from the previous pass's sampler output on the device
  (an id the host leaves at ``-1``), so the engine launches pass n+1 before
  it fetches pass n's tokens.
- :class:`BlockDiffusion` — generation by diffusion over blocks of ``B``
  positions (``models/sdar_moe.py``; the family's
  ``block_diffusion_generate``).  A prefill stores the prompt's ``L // B``
  whole blocks under a block-causal mask and yields NO token.  Then block
  after block: the in-flight block's ids (the ``L mod B`` leftover prompt
  tokens in the first block, the mask token elsewhere) are run by ONE decode
  program at ``[slots, B]`` — every pass writes the block's K/V rows at
  ``len .. len + B - 1`` and attends over ``len + B`` positions; at every
  still-masked position a token is chosen with its confidence, and the
  request's rule fixes some of them (``low_confidence_static``: the ``n_t``
  of highest confidence, ``n_t = B / denoising_steps``, a remainder to the
  earliest passes; ``low_confidence_dynamic``: every one over the threshold
  if they are at least ``n_t``).  The pass that fixes a block's last
  position delivers its tokens together; ONE more pass over the final ids
  (the commit) stores the block's K/V, and only then the length moves, by
  ``B``.  Slots are in different phases of different blocks in one call.
  Which positions are masked is this object's own per-slot record, never a
  comparison with the mask token: a sampled or prompted id equal to it
  stays what it is.

docs/serving.md "Kinds of generation" has the lifecycle.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.observability import span
from paddle_tpu.serving.request import RequestState
from paddle_tpu.serving.sampler import sample_tokens

__all__ = ["BlockDiffusion", "NextToken", "Pass", "make_generation"]

# part of an engine's AOT fingerprint: bump with any change to what the
# kind's programs compute round the model
NEXT_TOKEN_REVISION = 1     # 1: the decode pass takes the previous sampler's
#                             output (run-ahead)
BLOCK_DIFFUSION_REVISION = 1
# the expert stats a program of a model with expert layers hands the
# sampler to carry (``PagedKVContext.expert_stats``)
EXPERT_STATS = 4

_BLOCK_KNOBS = ("denoising_steps", "remasking", "confidence_threshold")


class Pass:
    """A decode pass on the device, launched and not yet fetched.

    - `rows`: ``[(slot, request, its evictions at launch)]`` — a row is
      delivered only if the slot still serves that request, never evicted
      since (``LLMEngine._serving``);
    - `logits`, `stats`: what the decode program returned (its expert
      stats: ``()`` for a model without expert layers);
    - `tokens`: the sampler's unfetched output, for a kind that samples at
      launch (else None);
    - `pages`: the pages its attention reads (``serving.decode``'s
      ``pages_live``);
    - `window_rows`: the ring rows one window layer of its pool reads
      (``window_rows_live``; 0 for a pool without window layers)."""

    __slots__ = ("rows", "logits", "stats", "tokens", "pages",
                 "window_rows")

    def __init__(self, rows, logits, stats, tokens, pages):
        self.rows = rows
        self.logits = logits
        self.stats = stats
        self.tokens = tokens
        self.pages = pages
        self.window_rows = 0


class NextToken:
    """One token a sequence a step; a pass can run ahead of the host."""

    kind = "next_token"
    rows = 1            # positions a slot feeds a decode pass
    # what the kind adds to the AOT fingerprint
    path = f"+next_token/{NEXT_TOKEN_REVISION}"
    block_length = 0    # `EngineMetrics.block_length` (0: no blocks)
    prefill_heads = 1   # outputs a prefill program puts before the pools
    # a pass's ids can be the previous pass's sampler output on the device
    runs_ahead = True

    def __init__(self, cfg):
        self.slots = cfg.max_num_seqs
        self._no_prev = None    # the placed zeros of a pass with no prev

    # ------------------------------------------------------ requests
    def check_params(self, sp):
        for name in _BLOCK_KNOBS:
            if getattr(sp, name) is not None:
                raise ValueError(
                    f"{name}: this model generates one token a step; the "
                    f"knob is generation by diffusion over blocks' own")

    def positions_needed(self, prompt_len, max_new_tokens):
        """Cache positions a request can reach."""
        return prompt_len + max_new_tokens

    def prefill_len(self, tokens):
        """Of `tokens` replayed, the positions a prefill stores."""
        return tokens

    def check_handoff(self):
        """Whether a running request's pages can cross to another
        engine (the disaggregated hand-off)."""

    # ---------------------------------------------------------- spans
    def prefill_attrs(self, covered):
        return {}

    def decode_attrs(self, eng):
        return {}

    # ------------------------------------------------------- programs
    def sampler_widths(self):
        return (1, self.slots)

    def prefill_program(self, eng, bucket):
        """(fn, example_args, donate, out_shardings) for one prefill
        bucket — shared by the compile path and the shardlint self-audit
        (which traces the SAME program, never a lookalike)."""
        def prefill(params, k_pools, v_pools, row_table, ids, pos_ids,
                    length, *slot):
            ctx = eng._kv_context(k_pools, v_pools, row_table, length,
                                  "prefill", *slot)
            if eng._head_on_last:
                # the model's head runs on the last REAL token alone
                last = eng._run_model(
                    params, ids, pos_ids, ctx,
                    logits_positions=Tensor(length - 1))[:, 0]
                return (last.astype(jnp.float32), ctx.k_pools,
                        ctx.v_pools) + eng._expert_stats(ctx)
            logits = eng._run_model(params, ids, pos_ids, ctx)
            # logits [1, bucket, V] -> the last REAL token's row
            last = jnp.take_along_axis(
                logits, (length - 1)[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]
            return (last.astype(jnp.float32), ctx.k_pools, ctx.v_pools)

        return prefill, eng._prefill_example(bucket), (1, 2), \
            eng._step_out_shardings()

    def decode_program(self, eng):
        cfg = eng.config

        if cfg.guard:
            # sentinel-guarded decode: one extra [B, 1] poison operand
            # (all zeros in production — the fault-injection hook adds
            # NaN/inf to a victim row, so injection never changes the
            # compiled program) and one extra [B, 2] anomaly-flag
            # output.  Still ONE decode program for the engine's life.
            def decode(params, k_pools, v_pools, tables, lens, tokens,
                       poison):
                ctx = eng._kv_context(k_pools, v_pools, tables, lens,
                                      "decode")
                logits = eng._run_model(params, tokens, lens[:, None],
                                        ctx)
                logits = logits[:, 0].astype(jnp.float32) + poison
                flags = eng._guard_flags(logits, ctx.k_pools,
                                         ctx.v_pools, tables, lens)
                return (logits, ctx.k_pools, ctx.v_pools,
                        flags) + eng._expert_stats(ctx)

            return decode, (
                *eng._decode_example(self.rows),
                jnp.zeros((cfg.max_num_seqs, 1), jnp.float32)), (1, 2), \
                eng._guarded_out_shardings()

        def decode(params, k_pools, v_pools, tables, lens, tokens, prev):
            # a row the host left at -1 takes the token the previous
            # pass's sampler drew for its slot (`prev`: that sampler's
            # output, expert stats and all), on the device
            tokens = jnp.where(tokens < 0, prev[:tokens.shape[0], None],
                               tokens)
            ctx = eng._kv_context(k_pools, v_pools, tables, lens, "decode")
            logits = eng._run_model(params, tokens, lens[:, None], ctx)
            return (logits[:, 0].astype(jnp.float32),
                    ctx.k_pools, ctx.v_pools) + eng._expert_stats(ctx)

        return decode, (*eng._decode_example(self.rows),
                        jnp.zeros((self._sampled(eng),), jnp.int32)), \
            (1, 2), eng._step_out_shardings()

    def _sampled(self, eng):
        """Length of the decode sampler's output: a token a slot, then the
        carried expert stats."""
        return self.slots + (EXPERT_STATS if eng._moe_layers else 0)

    def sampler_program(self, eng, width):
        V = int(eng._model.config.vocab_size)
        fn, carry = sample_tokens, ()
        if eng._moe_layers:
            # the expert stats of the program that made the logits ride
            # the token fetch: one array comes back, not two
            def fn(logits, seeds, pos, temps, top_ks, top_ps, stats):
                return jnp.concatenate([sample_tokens(
                    logits, seeds, pos, temps, top_ks, top_ps), stats])
            carry = (jnp.zeros((EXPERT_STATS,), jnp.int32),)
        return fn, (
            jnp.zeros((width, V), jnp.float32),
            jnp.zeros((width,), jnp.int32),
            jnp.zeros((width,), jnp.int32),
            jnp.zeros((width,), jnp.float32),
            jnp.zeros((width,), jnp.int32),
            jnp.ones((width,), jnp.float32)) + carry, (), \
            (eng._repl_sharding if eng._mesh is not None else None)

    # ------------------------------------------------------- sampling
    def sample(self, eng, logits, reqs, width, carry=(), ahead=None,
               fetch=True):
        """reqs: per-row Request or None (padding rows).  Position is
        the ABSOLUTE index of the token being sampled = the row's cache
        length AFTER its input token was appended — which is exactly
        `total_len` host-side, plus `ahead[i]` (1: the row's input token is
        still on the device, in the previous pass's output).  `carry`: the
        expert stats of the program that made `logits` (a model with
        expert layers), which ride the blocking fetch into the engine's
        ``_moe_stats``.  Without `fetch`, the sampler's unfetched
        output."""
        seeds = np.zeros((width,), np.int32)
        pos = np.zeros((width,), np.int32)
        temps = np.zeros((width,), np.float32)
        top_ks = np.zeros((width,), np.int32)
        top_ps = np.ones((width,), np.float32)
        for i, r in enumerate(reqs):
            if r is None:
                continue
            sp = r.sampling_params
            seeds[i] = sp.seed
            pos[i] = r.total_len
            temps[i] = sp.temperature
            top_ks[i] = sp.top_k
            top_ps[i] = sp.top_p
        if ahead is not None:
            pos += ahead
        out = eng._run_sampler(width, logits, (seeds, pos), temps, top_ks,
                               top_ps, carry, fetch=fetch)
        return [int(t) for t in out[:width]] if fetch else out

    # ------------------------------------------------ a step's outcome
    def admitted(self, eng, req, slot, tokens, head, stats, span_, bucket,
                 t0, events):
        """After a prefill program ran for `req` at `slot`: the first
        token, sampled from the prompt's last logits."""
        tok = eng._sample(head[0], [req], width=1, carry=stats)[0]
        with span("serving.deliver", tokens=1):
            eng._note_experts(span_, bucket)
            now = eng.metrics.clock()
            eng._note_prefill(req, len(tokens), t0, now)
            eng._deliver(req, [tok], [None], now, events, gap=False)
            if not req.is_finished:
                req.transition(RequestState.DECODE)

    def decode_operands(self, eng, live, ahead, after):
        """The decode program's per-pass operands after the lengths: the
        ids ``[slots, rows]`` — ``-1`` where a row's last token is still
        on the device (``ahead``), in pass `after`'s sampler output — and
        that output (zeros when no pass is in flight); a guarded engine's
        program takes the ids alone."""
        tokens = np.zeros((self.slots, 1), np.int32)
        for s, r in live:
            tokens[s, 0] = -1 if ahead[s] else r.output_token_ids[-1]
        if eng.config.guard:
            return (eng._place(tokens),)
        if after is not None:
            return eng._place(tokens), after.tokens
        if self._no_prev is None:
            self._no_prev = eng._place(np.zeros((self._sampled(eng),),
                                                np.int32))
        return eng._place(tokens), self._no_prev

    def launched(self, eng, live, ahead, logits, stats, pages):
        """After the decode program was called over `live` ``[(slot,
        request)]``: the sampler is launched behind it, unfetched."""
        reqs = [None] * self.slots
        for s, r in live:
            reqs[s] = r
        out = eng._sample(logits, reqs, width=self.slots, carry=stats,
                          ahead=ahead, fetch=False)
        return Pass([(s, r, r.num_evictions) for s, r in live], None,
                    stats, out, pages)

    def decoded(self, eng, done, span_, t0, events):
        """Pass `done`'s tokens fetched: one token a sequence it still
        serves, every such length advanced by one."""
        toks = eng._fetch(done.tokens, done.stats)
        live = [(s, r) for s, r, ev in done.rows if eng._serving(s, r, ev)]
        with span("serving.deliver", tokens=len(live)):
            eng._note_experts(span_, self.slots)
            for s, r in live:
                eng._lens[s] += 1
            now = eng._note_decode(t0)
            for s, r in live:
                eng._deliver(r, [int(toks[s])], [None], now, events)


class BlockDiffusion(NextToken):
    """Generation by diffusion over blocks of ``block_length`` positions
    (module docstring).  Per-slot record of the in-flight block: its ids,
    which positions are still masked, the pass that fixed each, the passes
    done, and how many leading positions are the prompt's own.  Only a LIVE
    slot's record is read, and `open_block` writes all of it: an in-flight
    block goes with its slot (an evicted request replays prompt + delivered
    tokens and opens the block anew)."""

    kind = "block_diffusion"
    prefill_heads = 0
    # the next pass's ids come from the host's unmasking rule on the
    # fetched confidences: a pass cannot be launched before the last one's
    # fetch
    runs_ahead = False

    def __init__(self, cfg, spec):
        super().__init__(cfg)
        B = int(spec["block_length"])
        if B < 1 or B > cfg.page_size:
            raise ValueError(
                f"block_length {B} must be in 1..page_size "
                f"{cfg.page_size}: a pass writes its block into pages the "
                f"slot owns plus the one reserve page")
        if cfg.growth_reserve_pages < 1:
            raise ValueError("growth_reserve_pages must be >= 1: an "
                             "admitted request's first pass writes a block "
                             "past its prefill")
        if cfg.guard:
            raise NotImplementedError(
                "guard: the sentinel-guarded decode is not built for "
                "generation by diffusion over blocks")
        self.rows = self.block_length = B
        self.mask_id = int(spec["mask_token_id"])
        self.path = f"+block_diffusion/{B}/{BLOCK_DIFFUSION_REVISION}"
        N = cfg.max_num_seqs
        self.ids = np.full((N, B), self.mask_id, np.int32)
        self.masked = np.zeros((N, B), np.bool_)
        self.fixed_at = np.full((N, B), -1, np.int32)
        self.passes = np.zeros((N,), np.int32)
        self.given = np.zeros((N,), np.int32)

    # ------------------------------------------------------ requests
    def check_params(self, sp):
        if sp.denoising_steps is not None and sp.denoising_steps > self.rows:
            raise ValueError(
                f"denoising_steps {sp.denoising_steps} exceeds the block "
                f"length {self.rows}: a pass fixes at least one position")

    def positions_needed(self, prompt_len, max_new_tokens):
        B = self.rows
        stored = prompt_len // B * B
        return stored + -(-(prompt_len - stored + max_new_tokens) // B) * B

    def prefill_len(self, tokens):
        return tokens // self.rows * self.rows

    def check_handoff(self):
        raise NotImplementedError(
            "page hand-off: a request's in-flight block is not shipped; "
            "generation by diffusion over blocks migrates by replay")

    # ---------------------------------------------------------- spans
    def prefill_attrs(self, covered):
        return {"block_tokens": covered}

    def decode_attrs(self, eng):
        live = [s for s, r in enumerate(eng._slots) if r is not None]
        left = self.masked[live]
        return {"block_rows": len(live) * self.rows,
                "commits": int((~left.any(axis=1)).sum()),
                "masked": int(left.sum())}

    # ------------------------------------------------------- programs
    def sampler_widths(self):
        return (self.slots * self.rows,)

    def prefill_program(self, eng, bucket):
        def prefill(params, k_pools, v_pools, row_table, ids, pos_ids,
                    length, *slot):
            ctx = eng._kv_context(k_pools, v_pools, row_table, length,
                                  "prefill", *slot)
            # a prefill yields no token: the logits are not an output and
            # XLA drops the head
            head = ({"logits_positions": Tensor(jnp.zeros_like(length))}
                    if eng._head_on_last else {})
            eng._run_model(params, ids, pos_ids, ctx, **head)
            return (ctx.k_pools, ctx.v_pools) + eng._expert_stats(ctx)

        return prefill, eng._prefill_example(bucket), (1, 2), None

    def decode_program(self, eng):
        B = self.rows

        def decode(params, k_pools, v_pools, tables, lens, tokens):
            ctx = eng._kv_context(k_pools, v_pools, tables, lens, "decode")
            at = lens[:, None] + jnp.arange(B, dtype=lens.dtype)
            logits = eng._run_model(params, tokens, at, ctx)   # [N, B, V]
            return (logits.reshape(-1, logits.shape[-1]).astype(
                jnp.float32), ctx.k_pools, ctx.v_pools) + eng._expert_stats(
                    ctx)

        return decode, eng._decode_example(B), (1, 2), None

    def sampler_program(self, eng, width):
        """``[width]`` tokens, their confidences (bit for bit, as int32)
        and the carried expert stats: one array, one fetch."""
        V = int(eng._model.config.vocab_size)

        def fn(logits, seeds, pos, passes, temps, top_ks, top_ps, *stats):
            toks, conf = sample_tokens(logits, seeds, pos, temps, top_ks,
                                       top_ps, passes=passes)
            return jnp.concatenate([toks, jax.lax.bitcast_convert_type(
                conf, jnp.int32), *stats])

        carry = ((jnp.zeros((EXPERT_STATS,), jnp.int32),)
                 if eng._moe_layers else ())
        return fn, (
            jnp.zeros((width, V), jnp.float32),
            jnp.zeros((width,), jnp.int32),
            jnp.zeros((width,), jnp.int32),
            jnp.zeros((width,), jnp.int32),
            jnp.zeros((width,), jnp.float32),
            jnp.zeros((width,), jnp.int32),
            jnp.ones((width,), jnp.float32)) + carry, (), None

    # ------------------------------------------------------- sampling
    def sample(self, eng, logits, reqs, width, carry=()):
        """reqs: per-slot Request or None.  (tokens, confidences), each
        ``[slots, B]``: a draw at every still-masked position of the live
        slots' blocks, keyed by (seed, absolute position, pass within the
        block); every other row is greedy and unread."""
        N, B = self.slots, self.rows
        seeds = np.zeros((N, B), np.int32)
        pos = np.zeros((N, B), np.int32)
        passes = np.zeros((N, B), np.int32)
        temps = np.zeros((N, B), np.float32)
        top_ks = np.zeros((N, B), np.int32)
        top_ps = np.ones((N, B), np.float32)
        for s, r in enumerate(reqs):
            if r is None:
                continue
            sp, m = r.sampling_params, self.masked[s]
            seeds[s] = sp.seed
            pos[s] = int(eng._lens[s]) + np.arange(B)
            passes[s] = self.passes[s]
            temps[s, m] = sp.temperature
            top_ks[s, m] = sp.top_k
            top_ps[s, m] = sp.top_p
        flat = [a.reshape(-1) for a in (seeds, pos, passes, temps, top_ks,
                                        top_ps)]
        out = eng._run_sampler(width, logits, tuple(flat[:3]), *flat[3:],
                               carry)
        return (out[:N * B].reshape(N, B),
                out[N * B:2 * N * B].view(np.float32).reshape(N, B))

    def choose(self, sp, masked, conf, t):
        """The positions pass `t` of a block fixes, of those `masked`, by
        the request's rule from their confidences `conf` ``[B]``."""
        B = self.rows
        steps = sp.denoising_steps or B
        n_t = B // steps + (t < B % steps)
        at = np.flatnonzero(masked)
        if sp.remasking == "low_confidence_dynamic":
            tau = (0.9 if sp.confidence_threshold is None
                   else sp.confidence_threshold)
            over = at[conf[at] > tau]
            if len(over) >= n_t:
                return over
        # the n_t of highest confidence, the earliest first among equals
        return at[np.argsort(-conf[at], kind="stable")[:n_t]]

    # ------------------------------------------------ a step's outcome
    def open_block(self, slot, leftover=()):
        """A fresh in-flight block at `slot`: `leftover` prompt tokens,
        then masks."""
        r = len(leftover)
        self.ids[slot] = self.mask_id
        self.ids[slot, :r] = leftover
        self.masked[slot] = True
        self.masked[slot, :r] = False
        self.fixed_at[slot] = -1
        self.passes[slot] = 0
        self.given[slot] = r

    def admitted(self, eng, req, slot, tokens, head, stats, span_, bucket,
                 t0, events):
        """After `req`'s whole blocks were stored (no program ran for a
        prompt shorter than a block): no token; the first block opens on
        the prompt's leftover."""
        if stats:
            # the prefill's one blocking fetch: it samples nothing
            with span("serving.fetch"):
                eng._moe_stats = np.asarray(stats[0])
        with span("serving.deliver", tokens=0):
            if stats:
                eng._note_experts(span_, bucket)
            stored = self.prefill_len(len(tokens))
            eng._note_prefill(req, len(tokens), t0, eng.metrics.clock(),
                              ran=stored > 0)
            self.open_block(slot, tokens[stored:])
            req.transition(RequestState.DECODE)

    def decode_operands(self, eng, live, ahead, after):
        return (eng._place(self.ids.copy()),)

    def launched(self, eng, live, ahead, logits, stats, pages):
        return Pass([(s, r, r.num_evictions) for s, r in live], logits,
                    stats, None, pages)

    def decoded(self, eng, done, span_, t0, events):
        """After a pass over its live slots: a slot whose block held no
        mask has committed it — its length moves by ``B`` and the next
        block opens; any other slot fixes what its rule says, and delivers
        the block when no mask is left."""
        B, m = self.rows, eng.metrics
        live = [(s, r) for s, r, _ev in done.rows]
        toks, conf = eng._sample(done.logits, list(eng._slots),
                                 width=self.slots * B, carry=done.stats)
        with span("serving.deliver") as deliver:
            before = m.generated_tokens
            eng._note_experts(span_, self.slots * B)
            now = eng._note_decode(t0)
            m.decode_forwards_total += len(live)
            for s, r in live:
                if not self.masked[s].any():
                    eng._lens[s] += B
                    m.commit_passes_total += 1
                    self.open_block(s)
                    continue
                fix = self.choose(r.sampling_params, self.masked[s],
                                  conf[s], int(self.passes[s]))
                self.ids[s, fix] = toks[s, fix]
                self.masked[s, fix] = False
                self.fixed_at[s, fix] = self.passes[s]
                self.passes[s] += 1
                m.tokens_fixed_total += len(fix)
                if not self.masked[s].any():
                    g = int(self.given[s])
                    eng._deliver(r, self.ids[s, g:].tolist(),
                                 self.fixed_at[s, g:].tolist(), now, events)
            deliver.set(tokens=m.generated_tokens - before)


def make_generation(model, cfg):
    """The generation kind `model` declares (``generation_spec()``; none
    is next-token) for an engine of `cfg`."""
    spec = (model.generation_spec() if hasattr(model, "generation_spec")
            else {"kind": "next_token"})
    if spec["kind"] == "next_token":
        return NextToken(cfg)
    if spec["kind"] == "block_diffusion":
        return BlockDiffusion(cfg, spec)
    raise ValueError(f"unknown generation kind {spec['kind']!r}")
