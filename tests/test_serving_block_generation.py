"""serving/generation.py: generation by diffusion over blocks through
``LLMEngine`` (models/sdar_moe.py, tiny, float32, seeded) against the plain
reference's trajectory, and the next-token kind's refusals."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from benchmark.reference import sdar_moe as ref
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.serving import generation
from paddle_tpu.serving.sampler import sample_tokens
from tests.test_sdar_moe_model import M, TINY, build, tiny_weights

B = TINY["block_length"]
STATIC, DYNAMIC = serving.SamplingParams.REMASKING


@pytest.fixture(scope="module")
def weights():
    return tiny_weights()


@pytest.fixture(scope="module")
def model(weights):
    return build(weights)


def _engine(model, **kw):
    cfg = dict(max_num_seqs=3, page_size=8, max_model_len=64,
               dtype=jnp.float32)
    cfg.update(kw)
    return serving.LLMEngine(model, serving.EngineConfig(**cfg))


def _greedy(n, **kw):
    return serving.SamplingParams(max_new_tokens=n, temperature=0.0, **kw)


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, M, n).tolist() for n in lengths]


class _PassTap:
    """Keeps what every pass's sampler call saw, a live slot at a time:
    (request, length, pass within the block) -> (the block's ids as fed,
    which were masked, the logits of its rows)."""

    def __init__(self, engine):
        self.passes = {}
        inner, gen = engine._sample, engine._gen

        def tapped(logits, reqs, width, carry=()):
            arr = np.asarray(logits).reshape(len(reqs), B, -1)
            for s, r in enumerate(reqs):
                if r is not None:
                    key = (r.request_id, int(engine._lens[s]),
                           int(gen.passes[s]), bool(gen.masked[s].any()))
                    self.passes[key] = (gen.ids[s].copy(),
                                        gen.masked[s].copy(), arr[s])
            return inner(logits, reqs, width, carry)

        engine._sample = tapped


_ref_logits = jax.jit(lambda weights, ids: ref.logits(TINY, weights, ids))


def _reference_rows(weights, prefix, block_ids):
    """The naive pass: one forward of the stored prefix and the block
    (padded to one jitted shape: later blocks are not seen)."""
    ids = np.zeros((1, 48), np.int32)
    n = len(prefix)
    ids[0, :n + B] = list(prefix) + list(block_ids)
    return np.asarray(_ref_logits(weights, jnp.asarray(ids)))[0, n:n + B]


def _choose(conf, masked, n_t, rule, tau):
    at = np.flatnonzero(masked)
    if rule == DYNAMIC:
        over = at[conf[at] > tau]
        if len(over) >= n_t:
            return over
    return at[np.argsort(-conf[at], kind="stable")[:n_t]]


@pytest.mark.parametrize("steps", [4, 2, 1])
@pytest.mark.parametrize("rule", [STATIC, DYNAMIC])
def test_passes_match_the_reference_trajectory(model, weights, rule, steps):
    """Ragged prompts (whole blocks, a leftover, shorter than a block),
    slot reuse: at EVERY pass the engine's logits are the reference's naive
    pass over the same ids, the positions it fixed are the rule's on the
    reference's confidences, and the tokens it served the reference's
    best."""
    engine = _engine(model)
    tap = _PassTap(engine)
    prompts = _prompts((12, 17, 3, 9))
    outs = (9, 8, 7, 12)
    tau = 0.05
    results = engine.generate(prompts, [
        _greedy(n, denoising_steps=steps, remasking=rule,
                confidence_threshold=tau) for n in outs])
    assert len(tap.passes) >= 12
    by_request = {r.request_id: (p, r) for p, r in zip(prompts, results)}
    for (rid, length, t, denoising), (ids, masked, got) in \
            tap.passes.items():
        prompt, result = by_request[rid]
        seq = prompt + result.output_token_ids
        want = _reference_rows(weights, seq[:length], ids)
        np.testing.assert_allclose(got, want, atol=3e-4)
        if not denoising:
            continue
        # what the rule fixes from the REFERENCE's confidences is what the
        # engine delivered at those positions
        z = want - want.max(-1, keepdims=True)
        conf = np.exp(z).max(-1) / np.exp(z).sum(-1)
        n_t = B // steps + (t < B % steps)
        for j in _choose(conf, masked, n_t, rule, tau):
            k = length + j - len(prompt)
            if k < len(result.output_token_ids):
                assert result.output_token_ids[k] == int(want[j].argmax())
    for r, n in zip(results, outs):
        assert len(r.output_token_ids) == n and r.finish_reason == "length"
    engine.shutdown()


def test_the_dynamic_rule_fixes_more_on_a_peaked_head(weights):
    """With a head scaled until every confidence passes the threshold the
    dynamic rule fixes a whole block in ONE pass; the static rule still
    takes its ``denoising_steps``."""
    peaked = build(dict(weights, head=weights["head"] * 400.0))
    counts = {}
    for rule in (STATIC, DYNAMIC):
        engine = _engine(peaked)
        engine.generate(_prompts((8,)), _greedy(8, remasking=rule))
        m = engine.metrics
        assert m.tokens_fixed_total == 8
        counts[rule] = m.decode_forwards_total - m.commit_passes_total
        engine.shutdown()
    assert counts == {STATIC: 8, DYNAMIC: 2}


def test_a_leftover_opens_the_first_block_and_a_short_prompt_skips_prefill(
        model):
    engine = _engine(model)
    tap = _PassTap(engine)
    long, short = _prompts((13, 3))
    rid = engine.add_request(long, _greedy(5))
    engine.step()
    # 12 positions stored by the prefill, the 13th opens the block
    assert int(engine._lens[0]) == 12
    ids, masked, _ = tap.passes[(rid, 12, 0, True)]
    assert ids.tolist() == [long[12], M, M, M]
    assert masked.tolist() == [False, True, True, True]
    while engine.has_unfinished():
        engine.step()
    assert engine.metrics.prefill_steps == 1
    compiled = set(engine._compiled)
    rid = engine.add_request(short, _greedy(6))
    engine.step()
    ids, masked, _ = tap.passes[(rid, 0, 0, True)]
    assert ids.tolist() == short + [M]
    while engine.has_unfinished():
        engine.step()
    # no prefill ran and nothing was compiled for it
    assert engine.metrics.prefill_steps == 1
    assert set(engine._compiled) == compiled
    out = engine.finished_requests[rid]
    assert len(out.output_token_ids) == 6
    # the first block delivered its one generated position, fixed at pass 0
    assert out.fixed_at[0] == 0 and sorted(out.fixed_at[1:5]) == [0, 1, 2, 3]
    engine.shutdown()


def test_an_id_equal_to_the_mask_token_stays(model):
    """In a prompt (stored prefix and leftover alike) and as a sample: the
    masked record is the engine's own, not a comparison with M."""
    engine = _engine(model)
    inner = engine._sample

    def all_masks(logits, reqs, width, carry=()):
        toks, conf = inner(logits, reqs, width, carry)
        return np.full_like(toks, M), conf

    engine._sample = all_masks
    tap = _PassTap(engine)
    prompt = [M, 5, M, 9, M]                # one block stored, M left over
    rid = engine.add_request(prompt, _greedy(7))
    engine.step()
    ids, masked, _ = tap.passes[(rid, 4, 0, True)]
    assert ids.tolist() == [M, M, M, M]
    assert masked.tolist() == [False, True, True, True]
    while engine.has_unfinished():
        engine.step()
    out = engine.finished_requests[rid]
    assert out.output_token_ids == [M] * 7        # fixed once, never re-drawn
    # 3 + 4 positions took 3 + 4 passes and one commit: none was masked again
    assert engine.metrics.tokens_fixed_total == 7
    engine.shutdown()


def test_slots_in_different_phases_serve_what_each_serves_alone(model):
    prompts = _prompts((5, 14, 8, 21), seed=11)
    sps = [serving.SamplingParams(max_new_tokens=n, temperature=t, top_p=p,
                                  seed=40 + i, denoising_steps=s)
           for i, (n, t, p, s) in enumerate(
               ((10, 0.0, 1.0, 4), (7, 0.8, 0.95, 2), (12, 0.0, 1.0, 1),
                (6, 0.8, 0.95, 4)))]
    together = _engine(model)
    batch = together.generate(prompts, sps)
    together.shutdown()
    for p, sp, got in zip(prompts, sps, batch):
        alone = _engine(model)
        (want,) = alone.generate([p], [sp])
        alone.shutdown()
        assert got.output_token_ids == want.output_token_ids


def test_an_evicted_request_replays_token_identically(model):
    """A pool too small for three: an in-flight block is dropped with its
    slot, prompt + delivered tokens are replayed through the block-causal
    prefill, and the same tokens are served."""
    prompts = _prompts((13, 22, 9), seed=5)
    sps = [serving.SamplingParams(max_new_tokens=n, temperature=t, seed=i)
           for i, (n, t) in enumerate(((18, 0.0), (11, 0.7), (20, 0.0)))]
    roomy = _engine(model)
    want = roomy.generate(prompts, sps)
    roomy.shutdown()
    tight = _engine(model, num_pages=8)       # 7 pages of 8 for 3 slots
    got = tight.generate(prompts, sps)
    assert tight.metrics.requests_evicted >= 1
    assert sum(r.num_evictions for r in got) >= 1
    for g, w in zip(got, want):
        assert g.output_token_ids == w.output_token_ids
    tight._alloc.check_invariant()
    tight.shutdown()


@pytest.mark.parametrize("new_tokens", [1, 6, 7])
def test_a_last_partial_block_is_delivered_in_part(model, new_tokens):
    engine = _engine(model)
    (got,) = engine.generate(_prompts((10,)), _greedy(new_tokens))
    (more,) = engine.generate(_prompts((10,)), _greedy(12))
    assert got.finish_reason == "length"
    assert got.output_token_ids == more.output_token_ids[:new_tokens]
    engine.shutdown()


def test_eos_inside_a_block_ends_the_delivery(model):
    engine = _engine(model)
    (free,) = engine.generate(_prompts((8,)), _greedy(8))
    eos = free.output_token_ids[5]
    first = free.output_token_ids.index(eos)
    (got,) = engine.generate(_prompts((8,)), serving.SamplingParams(
        max_new_tokens=8, eos_token_id=eos))
    assert got.finish_reason == "stop"
    assert got.output_token_ids == free.output_token_ids[:first + 1]
    engine.shutdown()


def test_the_commit_moves_the_length_by_a_block_inside_the_slots_pages(model):
    """Step by step: lengths are whole blocks, move only by the commit,
    the allocator owns the pages a pass writes and never more than a slot
    may have; a block's tokens arrive together and the commit delivers
    nothing."""
    engine = _engine(model)
    page = engine.config.page_size
    rids = [engine.add_request(p, _greedy(n))
            for p, n in zip(_prompts((13, 6)), (11, 9))]
    before = None
    while engine.has_unfinished():
        commits = engine._gen.decode_attrs(engine)["commits"] \
            if engine.num_running else 0
        events = engine.step()
        per_request = {rid: [e for e in events if e[0] == rid]
                       for rid in rids}
        lens = engine._lens.copy()
        for s, r in enumerate(engine._slots):
            if r is None:
                continue
            assert lens[s] % B == 0
            owned = len(engine._alloc.owned_pages(s))
            # what is stored lies in pages the slot owns (the next pass's
            # block gets its page in that pass's capacity check)
            assert -(-int(lens[s]) // page) <= owned \
                <= engine.config.max_pages_per_seq
        for ev in per_request.values():
            assert len(ev) in (0, 1, 2, 3, B)      # a block, or its tail
        if before is not None:
            moved = lens - before
            assert set(moved[moved > 0].tolist()) <= {B}
            # only slots that were running before the step can commit
            assert (moved == B).sum() <= max(commits, 0) + 2
        before = lens
        engine._alloc.check_invariant()
    m = engine.metrics.snapshot()["blocks"]
    assert m["tokens_fixed_total"] == 11 + 9 + 1   # 13 = 12 + 1: 3 + 4 + 4, +
    #                                 6 = 4 + 2: 2 + 4 + 4 (one undelivered)
    assert m["commit_passes_total"] == 2 + 2
    engine.shutdown()


def test_spans_and_counters_carry_the_blocks(model):
    from paddle_tpu.observability import spans
    engine = _engine(model)
    rec = spans.recorder()
    rec.clear()
    engine.generate(_prompts((14,)), _greedy(6))
    got = {}
    for r in rec.spans():
        got.setdefault(r.name, []).append(r.attrs)
    assert got["serving.prefill"][0]["block_tokens"] == 12
    assert got["serving.prefill"][0]["tokens"] == 14
    decode = got["serving.decode"]
    # 14 = 12 + 2: the first block fixes 2 in 2 passes, commits, then 4
    assert [d["masked"] for d in decode] == [2, 1, 0, 4, 3, 2, 1]
    assert [d["commits"] for d in decode] == [0, 0, 1, 0, 0, 0, 0]
    assert {d["block_rows"] for d in decode} == {B}
    assert decode[0]["pages_live"] == 2 and decode[0]["kernel"] is False
    assert got["serving.experts"][-1]["rows"] == 3 * B
    steps = [s["tokens"] for s in got["serving.step"]]
    assert sum(steps) == 6 and max(steps) == 4
    snap = engine.metrics.snapshot()
    assert snap["blocks"] == {
        "block_length": 4, "decode_forwards_total": 7,
        "commit_passes_total": 1, "tokens_fixed_total": 6,
        "tokens_per_forward": round(6 / 7, 4)}
    assert snap["tokens"]["generated"] == 6
    assert snap["inter_token_ms"]["count"] == 1      # one gap a delivery
    assert engine.attention_path == "xla/row_pages+block_diffusion/4/1"
    assert engine.config.compile_bound >= len(engine._compiled)
    engine.shutdown()


def test_pool_and_programs_follow_the_declarations(model):
    engine = _engine(model)
    cfg = engine.config
    assert engine._pool.kind == "kv" and engine._pool.causal_block == B
    # K/V pages at the model's OWN K/V heads (2 x 16), not its 4 query heads
    assert engine._k_pools[0].shape == (cfg.num_pages, cfg.page_size, 32)
    assert engine.kv_pool_bytes == 2 * 2 * cfg.num_pages * cfg.page_size \
        * 32 * 4
    progs = engine.audit_programs()
    assert "sample_12" in progs and "sample_1" not in progs
    assert progs["decode"].in_avals[-1].shape == (3, B)
    # a prefill yields no token: pools and the expert stats, no logits
    assert [a.shape for a in progs["prefill_16"].out_avals][-1] == (4,)
    assert all(a.ndim == 3 or a.shape == (4,)
               for a in progs["prefill_16"].out_avals)
    engine.shutdown()


def test_confidence_is_the_chosen_tokens_probability():
    rng = np.random.default_rng(9)
    logits = jnp.asarray(rng.normal(size=(6, 50)) * 3, jnp.float32)
    seeds = jnp.arange(6, dtype=jnp.int32)
    pos = jnp.full((6,), 17, jnp.int32)
    temps = jnp.asarray([0, 0, 0.8, 0.8, 0.8, 0.8], jnp.float32)
    top_ks = jnp.zeros((6,), jnp.int32)
    top_ps = jnp.asarray([1, 1, 1, 1, 0.5, 0.5], jnp.float32)

    def run(passes):
        toks, conf = sample_tokens(logits, seeds, pos, temps, top_ks, top_ps,
                                   passes=jnp.asarray(passes, jnp.int32))
        return np.asarray(toks), np.asarray(conf)

    toks, conf = run([0] * 6)
    probs = np.array(jnp.exp(logits - jnp.max(logits, -1, keepdims=True)))
    probs /= probs.sum(-1, keepdims=True)
    np.testing.assert_array_equal(toks[:2], probs[:2].argmax(-1))
    np.testing.assert_allclose(conf[:2], probs[:2].max(-1), rtol=1e-5)
    scaled = np.asarray(logits)[2:] / 0.8
    p = np.exp(scaled - scaled.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(conf[2:4], p[[0, 1], toks[2:4]], rtol=1e-5)
    # a nucleus keeps less mass: the renormalised probability is larger
    assert (conf[4:] >= p[[2, 3], toks[4:]] - 1e-6).all()
    assert ((conf > 0) & (conf <= 1.0 + 1e-6)).all()
    # the key folds in the pass: same seed and position, another draw
    again, _ = run([0] * 6)
    np.testing.assert_array_equal(again, toks)
    draws = np.stack([run([t] * 6)[0][2:4] for t in range(6)])
    assert len({tuple(d) for d in draws}) > 1
    # without passes the function is the next-token sampler
    plain = sample_tokens(logits, seeds, pos, temps, top_ks, top_ps)
    assert np.asarray(plain).shape == (6,)


@pytest.mark.parametrize("knob", [{"denoising_steps": 2},
                                  {"remasking": DYNAMIC},
                                  {"confidence_threshold": 0.5}],
                         ids=lambda k: next(iter(k)))
def test_a_next_token_model_refuses_the_block_knobs_by_name(knob):
    P.seed(0)
    model = GPTForCausalLM(gpt3_tiny())
    engine = serving.LLMEngine(model, serving.EngineConfig(
        max_num_seqs=2, page_size=8, max_model_len=32))
    assert isinstance(engine._gen, generation.NextToken)
    assert "blocks" not in engine.metrics.snapshot()
    with pytest.raises(ValueError, match=next(iter(knob))):
        engine.add_request([1, 2, 3], serving.SamplingParams(**knob))
    assert not engine.has_unfinished()
    engine.shutdown()


@pytest.mark.parametrize("what, error, match", [
    ({"kv_cache_dtype": "int8"}, ValueError, "kv_cache_dtype"),
    ({"mesh": {"tp": 2}}, ValueError, "mesh"),
    ({"guard": True}, NotImplementedError, "guard"),
    ({"page_size": 2}, ValueError, "block_length"),
    ({"growth_reserve_pages": 0}, ValueError, "growth_reserve_pages")],
    ids=["kv_cache_dtype", "mesh", "guard", "page_size", "reserve"])
def test_block_diffusion_refuses_by_name(model, what, error, match):
    with pytest.raises(error, match=match):
        _engine(model, **what)


def test_block_requests_are_refused_by_name(model):
    engine = _engine(model)
    with pytest.raises(ValueError, match="denoising_steps"):
        engine.add_request([1, 2, 3], _greedy(4, denoising_steps=5))
    with pytest.raises(ValueError, match="remasking"):
        serving.SamplingParams(remasking="random")
    with pytest.raises(ValueError, match="confidence_threshold"):
        serving.SamplingParams(confidence_threshold=1.5)
    with pytest.raises(ValueError, match="max_model_len"):
        engine.add_request(list(range(1, 58)), _greedy(8))
    rid = engine.add_request([1, 2, 3, 4, 5], _greedy(4))
    engine.step()
    with pytest.raises(NotImplementedError, match="hand-off"):
        engine.export_page_state(rid)
    with pytest.raises(ValueError, match="generation kind"):
        class Odd:
            def generation_spec(self):
                return {"kind": "speculative"}
        generation.make_generation(Odd(), engine.config)
    engine.shutdown()
