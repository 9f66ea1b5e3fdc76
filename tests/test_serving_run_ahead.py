"""The next-token engine's run-ahead (serving/engine.py
``_decode_step_inner``): pass n+1 is launched, its ids taken on the device
from pass n's sampler output, before pass n's tokens are fetched.  The
tokens must be those of the synchronous loop, request by request, through
every path that empties the pipeline; the counters say what happened."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.resilience.faultinject import (FaultInjector, FaultPlan,
                                               FaultSpec)
from paddle_tpu.serving.fleet import DisaggregatedEngine
from paddle_tpu.serving.metrics import DRAIN_CAUSES


def _gpt():
    P.seed(0)
    return GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=16, num_layers=2, num_heads=2,
        max_seq_len=64, dropout=0.0, attention_dropout=0.0)), \
        dict(page_size=4, max_model_len=48, prefill_buckets=(8, 16, 32))


def _deepseek_v3():
    """A model with expert layers: its sampler output carries the expert
    stats behind the tokens."""
    from tests.test_deepseek_v3_model import build
    from tests.test_deepseek_v3_reference import tiny_weights
    return build(tiny_weights()), dict(page_size=8, max_model_len=64,
                                       dtype=jnp.float32)


def _granitemoehybrid():
    """A pool with a state by slot (Mamba-2 layers beside one layer of
    pages): every pass advances a slot's state in place."""
    from tests.test_granitemoehybrid_model import build, tiny_weights
    return build(tiny_weights()), dict(page_size=8, max_model_len=64,
                                       dtype=jnp.float32)


def _qwen3_next():
    """A state by slot of another recurrence (Gated DeltaNet layers beside
    one layer of pages)."""
    from tests.test_qwen3_next_model import build, tiny_weights
    return build(tiny_weights()), dict(page_size=8, max_model_len=64,
                                       dtype=jnp.float32)


STATE_KINDS = {"granitemoehybrid": _granitemoehybrid,
               "qwen3_next": _qwen3_next}
KINDS = {"gpt": _gpt, "deepseek_v3": _deepseek_v3, **STATE_KINDS}


@pytest.fixture(scope="module", params=sorted(KINDS))
def kind(request):
    return KINDS[request.param]()


def _engine(kind, clock=None, **kw):
    model, cfg = kind
    return serving.LLMEngine(model, serving.EngineConfig(
        max_num_seqs=3, **{**cfg, **kw}), clock=clock)


PROMPTS = [[5, 6, 7], [9, 3, 2, 8, 1], [4, 4], [7, 1, 2, 3], [2, 9, 5],
           [6, 6, 1, 3, 8, 2], [3, 8, 8, 1]]


def _params(eos):
    """Greedy and seeded top-p draws; the third request may stop at
    `eos`, the first has a deadline."""
    return [
        serving.SamplingParams(max_new_tokens=12, temperature=0.0,
                               deadline_s=5.0),
        serving.SamplingParams(max_new_tokens=9, temperature=0.8,
                               top_p=0.9, seed=11),
        serving.SamplingParams(max_new_tokens=10, temperature=0.0,
                               eos_token_id=eos),
        serving.SamplingParams(max_new_tokens=7, temperature=1.0,
                               top_p=0.95, seed=3),
        serving.SamplingParams(max_new_tokens=8, temperature=0.0),
        serving.SamplingParams(max_new_tokens=6, temperature=0.7,
                               top_p=0.8, seed=29),
        serving.SamplingParams(max_new_tokens=5, temperature=0.9,
                               top_p=0.9, seed=41),
    ]


def _serve(kind, ahead, eos=None, faults=True):
    """The mix, step by step: three requests, three more arriving
    mid-stream (queued: their admissions fill the slots), the first
    request's deadline passing at step 5, a ``pool_exhaust`` preemption, a
    ``serving.decode`` exception (the crash-safe path) and a seventh
    request arriving at step 11 into a slot left free (it joins the pass
    launched ahead).  Returns ({request id: (tokens, finish reason)}, the
    engine's snapshot, compiles after warm-up)."""
    now = [0.0]
    engine = _engine(kind, clock=lambda: now[0])
    engine._run_ahead = ahead
    engine.warmup()
    compiled = engine.metrics.compile_count
    sps = _params(eos)
    plan = FaultPlan([
        FaultSpec("serving.pool", "pool_exhaust", at=4,
                  payload={"victims": 1}),
        FaultSpec("serving.decode", "exception", at=7,
                  payload={"request_id": "req-1"}),
    ] if faults else [])
    with FaultInjector(plan):
        for k in range(3):
            engine.add_request(PROMPTS[k], sps[k])
        for step in range(200):
            if step == 2:
                for k in range(3, 6):
                    engine.add_request(PROMPTS[k], sps[k])
            if step == 5:
                now[0] = 10.0
            if step == 11:
                engine.add_request(PROMPTS[6], sps[6])
            if not engine.has_unfinished():
                break
            engine.step()
    done = {rid: (list(r.output_token_ids), r.finish_reason)
            for rid, r in engine.finished_requests.items()}
    snap = engine.metrics.snapshot()
    new_compiles = engine.metrics.compile_count - compiled
    assert engine.metrics.compile_count <= engine.config.compile_bound
    engine.shutdown()
    return done, snap, new_compiles


def test_run_ahead_serves_the_synchronous_tokens(kind):
    """Request by request the tokens of the synchronous loop, through
    admissions mid-stream, a request stopping at EOS and the rest at
    `max_new_tokens`, a deadline, a preemption and a decode fault; nothing
    compiles after warm-up.  A deadline cuts a request by the clock, and
    an admission's step delivers the pass in flight and the next one: the
    request cut has as many tokens or more, of the same sequence."""
    plain, _, _ = _serve(kind, ahead=False, faults=False)
    eos = plain["req-2"][0][2]          # the third request stops early
    want, sync, _ = _serve(kind, ahead=False, eos=eos)
    got, snap, new_compiles = _serve(kind, ahead=True, eos=eos)
    assert got.keys() == want.keys()
    for rid, (tokens, reason) in want.items():
        if reason == "deadline":
            assert got[rid][1] == reason
            assert got[rid][0][:len(tokens)] == tokens
        else:
            assert got[rid] == (tokens, reason)
    reasons = [reason for _toks, reason in got.values()]
    assert sorted(set(reasons)) == ["deadline", "length", "stop"]
    assert len(got["req-2"][0]) <= 3
    assert snap["requests"]["evicted"] >= 2
    assert snap["decode_fault_recoveries"] == 1
    assert new_compiles == 0
    assert snap["run_ahead"]["passes_ahead"] > 0
    assert snap["run_ahead"]["drains"]["prefill"] > 0
    assert sync["run_ahead"]["passes_ahead"] == 0


def test_drains_are_counted_by_cause(kind):
    """`snapshot()["run_ahead"]`: a pass launched ahead each step but the
    first and the last of a lone request; a pool fault in a pass launched
    ahead drains it (`evict`), so does a decode fault (`fault`), an
    admission that takes the last free slot (`prefill`), a hand-off
    (`handoff`), and the end of the work (`idle`)."""
    engine = _engine(kind)
    sp = serving.SamplingParams(max_new_tokens=6, temperature=0.0)
    engine.generate([[3, 4, 5]], sp)
    counts = engine.metrics.snapshot()["run_ahead"]
    # six tokens: the prefill's, then five passes, four of them ahead;
    # the last fetched with none behind it
    assert counts["passes_ahead"] == 4
    assert counts["drains"] == {**dict.fromkeys(DRAIN_CAUSES, 0),
                                "idle": 1}
    rid = engine.add_request([3, 4, 5], serving.SamplingParams(
        max_new_tokens=8, temperature=0.0))
    engine.step()
    engine.add_request([1, 2], sp)
    engine.add_request([2, 7, 1], sp)
    engine.step()
    plan = FaultPlan([FaultSpec("serving.pool", "pool_exhaust", at=0),
                      FaultSpec("serving.decode", "exception", at=1)])
    with FaultInjector(plan):
        engine.step()                   # the pool fault: evict
        engine.step()                   # a fresh pass, then a decode fault
    engine.step()
    engine.export_page_state(next(iter(engine._requests)))
    drains = engine.metrics.snapshot()["run_ahead"]["drains"]
    assert {c: drains[c] for c in ("prefill", "evict", "fault",
                                   "handoff")} == dict.fromkeys(
        ("prefill", "evict", "fault", "handoff"), 1)
    engine.shutdown()


def _split(kind, ahead, sps):
    """A prefill engine and a decode engine, each request handed off after
    the prefill engine's first step — with the pass launched ahead of that
    step in flight, holding its third token.  Also returns the hand-off
    drains and whether the pool keeps a state by slot."""
    pre, dec = _engine(kind), _engine(kind)
    pre._run_ahead = dec._run_ahead = ahead
    out = DisaggregatedEngine(pre, dec).generate(PROMPTS[:4], sps)
    handoffs = pre.metrics.snapshot()["run_ahead"]["drains"]["handoff"]
    pre.shutdown()
    dec.shutdown()
    return [(r.tokens, r.finish_reason, r.finished_on) for r in out], \
        handoffs, bool(pre._pool.state_layers)


@pytest.mark.parametrize("stop", ["length", "eos"])
def test_a_hand_off_serves_the_synchronous_tokens(kind, stop):
    """Over pages alone a hand-off discards the pass in flight: a request
    whose third token — its last, by `max_new_tokens` or at EOS — was in
    it is exported as the step left it, and the decode engine serves that
    token.  A pool with a state by slot fetches the pass instead: such a
    request finishes on the prefill engine, with the same tokens."""
    new = 3 if stop == "length" else 8
    sps = [serving.SamplingParams(max_new_tokens=new, temperature=0.0)] + [
        serving.SamplingParams(max_new_tokens=new, temperature=0.9,
                               top_p=0.9, seed=seed) for seed in (5, 6, 7)]
    if stop == "eos":
        plain, _, _ = _split(kind, False, sps)
        sps = [serving.SamplingParams(
            max_new_tokens=8, temperature=sp.temperature, top_p=sp.top_p,
            seed=sp.seed, eos_token_id=tokens[2])
            for sp, (tokens, _r, _on) in zip(sps, plain)]
    want, _, _ = _split(kind, False, sps)
    got, handoffs, state = _split(kind, True, sps)
    assert handoffs > 0
    if not state:
        assert got == want
        assert sum(len(t) == 3 and on == "decode" for t, _r, on in got) >= 2
    else:
        assert [(t, r) for t, r, _on in got] == [(t, r) for t, r, _on in want]
        assert sum(len(t) == 3 and on == "decode" for t, _r, on in want) >= 2
        assert sum(len(t) == 3 and on == "prefill" for t, _r, on in got) >= 2


@pytest.mark.parametrize("name", sorted(STATE_KINDS))
def test_a_state_hand_off_fetches_the_pass_in_flight(name):
    """A hand-off from a pool with a state by slot, with a pass in flight,
    fetches it: the payload carries its token, its state rows (and pages)
    are the synchronous engine's after the same tokens, the next step
    returns the token's event, and an engine importing the payload serves
    the synchronous tokens."""
    kind = KINDS[name]()
    prompt = PROMPTS[5]
    sp = serving.SamplingParams(max_new_tokens=10, temperature=0.8,
                                top_p=0.9, seed=13)
    whole = _engine(kind)
    whole._run_ahead = False
    want = whole.generate([prompt], sp)[0].output_token_ids
    ahead = _engine(kind)
    rid = ahead.add_request(prompt, sp)
    for _ in range(3):
        ahead.step()
    held = len(ahead._requests[rid].output_token_ids)
    assert ahead._ahead is not None
    got = ahead.export_page_state(rid)
    assert ahead.metrics.drains["handoff"] == 1
    n = len(got["output_token_ids"])
    assert n == held + 1 and got["output_token_ids"] == want[:n]
    assert ahead.step() == [(rid, want[n - 1], False)]
    # a fresh engine: a page's rows past the length are another request's
    sync = _engine(kind)
    sync._run_ahead = False
    srid = sync.add_request(prompt, sp)
    while len(sync._requests[srid].output_token_ids) < n:
        sync.step()
    there = sync.export_page_state(srid)
    assert got["len"] == there["len"]
    assert [sorted(b) for b in got["layers"]] == [
        sorted(b) for b in there["layers"]]
    assert sum("ssm" in b for b in got["layers"]) == \
        ahead._pool.state_layers
    for mine, theirs in zip(got["layers"], there["layers"]):
        for block in theirs:
            np.testing.assert_array_equal(mine[block], theirs[block])
    dec = _engine(kind)
    moved = dec.import_page_state(got)
    while dec.has_unfinished():
        dec.step()
    assert dec.finished_requests[moved].output_token_ids == want
    assert dec.metrics.snapshot()["run_ahead"]["passes_ahead"] > 0
    for engine in (whole, sync, ahead, dec):
        engine.shutdown()


@pytest.mark.parametrize("which", ["guard", "block_diffusion"])
def test_guard_and_block_engines_do_not_run_ahead(which):
    """A guarded engine and a block-diffusion engine fetch every pass
    before the next is launched; the guarded one serves the unguarded
    synchronous loop's tokens."""
    if which == "guard":
        kind = _gpt()
        prompts, new = PROMPTS[:4], 6
        sync = _engine(kind)
        sync._run_ahead = False
        sp = serving.SamplingParams(max_new_tokens=new, temperature=0.0)
        want = [r.output_token_ids for r in sync.generate(prompts, sp)]
        sync.shutdown()
        engine = _engine(kind, guard=True)
    else:
        from tests.test_sdar_moe_model import M, build, tiny_weights
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, M, n).tolist() for n in (9, 14, 5)]
        new, want = 6, None
        engine = serving.LLMEngine(build(tiny_weights()),
                                   serving.EngineConfig(
                                       max_num_seqs=3, page_size=8,
                                       max_model_len=64, dtype=jnp.float32))
        sp = serving.SamplingParams(max_new_tokens=new, temperature=0.0)
    got = [r.output_token_ids for r in engine.generate(prompts, sp)]
    counts = engine.metrics.snapshot()["run_ahead"]
    assert counts["passes_ahead"] == 0
    assert counts["drains"]["kind"] == engine.metrics.decode_steps > 0
    assert all(len(t) == new for t in got)
    if want is not None:
        assert got == want
    engine.shutdown()


def test_a_replica_reports_a_request_its_hand_off_finished():
    """Over the fleet's verbs: a hand-off from a state pool whose pass in
    flight holds the request's last token answers ``finished`` and parks
    no blob, and the replica's next step reports the finish."""
    from paddle_tpu.serving.fleet.server import ReplicaServer
    engine = _engine(_granitemoehybrid())
    server = ReplicaServer(None, 0, lambda p: engine)
    server._dispatch("boot", {})
    rid = server._dispatch("add", {"prompt": [3, 4, 5], "sp": {
        "max_new_tokens": 3, "temperature": 0.0}})
    first = server._dispatch("step", {})["events"]
    assert [tok is not None for r, tok, _fin in first if r == rid] == [
        True, True]
    assert engine._ahead is not None
    assert server._dispatch("export_handoff", {
        "request_id": rid, "hid": "h0"}) == {"hid": "h0", "finished": True}
    after = server._dispatch("step", {})
    assert [fin for r, _tok, fin in after["events"] if r == rid] == [True]
    assert [f["rid"] for f in after["finished"]] == [rid]
    assert len(after["finished"][0]["tokens"]) == 3
    server._dispatch("shutdown", {})
