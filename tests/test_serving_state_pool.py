"""Two cache kinds in one engine (serving/kv_pool.py LayeredPool): pages of
grouped-query K/V for the attention layers and a per-SLOT recurrent state
for the mamba layers, driven through serving.LLMEngine by the tiny
granitemoehybrid model against its plain reference."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import granitemoehybrid as ref
from paddle_tpu import serving
from paddle_tpu.incubate.nn.paged_attention import grouped_causal_attention
from paddle_tpu.serving import kv_pool
from tests.test_deepseek_v3_model import _LogitTap
from tests.test_granitemoehybrid_model import TINY, build, tiny_weights

STATE_LAYERS = 3                                  # of TINY's four


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


def _greedy(n):
    return serving.SamplingParams(max_new_tokens=n, temperature=0.0)


def test_engine_prefill_then_decode_matches_reference(model, weights):
    """Ragged lengths, slot reuse (5 requests through 3 slots) and an
    evict-and-replay (a pool too small for all): at every sampled position
    the engine's logits — prefill through the chunked scan, decode through
    pages and per-slot state — are the reference's full forward's."""
    rng = np.random.default_rng(3)
    engine = _engine(model, num_pages=6)       # 5 pages of 8 for 3 slots
    tap = _LogitTap(engine)
    prompts = [rng.integers(1, TINY["vocab_size"], n).tolist()
               for n in (5, 17, 9, 26, 12)]
    sps = [_greedy(n) for n in (20, 7, 22, 6, 9)]
    results = engine.generate(prompts, sps)
    assert engine.metrics.requests_evicted >= 1          # a replay ran
    assert engine.metrics.moe_tokens_routed > 0
    assert engine.metrics.state_admits_total == (
        5 + engine.metrics.requests_evicted)
    checked = 0
    for k, (prompt, res) in enumerate(zip(prompts, results)):
        seq = prompt + list(res.output_token_ids)
        full = np.asarray(ref.logits(TINY, weights, jnp.asarray([seq])))[0]
        for j in range(len(res.output_token_ids)):
            got = tap.rows[(f"req-{k}", len(prompt) + j)]
            np.testing.assert_allclose(got, full[len(prompt) + j - 1],
                                       atol=2e-4)
            checked += 1
    assert checked == sum(sp.max_new_tokens for sp in sps)
    engine.shutdown()


def _slot_state(engine, slot):
    """(conv, ssm) of every state layer at `slot`, as numpy."""
    kinds = engine._pool.kinds
    return [(np.asarray(engine._k_pools[li][slot]),
             np.asarray(engine._v_pools[li][slot]))
            for li, kind in enumerate(kinds) if kind == "state"]


@pytest.mark.parametrize("buckets", [(16, 64), (32, 64), (64,)],
                         ids=["bucket16", "bucket32", "bucket64"])
def test_one_prompt_leaves_the_same_state_in_every_bucket(model, weights,
                                                          buckets):
    """A prompt of 13 tokens prefilled in a bucket of 16, 32 or 64: the
    slot's state after the prefill is the reference's after 13 positions
    (padding never reaches it)."""
    prompt = list(range(5, 18))
    engine = _engine(model, prefill_buckets=buckets)
    rid = engine.add_request(prompt, _greedy(4))
    engine._admit([])                           # the prefill alone
    slot = engine._requests[rid].slot
    got = _slot_state(engine, slot)
    # the reference's state of layer 0 after the prompt
    h0 = np.asarray(weights["embed"])[prompt][None] * TINY[
        "embedding_multiplier"]
    lw = ref.layer_weights(weights, 0)
    _, S, window = ref.mixer(TINY, lw, ref.rms_norm(
        jnp.asarray(h0), lw["ln1"], TINY["rms_norm_eps"]))
    np.testing.assert_allclose(got[0][1], np.asarray(S[0]), atol=1e-5)
    np.testing.assert_allclose(got[0][0], np.asarray(window[0]), atol=1e-5)
    assert len(got) == STATE_LAYERS
    engine.shutdown()


def test_state_is_the_same_array_in_two_buckets(model):
    """The same prompt through two engines with different buckets: every
    state layer's entry agrees to rounding."""
    prompt = list(range(40, 61))
    states = []
    for buckets in ((32, 64), (64,)):
        engine = _engine(model, prefill_buckets=buckets)
        rid = engine.add_request(prompt, _greedy(2))
        engine._admit([])
        states.append(_slot_state(engine, engine._requests[rid].slot))
        engine.shutdown()
    for (c0, s0), (c1, s1) in zip(*states):
        np.testing.assert_allclose(c0, c1, atol=1e-5)
        np.testing.assert_allclose(s0, s1, atol=1e-5)


def test_a_reused_slot_serves_what_the_request_gets_alone(model):
    """Six requests through ONE slot, one after another: each gets the
    tokens it gets alone in a fresh engine — a finished request's state
    never reaches the next."""
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, TINY["vocab_size"], n).tolist()
               for n in (9, 30, 4, 17, 11, 23)]
    sp = serving.SamplingParams(max_new_tokens=6, temperature=0.9,
                                top_p=0.9, seed=4)
    one = _engine(model, max_num_seqs=1)
    together = [r.output_token_ids for r in one.generate(prompts, sp)]
    assert one.metrics.state_admits_total == 6
    one.shutdown()
    for prompt, got in zip(prompts[1:3], together[1:3]):
        alone = _engine(model, max_num_seqs=1)
        assert alone.generate([prompt], sp)[0].output_token_ids == got
        alone.shutdown()


def test_an_evicted_request_replays_token_identically(model):
    """A pool too small for three running requests evicts one; the replay
    prefill rebuilds pages AND state: every request ends with the tokens
    of an engine that never evicted."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, TINY["vocab_size"], n).tolist()
               for n in (7, 15, 10)]
    sp = serving.SamplingParams(max_new_tokens=18, temperature=0.7,
                                top_p=0.95, seed=2)
    roomy = _engine(model)
    want = [r.output_token_ids for r in roomy.generate(prompts, sp)]
    assert roomy.metrics.requests_evicted == 0
    roomy.shutdown()
    tight = _engine(model, num_pages=7)
    got = [r.output_token_ids for r in tight.generate(prompts, sp)]
    assert tight.metrics.requests_evicted >= 1
    assert got == want
    tight.shutdown()


def test_state_and_pages_hand_off_mid_request(model):
    """A request exported after five steps — pages AND per-slot state
    through the fleet's wire format — imported into another slot of a
    second engine beside a running request, finishes with the tokens of
    the uninterrupted run."""
    from paddle_tpu.serving.fleet import wire
    prompt = list(range(3, 20))
    sp = serving.SamplingParams(max_new_tokens=12, temperature=0.7,
                                top_p=0.9, seed=5)
    whole = _engine(model)
    want = whole.generate([prompt], sp)[0].output_token_ids
    whole.shutdown()
    first, second = _engine(model), _engine(model)
    rid = first.add_request(prompt, sp)
    for _ in range(5):
        first.step()
    state = wire.unpack_state(wire.pack_state(first.export_page_state(rid)))
    assert not first.has_unfinished()
    assert [sorted(blocks) for blocks in state["layers"]] == [
        ["conv", "ssm"], ["conv", "ssm"], ["k", "v"], ["conv", "ssm"]]
    assert state["layers"][0]["ssm"].shape == (8, 16, 16)
    assert state["layers"][0]["ssm"].dtype == np.float32
    second.add_request([7, 8, 9], _greedy(30))
    second.step()
    moved = second.import_page_state(state)
    assert second._requests[moved].slot == 1
    while second.has_unfinished():
        second.step()
    assert second.finished_requests[moved].output_token_ids == want
    first.shutdown()
    second.shutdown()


def test_import_refuses_another_state_geometry(model):
    first = _engine(model)
    rid = first.add_request(list(range(3, 12)), _greedy(8))
    first.step()
    state = first.export_page_state(rid)
    state["geometry"] = dict(state["geometry"], ssm=[8, 16, 32])
    with pytest.raises(ValueError, match="ssm"):
        first.import_page_state(state)
    first.shutdown()


@pytest.mark.parametrize("what", [{"kv_cache_dtype": "int8"},
                                  {"mesh": {"tp": 2}}],
                         ids=["kv_cache_dtype", "mesh"])
def test_state_kind_refuses_by_name(model, what):
    with pytest.raises(ValueError, match="'state' layer"):
        _engine(model, **what)


def test_unknown_layer_kind_is_refused():
    cfg = serving.EngineConfig(max_num_seqs=2, page_size=8, max_model_len=32)
    with pytest.raises(ValueError, match="ring"):
        kv_pool.LayeredPool(cfg, [{"kind": "ring"}])
    with pytest.raises(ValueError, match="one geometry"):
        kv_pool.LayeredPool(cfg, [
            {"kind": "kv", "num_heads": 2, "head_dim": 8},
            {"kind": "kv", "num_heads": 4, "head_dim": 8}])


def test_spans_and_counters_carry_the_state(model):
    from paddle_tpu.observability import spans
    engine = _engine(model)
    rec = spans.recorder()
    rec.clear()
    engine.generate([[3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13]], _greedy(4))
    got = {r.name: r.attrs for r in rec.spans()
           if r.name in ("serving.decode", "serving.prefill",
                         "serving.experts")}
    assert got["serving.prefill"]["scan_tokens"] == 11
    assert "scan_tokens" not in got["serving.decode"]
    assert got["serving.decode"]["state_rows"] == STATE_LAYERS
    assert got["serving.decode"]["kernel"] is False
    # the marker counts the experts HELD (all 8 of TINY's, 4 layers)
    assert 1 <= got["serving.experts"]["experts_hit"] <= 4 * 8
    snap = engine.metrics.snapshot()["state"]
    assert snap == {"pool_bytes": engine._pool.state_nbytes,
                    "admits_total": 1}
    engine.shutdown()


def test_pool_accounting_follows_the_declaration(model):
    engine = _engine(model)
    cfg = engine.config
    pool = engine._pool
    assert pool.kinds == ["state", "state", "kv", "state"]
    # K/V pages at the attention layer's OWN heads: 2 x 16, not 4 x 16
    kv = 2 * cfg.num_pages * 2 * cfg.page_size * 16 * 4
    state = STATE_LAYERS * cfg.max_num_seqs * (
        3 * (8 * 16 + 2 * 16) * 4 + 8 * 16 * 16 * 4)
    assert pool.state_nbytes == state == engine.metrics.state_pool_bytes
    assert engine.kv_pool_bytes == kv + state
    assert engine.hbm_budget_bytes == (engine.params_bytes
                                       + 2 * (kv + state) + (64 << 20))
    assert engine.attention_path == \
        "kv:xla/row_pages+state:xla/float32+next_token/1"
    # row pages: a token's 2 x 16 K values are one row
    assert engine._k_pools[2].shape == (cfg.num_pages, cfg.page_size, 32)
    assert engine._v_pools[0].dtype == jnp.float32
    engine.shutdown()


def test_gpt_and_latent_pools_add_nothing():
    """The kinds that cache by page alone: no extra operand, no extra
    span attribute, no state bytes."""
    cfg = serving.EngineConfig(max_num_seqs=2, page_size=8, max_model_len=32)
    pool = kv_pool.PlainKV(cfg, 2, 4, 8)
    assert pool.slot_operands(1) == () and pool.state_nbytes == 0
    assert pool.prefill_attrs(5, 16) == {} and pool.decode_attrs(2) == {}


def test_grouped_decode_read_equals_dense_attention():
    """GroupedKV with 4 query heads on 2 K/V heads and its own scale: the
    paged decode read of the last position equals dense grouped
    attention's last row."""
    cfg = serving.EngineConfig(max_num_seqs=1, page_size=4, max_model_len=16,
                               num_pages=6)
    pool = kv_pool.GroupedKV(cfg, 1, 2, 8, query_heads=4, scale=0.3)
    assert pool.rows and not pool.decode_kernel
    assert pool.attention_path == "xla/row_pages"
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 10, h, 8))
               for i, h in enumerate((4, 2, 2)))
    (kp,), (vp,) = pool.allocate()
    tables = jnp.asarray([[2, 5, 1, 3]], jnp.int32)
    out, kp, vp = pool.prefill(q[:, :9], k[:, :9], v[:, :9], kp, vp, tables,
                               jnp.asarray([9], jnp.int32))
    want = grouped_causal_attention(q, k, v, 0.3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want[:, :9]),
                               atol=1e-5)
    last, kp, vp = pool.decode(q[:, 9:], k[:, 9:], v[:, 9:], kp, vp, tables,
                               jnp.asarray([9], jnp.int32))
    np.testing.assert_allclose(np.asarray(last), np.asarray(want[:, 9:]),
                               atol=1e-5)
