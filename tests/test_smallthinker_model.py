"""models/smallthinker.py — full attention without positions beside
sliding-window attention with rotary, ReGLU experts routed from the
attention block's input — against the plain reference
(benchmark/reference/smallthinker.py) on seeded weights, float32, and
driven through serving.LLMEngine: pages for the full layers, a ring of the
window's rows a slot for the window layers, next-token generation running
ahead.  The window is 8 positions and the sequences several windows long,
so that every comparison crosses the window's edge and the ring's wrap."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.models import smallthinker as family
from benchmark.reference import common as refc
from benchmark.reference import smallthinker as ref
from paddle_tpu import serving
from paddle_tpu.distributed.moe import DroplessMoELayer
from paddle_tpu.models.smallthinker import SmallThinkerConfig
from tests.test_deepseek_v3_model import _LogitTap

WINDOW = 8
TINY = {
    "family": "smallthinker", "hidden_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_ffn_hidden_size": 16, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 2, "vocab_size": 300,
    "rms_norm_eps": 1e-6, "rope_theta": 1.5e6, "rope_scaling": None,
    "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
    "sliding_window_size": WINDOW, "max_position_embeddings": 128,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "tie_word_embeddings": False, "initializer_range": 0.1,
}
# float32 everywhere: the program and the reference differ by the order of
# float32 sums alone (flash-free XLA attention, ragged_dot experts)
ATOL = 2e-4


def tiny_weights(seed=5, cfg=TINY):
    w = refc.make_weights(ref.weight_spec(cfg), seed, jnp.float32)
    # norms away from 1, so that a dropped or swapped one would show
    key = jax.random.PRNGKey(seed + 1)
    for i, name in enumerate(sorted(w)):
        if name.endswith(("ln1", "ln2", "norm")):
            w[name] = w[name] + 0.2 * jax.random.normal(
                jax.random.fold_in(key, i), w[name].shape)
    return w


def build(weights, cfg=TINY):
    model = family.build(cfg, training=False, init_weights=False)
    model.eval()
    params = dict(model.named_parameters())
    names = family.leaf_names(cfg)
    assert set(names.values()) == set(params)
    for mine, theirs in names.items():
        params[theirs]._set_value(weights[mine])
    return model


@pytest.fixture(scope="module")
def weights():
    return tiny_weights()


@pytest.fixture(scope="module")
def model(weights):
    return build(weights)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], (1, n)).astype(np.int32)


def test_forward_matches_reference(model, weights):
    """40 positions, five windows: the whole forward."""
    ids = _ids(40)
    got = np.asarray(model(jnp.asarray(ids)))
    want = np.asarray(ref.logits(TINY, weights, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("fault", ["router_reads_b", "no_window"])
def test_reference_sees_a_changed_mechanism(model, weights, monkeypatch,
                                            fault):
    """The comparison can tell the mechanism apart: a reference whose
    router reads the experts' input ``b`` instead of the attention block's
    ``a``, or whose window layers attend over every earlier position,
    misses the program's logits by far more than the tolerance."""
    if fault == "router_reads_b":
        inner = ref.expert_layer
        monkeypatch.setattr(ref, "expert_layer",
                            lambda cfg, w, b, a, mode="f32":
                            inner(cfg, w, b, b, mode))
        cfg = TINY
    else:
        cfg = dict(TINY, sliding_window_size=1000)
    ids = _ids(40)
    got = np.asarray(model(jnp.asarray(ids)))
    want = np.asarray(ref.logits(cfg, weights, jnp.asarray(ids)))
    assert np.abs(got - want).max() > 50 * ATOL


def _engine(model, **kw):
    cfg = dict(max_num_seqs=3, page_size=8, max_model_len=64,
               dtype=jnp.float32)
    cfg.update(kw)
    return serving.LLMEngine(model, serving.EngineConfig(**cfg))


def _greedy(n):
    return serving.SamplingParams(max_new_tokens=n, temperature=0.0)


def _check_against_reference(tap, weights, prompts, results):
    checked = 0
    for k, (prompt, res) in enumerate(zip(prompts, results)):
        seq = prompt + list(res.output_token_ids)
        full = np.asarray(ref.logits(TINY, weights, jnp.asarray([seq])))[0]
        for j in range(len(res.output_token_ids)):
            got = tap.rows[(f"req-{k}", len(prompt) + j)]
            np.testing.assert_allclose(got, full[len(prompt) + j - 1],
                                       atol=ATOL)
            checked += 1
    return checked


def test_engine_prefill_then_decode_matches_reference(model, weights):
    """Ragged lengths, prompts longer than the window, decode across the
    ring's wrap, slot reuse (5 requests through 3 slots, the later ones
    shorter) and an evict-and-replay (the full layers' pages too few for
    all): at every sampled position the engine's logits are the
    reference's full forward's — with the passes running ahead."""
    rng = np.random.default_rng(3)
    engine = _engine(model, num_pages=9)     # 8 pages of 8 for 3 slots
    assert engine._run_ahead
    tap = _LogitTap(engine)
    prompts = [rng.integers(1, TINY["vocab_size"], n).tolist()
               for n in (21, 17, 30, 3, 12)]
    sps = [_greedy(n) for n in (20, 7, 22, 25, 9)]
    results = engine.generate(prompts, sps)
    assert engine.metrics.requests_evicted >= 1          # a replay ran
    assert engine.metrics.passes_ahead > 0
    assert _check_against_reference(tap, weights, prompts, results) == sum(
        sp.max_new_tokens for sp in sps)
    engine.shutdown()


def test_a_discarded_pass_in_flight_leaves_the_rings_sound(model, weights):
    """A pass run ahead and discarded unfetched (as a hand-off or shutdown
    discards it) wrote a ring row of a position no later query's window
    holds: generation resumed after it serves the reference's logits."""
    engine = _engine(model)
    tap = _LogitTap(engine)
    prompts = [_ids(19, seed=1)[0].tolist(), _ids(5, seed=2)[0].tolist()]
    for k, p in enumerate(prompts):
        assert engine.add_request(p, _greedy(18)) == f"req-{k}"
    discarded = 0
    while engine.has_unfinished():
        engine.step()
        if engine._ahead is not None and discarded < 3:
            engine._drain("idle")
            discarded += 1
    assert discarded == 3
    results = [engine.finished_requests[f"req-{k}"] for k in range(2)]
    assert _check_against_reference(tap, weights, prompts, results) == 36
    engine.shutdown()


def test_pool_spans_and_counters(model):
    """One full layer of pages beside three window layers' rings: the
    pool, the spans' window attributes, the snapshot."""
    from paddle_tpu.observability import spans
    engine = _engine(model)
    pool = engine._pool
    assert pool.kinds == ["kv", "window", "window", "window"]
    assert pool.window_layers == 3 and pool.state_layers == 0
    assert engine.attention_path.startswith(
        f"kv:xla/row_pages+window/{WINDOW}:xla/ring")
    ring = 2 * 3 * WINDOW * 2 * 16 * 4     # K and V, slots, rows, H_kv, d
    assert pool.window_nbytes == 3 * ring
    assert engine.kv_pool_bytes == pool.kv.nbytes + 3 * ring
    rec = spans.recorder()
    rec.clear()
    engine.generate([_ids(11)[0].tolist()], _greedy(4))
    got = {r.name: r.attrs for r in rec.spans()
           if r.name in ("serving.decode", "serving.prefill")}
    assert got["serving.prefill"]["window"] == WINDOW
    assert got["serving.prefill"]["window_layers"] == 3
    assert got["serving.prefill"]["window_rows"] == 3 * WINDOW
    # a step launches one or two passes over one slot of 11 + 1.. rows
    live = [r.attrs["window_rows_live"] for r in rec.spans()
            if r.name == "serving.decode"]
    assert max(live) == 2 * WINDOW and min(live) == 0
    snap = engine.metrics.snapshot()["window"]
    assert snap["pool_bytes"] == 3 * ring
    engine.shutdown()


def test_reglu_experts_match_reference(weights):
    """DroplessMoELayer(act="relu") routed from another input is the
    reference's expert layer; an unknown activation is refused."""
    import paddle_tpu as P
    w = {k[3:]: v for k, v in weights.items() if k.startswith("l1.")}
    layer = DroplessMoELayer(32, 16, 8, 2, route="softmax", act="relu")
    layer.gate_weight._set_value(w["gate"])
    layer.w13._set_value(w["experts.w13"])
    layer.w2._set_value(w["experts.w2"])
    rng = np.random.default_rng(4)
    a, b = (jnp.asarray(rng.standard_normal((24, 32)), jnp.float32)
            for _ in range(2))
    got = np.asarray(layer(P.to_tensor(b), route_from=P.to_tensor(a)))
    want = np.asarray(ref.expert_layer(TINY, w, b, a))
    np.testing.assert_allclose(got, want, atol=1e-5)
    with pytest.raises(ValueError, match="gelu"):
        DroplessMoELayer(32, 16, 8, 2, route="softmax", act="gelu")


@pytest.mark.parametrize("change,error", [
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"moe_num_secondary_experts": 4}, "secondary"),
    ({"rope_layout": [0, 0, 1, 1]}, "rope_layout"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
])
def test_what_is_not_built_is_refused(change, error):
    with pytest.raises(NotImplementedError, match=error):
        SmallThinkerConfig.from_published(dict(TINY, **change))


def test_published_layouts_name_the_layers():
    c = SmallThinkerConfig.from_published(dict(
        TINY, num_hidden_layers=8, rope_layout=[0, 1, 1, 1] * 13,
        sliding_window_layout=[0, 1, 1, 1] * 13))
    assert c.window_layers == [False, True, True, True] * 2
    assert (c.n_routed_experts, c.num_experts_per_tok,
            c.moe_intermediate_size) == (8, 2, 16)


def test_fingerprint_tells_window_geometries_apart(model):
    """Two pools that differ only in the window, or only in which layer is
    a window layer, name different program families in the serving AOT
    cache's fingerprint; on a TPU the flash kernel's revision is part of
    the same term."""
    from paddle_tpu.ops.pallas.flash_attention import \
        FLASH_ATTENTION_REVISION
    from paddle_tpu.serving.aot_cache import engine_fingerprint
    from paddle_tpu.serving.kv_pool import LayeredPool
    cfg = serving.EngineConfig(max_num_seqs=3, page_size=8, max_model_len=64,
                               dtype=jnp.float32)
    heads = {"num_heads": 2, "head_dim": 16, "query_heads": 4}
    full = dict(heads, kind="kv")

    def ring(w):
        return dict(heads, kind="window", window=w)

    params = {k: t._value for k, t in model.state_dict().items()}
    prints = {
        name: engine_fingerprint(model.config, cfg, params,
                                 attention=LayeredPool(cfg, layers)
                                 .attention_path)
        for name, layers in (
            ("w8", [full, ring(8), ring(8), ring(8)]),
            ("w16", [full, ring(16), ring(16), ring(16)]),
            ("moved", [ring(8), full, ring(8), ring(8)]))}
    assert len(set(prints.values())) == 3
    assert FLASH_ATTENTION_REVISION not in LayeredPool(
        cfg, [full, ring(8)]).attention_path     # no flash on the CPU
