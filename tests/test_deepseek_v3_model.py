"""models/deepseek_v3.py, distributed/moe.py's dropless layer and the
latent pool of serving.LLMEngine against the plain reference
(benchmark/reference/deepseek_v3.py) on seeded weights, float32."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from benchmark.models import deepseek_v3 as family
from benchmark.reference import deepseek_v3 as ref
from paddle_tpu import serving
from paddle_tpu.distributed.moe import DroplessMoELayer
from paddle_tpu.incubate.nn import paged_attention as pa
from paddle_tpu.models.deepseek_v3 import rope_interleave
from tests.test_deepseek_v3_reference import TINY, tiny_weights


def build(weights, cfg=TINY, **kw):
    model = family.build(cfg, training=False, **kw)
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


def test_forward_matches_reference(model, weights):
    ids = np.random.default_rng(1).integers(1, TINY["vocab_size"], (2, 29))
    got = model(P.to_tensor(ids.astype(np.int32))).numpy()
    want = np.asarray(ref.logits(TINY, weights, jnp.asarray(ids)))
    # float32 both sides; the program's products run at the backend's
    # default precision, the reference's at HIGHEST (logits ~0.3)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_placeholders_hold_nothing():
    model = family.build(TINY, training=False)
    assert sum(int(p._value.size) for p in model.parameters()) == 0


def test_model_initialises_itself():
    """Without a loader (``init_weights=True``, the default) the model
    draws its own weights and runs."""
    model = family.build(TINY, training=False, init_weights=True)
    model.eval()
    assert sum(int(p._value.size) for p in model.parameters()) > 0
    out = model(P.to_tensor(np.arange(12, dtype=np.int32).reshape(2, 6)))
    assert out.shape == [2, 6, TINY["vocab_size"]]
    assert np.isfinite(out.numpy()).all()


def test_interleaved_rotary_is_the_pairwise_rotation():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(1, 5, 2, 8)).astype(np.float32)
    pos = np.array([[0, 1, 7, 100, 3000]])
    got = np.asarray(rope_interleave(jnp.asarray(x), jnp.asarray(pos), 1e6))
    # pair j = (x[2j], x[2j+1]) turned by pos * theta^(-2j/d); the result
    # de-interleaved: first halves, then second halves
    ang = pos[0][:, None] * (1e6 ** (-np.arange(0, 8, 2) / 8))[None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    # the program's angles are float32 (an angle of 3000 rad carries
    # ~2e-4 of rounding), this check's float64
    np.testing.assert_allclose(got, want, atol=1e-3)
    np.testing.assert_allclose(got[:, :3], want[:, :3], atol=1e-5)
    np.testing.assert_allclose(
        got, np.asarray(ref.rope_interleave(jnp.asarray(x),
                                            jnp.asarray(pos[0]), 1e6)),
        atol=1e-6)


def _layer(weights, i, held=None, shared=True):
    w = ref.layer_weights(weights, i)
    layer = DroplessMoELayer(
        TINY["hidden_size"], TINY["moe_intermediate_size"],
        TINY["n_routed_experts"], TINY["num_experts_per_tok"],
        n_shared=TINY["n_shared_experts"],
        routed_scaling_factor=TINY["routed_scaling_factor"], held=held,
        shared=shared)
    first, count = held or (0, TINY["n_routed_experts"])
    layer.gate_weight._set_value(w["gate"])
    layer.gate_bias._set_value(w["gate_bias"])
    layer.w13._set_value(w["experts.w13"][first:first + count])
    layer.w2._set_value(w["experts.w2"][first:first + count])
    if layer.has_shared:
        layer.shared_w13._set_value(w["shared.w13"])
        layer.shared_w2._set_value(w["shared.w2"])
    return layer, w


def test_expert_layer_matches_reference(weights):
    layer, w = _layer(weights, 1)
    h = jax.random.normal(jax.random.PRNGKey(7), (3, 11, TINY["hidden_size"]))
    got = layer(P.to_tensor(np.asarray(h))).numpy()
    want = np.asarray(ref.expert_layer(TINY, w, h))
    np.testing.assert_allclose(got, want, atol=1e-5)
    counts = np.asarray(layer.last_counts._value)
    assert counts.sum() == 33 * TINY["num_experts_per_tok"]


def test_nothing_dropped_under_planted_imbalance(weights):
    """Every token to the same 3 experts: a capacity-bound layer would
    drop most of them; here each of the 3 runs all 40 tokens."""
    layer, w = _layer(weights, 1)
    bias = np.zeros(TINY["n_routed_experts"], np.float32)
    bias[[2, 5, 11]] = 10.0                  # the choice, not the weights
    layer.gate_bias._set_value(jnp.asarray(bias))
    w = dict(w, gate_bias=jnp.asarray(bias))
    h = jax.random.normal(jax.random.PRNGKey(8), (40, TINY["hidden_size"]))
    got = layer(P.to_tensor(np.asarray(h))).numpy()
    counts = np.asarray(layer.last_counts._value)
    assert counts[[2, 5, 11]].tolist() == [40, 40, 40]
    assert counts.sum() == 120
    np.testing.assert_allclose(
        got, np.asarray(ref.expert_layer(TINY, w, h)), atol=1e-5)


def test_shares_of_the_expert_layer_sum_to_the_whole(weights):
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 9, TINY["hidden_size"]))
    x = P.to_tensor(np.asarray(h))
    whole = _layer(weights, 2)[0](x).numpy()
    parts = sum(_layer(weights, 2, held=(4 * i, 4), shared=(i == 0))[0](x)
                .numpy() for i in range(4))
    np.testing.assert_allclose(parts, whole, atol=1e-5)
    np.testing.assert_allclose(
        whole, np.asarray(ref.expert_layer(
            TINY, ref.layer_weights(weights, 2), h)), atol=1e-5)


def test_absorbed_decode_equals_expanded_attention(weights):
    """latent_attend over cached rows [c | k_r] with the absorbed query
    gives the expanded form's attention output."""
    cfg = TINY
    w = ref.layer_weights(weights, 0)
    H, dn, dv, r = 4, cfg["qk_nope_head_dim"], cfg["v_head_dim"], 32
    dr = cfg["qk_rope_head_dim"]
    s = 21
    h = jax.random.normal(jax.random.PRNGKey(10), (1, s, cfg["hidden_size"]))
    pos = jnp.arange(s)
    rows = ref.latent_rows(cfg, w, h, pos)                    # [1, s, r+dr]
    q = (h @ w["q"]).reshape(1, s, H, dn + dr)
    q_r = ref.rope_interleave(q[..., dn:], pos, cfg["rope_theta"])
    kvb = w["kvb"].reshape(r, H, dn + dv)
    # expanded, last position
    kv = jnp.einsum("bsr,rhd->bshd", rows[..., :r], kvb)
    score = (jnp.einsum("hd,shd->hs", q[0, -1, :, :dn], kv[0, ..., :dn])
             + jnp.einsum("hd,sd->hs", q_r[0, -1], rows[0, :, r:]))
    p = jax.nn.softmax(score / np.sqrt(dn + dr), -1)
    want = jnp.einsum("hs,shd->hd", p, kv[0, ..., dn:])
    # absorbed, through a paged pool
    page = 8
    pool = jnp.zeros((5, page, pa.latent_pool_width(r + dr)))
    tables = jnp.asarray([[3, 1, 4]], jnp.int32)
    pool = pa.latent_prefill_append(rows[:, :-1], pool, tables,
                                    jnp.asarray([s - 1]), page)
    q_abs = jnp.concatenate([jnp.einsum(
        "hd,rhd->hr", q[0, -1, :, :dn], kvb[..., :dn]), q_r[0, -1]], -1)
    u, pool = pa.latent_decode_step(
        q_abs[None], rows[:, -1], pool, tables, jnp.asarray([s - 1]), r,
        page, 1.0 / np.sqrt(dn + dr))
    got = jnp.einsum("hr,rhd->hd", u[0], kvb[..., dn:])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # the pool holds the reference's rows, pad columns zero
    cached = np.asarray(pool)[[3, 1, 4]].reshape(-1, pool.shape[-1])[:s]
    np.testing.assert_allclose(cached[:, :r + dr], np.asarray(rows[0]),
                               atol=1e-6)
    assert not cached[:, r + dr:].any()


def _engine(model, **kw):
    cfg = dict(max_num_seqs=3, page_size=8, max_model_len=64,
               dtype=jnp.float32)
    cfg.update(kw)
    return serving.LLMEngine(model, serving.EngineConfig(**cfg))


class _LogitTap:
    """Keeps the logits every sampler call saw, by request and position
    (the engine compares logits nowhere else; a row whose last token is
    still on the device, ``ahead``, samples one position further)."""

    def __init__(self, engine):
        self.rows = {}
        inner = engine._sample

        def tapped(logits, reqs, width, carry=(), **kw):
            arr = np.asarray(logits)
            ahead = kw.get("ahead")
            for i, r in enumerate(reqs):
                if r is not None:
                    pos = r.total_len + (0 if ahead is None else ahead[i])
                    self.rows[(r.request_id, int(pos))] = arr[i]
            return inner(logits, reqs, width, carry, **kw)

        engine._sample = tapped


def test_engine_prefill_then_decode_matches_reference(model, weights):
    """Ragged lengths, slot reuse (5 requests through 3 slots) and an
    evict-and-replay (a pool too small for all): at every sampled
    position the engine's logits are the reference's full forward's."""
    rng = np.random.default_rng(3)
    engine = _engine(model, num_pages=6)       # 5 pages of 8 for 3 slots
    tap = _LogitTap(engine)
    prompts = [rng.integers(1, TINY["vocab_size"], n).tolist()
               for n in (5, 17, 9, 26, 12)]
    sps = [serving.SamplingParams(max_new_tokens=n, temperature=0.0)
           for n in (20, 7, 22, 6, 9)]
    results = engine.generate(prompts, sps)
    assert engine.metrics.requests_evicted >= 1          # a replay ran
    assert engine.metrics.moe_tokens_routed > 0
    assert engine._v_pools == [] and engine._pool.kind == "latent"
    checked = 0
    for k, (prompt, res) in enumerate(zip(prompts, results)):
        seq = prompt + list(res.output_token_ids)
        full = np.asarray(ref.logits(TINY, weights,
                                     jnp.asarray([seq])))[0]
        for j in range(len(res.output_token_ids)):
            got = tap.rows[(f"req-{k}", len(prompt) + j)]
            # float32; default-precision products against HIGHEST
            np.testing.assert_allclose(got, full[len(prompt) + j - 1],
                                       atol=3e-4)
            checked += 1
    assert checked == sum(sp.max_new_tokens for sp in sps)
    engine.shutdown()


def test_engine_spans_carry_expert_counters(model):
    from paddle_tpu.observability import spans
    engine = _engine(model)
    rec = spans.recorder()
    rec.clear()
    engine.generate([[3, 4, 5, 6]],
                    serving.SamplingParams(max_new_tokens=4,
                                           temperature=0.0))
    got = {r.name: r.attrs for r in rec.spans()
           if r.name in ("serving.decode", "serving.prefill",
                         "serving.experts")}
    layers = 2                                   # expert layers of TINY
    for name in ("serving.decode", "serving.prefill", "serving.experts"):
        a = got[name]
        assert 1 <= a["experts_hit"] <= layers * TINY["n_routed_experts"]
        assert a["expert_tokens_max"] >= 1
    snap = engine.metrics.snapshot()["moe"]
    assert snap["tokens_routed"] == engine.metrics.moe_tokens_routed > 0
    engine.shutdown()


@pytest.mark.parametrize("kernel", [False, True])
def test_marker_says_which_grouped_products_took_the_kernel(
        model, monkeypatch, kernel):
    """``grouped_kernel`` on the marker: the share of the program's grouped
    expert products that took the Pallas kernel — none on the CPU's
    path, all where the rule names the kernel (interpreted here); the
    tokens are the same either way."""
    from paddle_tpu.observability import spans
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    prompt, sp = [[3, 4, 5, 6]], serving.SamplingParams(max_new_tokens=4,
                                                        temperature=0.0)
    plain = _engine(model)
    want = plain.generate(prompt, sp)[0].output_token_ids
    plain.shutdown()
    if kernel:
        monkeypatch.setattr(gm, "pick_tiles",
                            lambda rows, groups, k, n, dtype: (16, n))
    engine = _engine(model)
    rec = spans.recorder()
    rec.clear()
    got = engine.generate(prompt, sp)[0].output_token_ids
    marks = [r.attrs["grouped_kernel"] for r in rec.spans()
             if r.name == "serving.experts"]
    assert marks and set(marks) == {1.0 if kernel else 0.0}
    assert got == want
    engine.shutdown()


@pytest.mark.parametrize("what", [{"kv_cache_dtype": "int8"},
                                  {"mesh": {"tp": 2}}],
                         ids=["kv_cache_dtype", "mesh"])
def test_latent_pool_refuses_by_name(model, what):
    with pytest.raises(ValueError, match="latent"):
        _engine(model, **what)


def test_latent_pages_hand_off_mid_request(model):
    """A request exported after five steps — its latent rows through the
    fleet's wire format — and imported into a second engine, on other
    page ids beside a request already running there, finishes with the
    tokens of the uninterrupted run."""
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
    assert 1 < len(first._requests[rid].output_token_ids) < 12
    state = wire.unpack_state(wire.pack_state(first.export_page_state(rid)))
    assert not first.has_unfinished()
    assert [sorted(blocks) for blocks in state["layers"]] \
        == [["rows"]] * TINY["num_hidden_layers"]
    second.add_request([7, 8, 9], serving.SamplingParams(
        max_new_tokens=30, temperature=0.0))
    second.step()
    moved = second.import_page_state(state)
    assert second._alloc.owned_pages(second._requests[moved].slot)[0] != 1
    while second.has_unfinished():
        second.step()
    assert second.finished_requests[moved].output_token_ids == want
    first.shutdown()
    second.shutdown()


def test_pool_accounting_follows_the_declaration(model):
    engine = _engine(model)
    cfg = engine.config
    width = pa.latent_pool_width(TINY["kv_lora_rank"]
                                 + TINY["qk_rope_head_dim"])
    per_layer = cfg.num_pages * cfg.page_size * width * 4
    assert engine.kv_pool_bytes == TINY["num_hidden_layers"] * per_layer
    assert engine.kv_bytes_per_token == TINY["num_hidden_layers"] * width * 4
    assert engine.hbm_budget_bytes == (engine.params_bytes
                                       + 2 * engine.kv_pool_bytes + (64 << 20))
    assert engine.attention_path == "latent/xla+next_token/1"
    engine.shutdown()
