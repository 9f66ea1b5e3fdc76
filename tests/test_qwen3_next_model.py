"""models/qwen3_next.py — Gated DeltaNet layers beside a gated attention
layer, softmax-routed experts with a sigmoid-gated shared expert — against
the plain reference (benchmark/reference/qwen3_next.py, which runs the delta
rule token by token) on seeded weights, float32, and driven through
serving.LLMEngine: pages for the full layer, a per-slot state for the delta
rule's layers.  The chunk is 8 positions and the prompts several chunks
long, so that every comparison crosses a chunk's edge."""
import hashlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from benchmark.models import qwen3_next as family
from benchmark.reference import common as refc
from benchmark.reference import qwen3_next as ref
from paddle_tpu import serving
from paddle_tpu.distributed.moe import DroplessMoELayer
from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                          gated_delta_chunked,
                                          gated_delta_step)
from tests.test_deepseek_v3_model import _LogitTap

CHUNK = 8
TINY = {
    "family": "qwen3_next", "hidden_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 1e7,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "num_experts": 8,
    "num_experts_per_tok": 3, "vocab_size": 300,
    "full_attention_interval": 4, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 128, "rope_scaling": None,
    "tie_word_embeddings": False, "norm_topk_prob": True,
    "mlp_only_layers": [], "decoder_sparse_step": 1,
    "use_sliding_window": False, "initializer_range": 0.1,
    "conv_kernel_std": 0.29, "delta_chunk": CHUNK,
}
# float32 everywhere: the program and the reference differ by the order of
# float32 sums alone (the chunked form against the token recurrence, XLA
# attention, ragged_dot experts); a logit is ~2 in size
ATOL = 2e-4


def tiny_weights(seed=5, cfg=TINY):
    w = refc.make_weights(ref.weight_spec(cfg), seed, jnp.float32)
    # zero-centred norms away from 0 and the delta rule's away from 1, so
    # that a dropped one, or `w` for `1 + w`, would show
    key = jax.random.PRNGKey(seed + 1)
    for i, name in enumerate(sorted(w)):
        if name.endswith(("ln1", "ln2", "norm", "q_norm", "k_norm")):
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


# ------------------------------------------------------ the delta rule
def _token_by_token(q, k, v, g, beta, S):
    """The definition in float64, one position at a time."""
    q, k, v, g, beta, S = (np.asarray(x, np.float64)
                           for x in (q, k, v, g, beta, S))
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        S = S * np.exp(g[:, t])[..., None, None]
        kv = np.einsum("bhkv,bhk->bhv", S, k[:, t])
        delta = beta[:, t][..., None] * (v[:, t] - kv)
        S = S + k[:, t][..., :, None] * delta[..., None, :]
        out[:, t] = np.einsum("bhkv,bhk->bhv", S, q[:, t])
    return out, S


def _inputs(b, s, H=3, dk=8, dv=6, seed=0):
    rng = np.random.default_rng(seed)
    q, k = (rng.standard_normal((b, s, H, dk)) for _ in range(2))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    v = rng.standard_normal((b, s, H, dv))
    # decays from ~0.95 down to ~0.05 a step, writes of every strength
    g = -rng.uniform(0.05, 3.0, (b, s, H))
    beta = rng.uniform(0.0, 1.0, (b, s, H))
    S0 = rng.standard_normal((b, H, dk, dv))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, S0)]


@pytest.mark.parametrize("chunk", [1, 3, 8, 64])
def test_chunked_rule_equals_the_token_recurrence(chunk):
    """Several chunk sizes (one that does not divide the prompt, one longer
    than it), ragged ``lens`` (a prompt shorter than a chunk among them)
    and a nonzero state to start from: every real position's output and
    the state after each row's last real position are the recurrence's.
    Float32 against float64: 1e-4 covers the ~50 products a position's
    output sums at float32 rounding."""
    q, k, v, g, beta, S0 = _inputs(3, 21)
    lens = np.array([21, 13, 2], np.int32)
    o, S = gated_delta_chunked(q, k, v, g, beta, chunk,
                               lens=jnp.asarray(lens), state=S0)
    for i, n in enumerate(lens):
        want_o, want_S = _token_by_token(
            *(x[i:i + 1, :n] for x in (q, k, v, g, beta)), S0[i:i + 1])
        np.testing.assert_allclose(np.asarray(o[i:i + 1, :n]), want_o,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(S[i:i + 1]), want_S,
                                   atol=1e-4)


def test_one_token_step_is_the_recurrence():
    q, k, v, g, beta, S0 = _inputs(2, 1, seed=1)
    o, S = gated_delta_step(S0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                            beta[:, 0])
    want_o, want_S = _token_by_token(q, k, v, g, beta, S0)
    np.testing.assert_allclose(np.asarray(o), want_o[:, 0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), want_S, atol=1e-5)


def test_a_chunk_that_forgets_its_decay_is_seen():
    """The planted fault of ``benchmark/tools/plant_delta_fault.py`` (the
    state handed to the next chunk without the chunk's decay) moves the
    outputs of every later chunk far past the tolerance."""
    import importlib.util
    import os
    from paddle_tpu.models import qwen3_next
    from paddle_tpu.serving import kv_pool
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "tools",
        "plant_delta_fault.py")
    spec = importlib.util.spec_from_file_location("plant_delta_fault", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    sound = qwen3_next.gated_delta_chunked
    init = kv_pool.LayeredPool.__init__
    try:
        tool.plant(qwen3_next, kv_pool)
        q, k, v, g, beta, S0 = _inputs(1, 24, seed=2)
        bad, _ = qwen3_next.gated_delta_chunked(q, k, v, g, beta, 8)
    finally:
        qwen3_next.gated_delta_chunked = sound
        kv_pool.LayeredPool.__init__ = init
    good, _ = sound(q, k, v, g, beta, 8)
    np.testing.assert_allclose(np.asarray(bad[:, :8]),
                               np.asarray(good[:, :8]), atol=1e-6)
    assert float(jnp.max(jnp.abs(bad[:, 8:] - good[:, 8:]))) > 1e-2


# --------------------------------------------------------- whole model
def test_forward_matches_reference(model, weights):
    """40 positions, five chunks: the whole forward."""
    ids = _ids(40)
    got = np.asarray(model(jnp.asarray(ids)))
    want = np.asarray(ref.logits(TINY, weights, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("fault", ["norm_not_centred", "no_output_gate",
                                   "rotary_everywhere"])
def test_reference_sees_a_changed_mechanism(model, weights, monkeypatch,
                                            fault):
    """The comparison tells the mechanism apart: a reference whose norms
    multiply by ``w`` for ``1 + w``, whose attention drops the sigmoid
    output gate, or whose rotary covers the whole head, misses the
    program's logits by far more than the tolerance."""
    ids = _ids(40)
    got = np.asarray(model(jnp.asarray(ids)))
    cfg = TINY
    if fault == "norm_not_centred":
        monkeypatch.setattr(ref, "centred_norm", lambda x, w, eps: (
            x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * w))
    elif fault == "no_output_gate":
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: jnp.ones_like(x))
    else:
        cfg = dict(TINY, partial_rotary_factor=1.0)
    want = np.asarray(ref.logits(cfg, weights, jnp.asarray(ids)))
    assert np.abs(got - want).max() > 50 * ATOL


def test_norms_gate_and_partial_rotary_follow_the_equations(model):
    """A zero-centred norm is ``rms(x) (1 + w)``; rotary turns the first
    ``partial_rotary_factor d_h`` dimensions of a head and leaves the rest
    as they are; the attention's output is ``attn * sigmoid(gate)``."""
    from paddle_tpu.models.qwen3_next import _centred_rms
    rng = np.random.default_rng(7)
    x = rng.standard_normal((5, 16)).astype(np.float32)
    w = rng.standard_normal(16).astype(np.float32)
    want = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) * (1 + w)
    np.testing.assert_allclose(np.asarray(_centred_rms(x, w, 1e-6)), want,
                               rtol=1e-5)
    attn = model.layers[3].mixer
    assert attn._cfg.rotary_dim == 4
    h = jnp.asarray(rng.standard_normal((1, 6, 2, 16)), jnp.float32)
    turned = np.asarray(attn._rotate(h, jnp.arange(6)[None]))
    np.testing.assert_array_equal(turned[..., 4:], np.asarray(h)[..., 4:])
    assert np.abs(turned[:, 1:, :, :4] - np.asarray(h)[:, 1:, :, :4]).max() \
        > 1e-3
    np.testing.assert_allclose(turned[:, 0], np.asarray(h)[:, 0])   # pos 0
    # the gate: with its columns zeroed every gate reads sigmoid(0) = 1/2,
    # so the layer gives half of what it gives with the sigmoid read as 1
    wq = np.asarray(attn.q._value)
    zeroed = wq.reshape(32, 4, 2, 16).copy()
    zeroed[:, :, 1] = 0.0
    hidden = P.to_tensor(rng.standard_normal((1, 6, 32)).astype(np.float32))
    pos = P.to_tensor(np.arange(6, dtype=np.int32)[None])
    try:
        attn.q._set_value(jnp.asarray(zeroed.reshape(32, 128)))
        half = np.asarray(attn(hidden, pos)._value)
        real = jax.nn.sigmoid
        jax.nn.sigmoid = jnp.ones_like
        try:
            whole = np.asarray(attn(hidden, pos)._value)
        finally:
            jax.nn.sigmoid = real
    finally:
        attn.q._set_value(jnp.asarray(wq))
    np.testing.assert_allclose(half, 0.5 * whole, atol=1e-6)
    assert np.abs(whole).max() > 1e-2


def _engine(model, **kw):
    cfg = dict(max_num_seqs=3, page_size=8, max_model_len=64,
               dtype=jnp.float32)
    cfg.update(kw)
    return serving.LLMEngine(model, serving.EngineConfig(**cfg))


def _greedy(n):
    return serving.SamplingParams(max_new_tokens=n, temperature=0.0)


def test_engine_prefill_then_decode_matches_reference(model, weights):
    """Ragged lengths over several chunks, a prompt shorter than a chunk,
    slot reuse (5 requests through 3 slots: a slot's state is overwritten
    by the next prefill) and an evict-and-replay (the full layer's pages
    too few for all): at every sampled position the engine's logits are
    the reference's full forward's."""
    rng = np.random.default_rng(3)
    engine = _engine(model, num_pages=9)     # 8 pages of 8 for 3 slots
    assert engine._pool.kinds == ["state", "state", "state", "kv"]
    assert not engine._run_ahead             # a state by slot: synchronous
    tap = _LogitTap(engine)
    prompts = [rng.integers(1, TINY["vocab_size"], n).tolist()
               for n in (21, 17, 30, 3, 12)]
    sps = [_greedy(n) for n in (20, 7, 22, 25, 9)]
    results = engine.generate(prompts, sps)
    assert engine.metrics.requests_evicted >= 1          # a replay ran
    checked = 0
    for k, (prompt, res) in enumerate(zip(prompts, results)):
        seq = prompt + list(res.output_token_ids)
        full = np.asarray(ref.logits(TINY, weights, jnp.asarray([seq])))[0]
        for j in range(len(res.output_token_ids)):
            got = tap.rows[(f"req-{k}", len(prompt) + j)]
            np.testing.assert_allclose(got, full[len(prompt) + j - 1],
                                       atol=ATOL)
            checked += 1
    assert checked == sum(sp.max_new_tokens for sp in sps)
    engine.shutdown()


def test_pool_spans_and_counters(model):
    """The pool of three state layers beside one layer of pages; the spans
    name the recurrence and count the chunks run; the snapshot gives the
    bytes of one slot's state in one layer."""
    from paddle_tpu.observability import spans
    engine = _engine(model)
    pool = engine._pool
    assert pool.state_layers == 3 and pool.state.recurrence == "gated_delta"
    C = 2 * 2 * 8 + 4 * 8                   # [q | k | v] channels
    slot_layer = 3 * C * 4 + 4 * 8 * 8 * 4  # conv rows (f32) + S (f32)
    assert pool.state.slot_nbytes == slot_layer
    assert pool.state_nbytes == 3 * 3 * slot_layer
    rec = spans.recorder()
    rec.clear()
    engine.generate([_ids(11)[0].tolist()], _greedy(4))
    prefill = next(r.attrs for r in rec.spans()
                   if r.name == "serving.prefill")
    assert prefill["recurrence"] == "gated_delta"
    assert prefill["scan_tokens"] == 11
    bucket = prefill["bucket"]
    assert prefill["scan_chunks"] == 3 * -(-bucket // CHUNK)
    decodes = [r.attrs for r in rec.spans() if r.name == "serving.decode"]
    assert decodes and all(d["recurrence"] == "gated_delta"
                           and d["state_rows"] == 3 for d in decodes)
    snap = engine.metrics.snapshot()["state"]
    assert snap["slot_layer_bytes"] == slot_layer
    assert snap["recurrence"] == "gated_delta"
    assert snap["pool_bytes"] == pool.state_nbytes
    engine.shutdown()


# ----------------------------------------------------------- the experts
def _layer(weights, i, held=None, shared=True, cfg=TINY):
    w = ref.layer_weights(weights, i)
    E = ref.router_width(cfg)
    layer = DroplessMoELayer(
        cfg["hidden_size"], cfg["moe_intermediate_size"], E,
        cfg["num_experts_per_tok"], route="softmax", held=held, n_shared=1,
        shared=shared, shared_gate=True)
    first, count = held or (0, E)
    layer.gate_weight._set_value(w["gate"])
    layer.w13._set_value(w["experts.w13"][first:first + count])
    layer.w2._set_value(w["experts.w2"][first:first + count])
    if layer.has_shared:
        layer.shared_w13._set_value(w["shared.w13"])
        layer.shared_w2._set_value(w["shared.w2"])
        layer.shared_gate_weight._set_value(w["shared_gate"])
    return layer, w


def test_shares_of_the_expert_layer_sum_to_the_whole(weights):
    """``held=(0, E/2)`` + ``held=(E/2, E/2)``, the gated shared expert
    counted once (the first holder's) = the uncut reference's layer; each
    share is the reference's share.  Float32: 1e-5."""
    h = jax.random.normal(jax.random.PRNGKey(13), (2, 9, 32))
    x = P.to_tensor(np.asarray(h))
    w = ref.layer_weights(weights, 2)
    parts = [_layer(weights, 2, held=(4 * i, 4), shared=(i == 0))[0](x)
             .numpy() for i in range(2)]
    flat = h.reshape(-1, 32)
    np.testing.assert_allclose(
        sum(parts).reshape(-1, 32),
        np.asarray(ref.expert_layer(TINY, w, flat)), atol=1e-5)
    for i, part in enumerate(parts):
        np.testing.assert_allclose(part.reshape(-1, 32), np.asarray(
            ref.expert_layer(TINY, w, flat, held=(4 * i, 4),
                             shared=(i == 0))), atol=1e-5)
    # the gate is a token's own: without it the shared part differs
    layer, _ = _layer(weights, 2)
    layer.shared_gate = False
    ungated = layer(x).numpy()
    assert np.abs(ungated - sum(parts)).max() > 1e-3


# Program digests (``LLMEngine.audit_programs()``: every program's jaxpr,
# traced at the cell's rehearsal sizes, hashed as
# benchmark/tools/program_digests.py hashes them) of the four expert cells
# as they were before the shared expert's gate existed: the option off
# leaves every one of those programs as it was.
UNGATED_PROGRAMS = {
    "granite4h_serve_chat": "4a098cf888f32a5a",
    "kanana2_serve_reasoning": "584766d41c4c95b2",
    "sdar30b_serve_chat": "766036eb94e26bf4",
    "smallthinker21b_serve_longdoc": "310aab38d2c05d1c",
}


def program_digest(cell):
    from benchmark import run
    _m, ctx, runner = run.open_cell(run.parse_args(
        ["--workload", cell, "--seed", "1", "--seconds", "1",
         "--rehearse-cpu"]))
    _model, engine = runner.build(ctx)
    found = {name: hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
             for name, jaxpr in sorted(engine.audit_programs().items())}
    return hashlib.sha256(json.dumps(found, sort_keys=True)
                          .encode()).hexdigest()[:16]


@pytest.mark.parametrize("cell", sorted(UNGATED_PROGRAMS))
def test_without_the_gate_the_expert_cells_programs_are_unchanged(cell):
    assert program_digest(cell) == UNGATED_PROGRAMS[cell]


# ------------------------------------------------------------ refusals
@pytest.mark.parametrize("change,error", [
    ({"mlp_only_layers": [1]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
])
def test_what_is_not_built_is_refused(change, error):
    with pytest.raises(NotImplementedError, match=error):
        Qwen3NextConfig.from_published(dict(TINY, **change))


def test_published_interval_names_the_layers():
    c = Qwen3NextConfig.from_published(dict(TINY, num_hidden_layers=8))
    assert c.layer_types == (["linear_attention"] * 3
                             + ["full_attention"]) * 2
    assert (c.n_routed_experts, c.num_experts_per_tok, c.chunk,
            c.rotary_dim) == (8, 3, CHUNK, 4)
    held = Qwen3NextConfig.from_published(dict(
        TINY, num_experts=4, held=[0, 4],
        published={"num_experts": 8}))
    assert held.n_routed_experts == 8 and held.held == (0, 4)
