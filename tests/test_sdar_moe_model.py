"""models/sdar_moe.py against the plain reference
(benchmark/reference/sdar_moe.py) on seeded weights, float32: QK-norm,
rotate-half rotary, the block-causal mask, the router's published order,
and the reference's own training layout against the naive per-pass form."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from benchmark.models import sdar_moe as family
from benchmark.reference import common as refc
from benchmark.reference import sdar_moe as ref
from paddle_tpu.distributed.moe import softmax_topk_route
from paddle_tpu.incubate.nn.paged_attention import grouped_causal_attention
from paddle_tpu.models.sdar_moe import SdarMoeConfig, rope_rotate_half

# 4 / 2 heads of 16 on a hidden size of 32 (H x d_h = 64 != hidden), 8
# experts top 2, blocks of 4; the mask token is the vocabulary's last row
TINY = dict(vocab_size=97, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
            norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e6,
            max_position_embeddings=128, block_length=4, mask_token_id=96,
            initializer_range=0.3)
M = TINY["mask_token_id"]


def tiny_weights(seed=7, cfg=TINY):
    return refc.make_weights(ref.weight_spec(cfg), seed)


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


@pytest.mark.parametrize("length", [22, 24, 3])
def test_forward_matches_reference(model, weights, length):
    """Whole forward, lengths that are and are not a multiple of the block
    (and one shorter than a block)."""
    ids = np.random.default_rng(1).integers(1, M, (2, length))
    got = model(P.to_tensor(ids.astype(np.int32))).numpy()
    want = np.asarray(ref.logits(TINY, weights, jnp.asarray(ids)))
    # float32 both sides; logits ~7
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_mask_is_causal_over_blocks(model):
    """A position sees every position of its own block and no later
    block."""
    ids = np.random.default_rng(2).integers(1, M, (1, 12)).astype(np.int32)
    base = model(P.to_tensor(ids)).numpy()
    later_in_block = ids.copy()
    later_in_block[0, 7] = (ids[0, 7] + 1) % M        # block 1's last
    moved = model(P.to_tensor(later_in_block)).numpy()
    assert np.abs(moved[0, 4] - base[0, 4]).max() > 1e-3   # block 1's first
    np.testing.assert_array_equal(moved[0, :4], base[0, :4])   # block 0
    later_block = ids.copy()
    later_block[0, 8] = (ids[0, 8] + 1) % M           # block 2's first
    np.testing.assert_array_equal(
        model(P.to_tensor(later_block)).numpy()[0, :8], base[0, :8])


def test_block_of_one_is_the_causal_mask():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 9, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 9, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 9, 2, 8)), jnp.float32)
    one = grouped_causal_attention(q, k, v, 0.3)
    np.testing.assert_array_equal(
        np.asarray(one), np.asarray(grouped_causal_attention(q, k, v, 0.3, 1)))
    # blocks of 3: position 3 sees 3..5 and 0..2; row 5 is plain causal
    three = np.asarray(grouped_causal_attention(q, k, v, 0.3, block=3))
    np.testing.assert_allclose(three[:, 5], np.asarray(one)[:, 5], atol=1e-6)
    np.testing.assert_allclose(three[:, 3], np.asarray(
        grouped_causal_attention(q[:, :6], k[:, :6], v[:, :6], 0.3,
                                 block=6))[:, 3], atol=1e-6)


def test_rotate_half_rotary_is_the_pairwise_rotation():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(1, 5, 2, 8)).astype(np.float32)
    pos = np.array([[0, 1, 7, 100, 3000]])
    got = np.asarray(rope_rotate_half(jnp.asarray(x), jnp.asarray(pos), 1e6))
    # dimension j pairs with j + d/2, turned by pos * theta^(-2j/d)
    ang = pos[0][:, None] * (1e6 ** (-np.arange(0, 8, 2) / 8))[None, :]
    a, b = x[..., :4], x[..., 4:]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    np.testing.assert_allclose(got, want, atol=1e-3)   # float32 angles
    np.testing.assert_allclose(got[:, :3], want[:, :3], atol=1e-5)
    np.testing.assert_allclose(
        got, np.asarray(ref.rotate_half(jnp.asarray(x), jnp.asarray(pos[0]),
                                        1e6)), atol=1e-6)


def test_qk_norm_comes_before_the_rotation(weights):
    """The reference's attention with a head-norm weight that is not 1
    differs from rotating first: the program follows the reference."""
    w = dict(weights)
    rng = np.random.default_rng(5)
    for i in range(TINY["num_hidden_layers"]):
        w[f"l{i}.qn"] = jnp.asarray(rng.uniform(0.5, 2.0, 16), jnp.float32)
        w[f"l{i}.kn"] = jnp.asarray(rng.uniform(0.5, 2.0, 16), jnp.float32)
    ids = rng.integers(1, M, (1, 10))
    got = build(w)(P.to_tensor(ids.astype(np.int32))).numpy()
    want = np.asarray(ref.logits(TINY, w, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=2e-4)
    plain = np.asarray(ref.logits(TINY, weights, jnp.asarray(ids)))
    assert np.abs(want - plain).max() > 1e-2


def test_softmax_over_the_top_is_the_published_order(weights):
    """softmax over all experts, top k, divided by their sum (the
    reference, as published) = top k of the logits, softmax over them (the
    program's router)."""
    h = jnp.asarray(np.random.default_rng(6).normal(size=(40, 32)),
                    jnp.float32)
    w = ref.layer_weights(weights, 0)
    want_w, want_i = ref.route(TINY, w, h)
    got_w, got_i = softmax_topk_route(h, w["gate"], 2)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_w).sum(-1), 1.0, atol=1e-6)


@pytest.mark.parametrize("first", [0, 8])
def test_denoise_logits_is_the_naive_pass(weights, first):
    """The training layout (clean sequence + noisy copy, one forward) gives
    at every noisy position what the naive pass gives: one forward of the
    clean prefix followed by that block's noisy state alone."""
    rng = np.random.default_rng(7)
    clean = rng.integers(1, M, (1, 20))
    noisy = clean[:, first:].copy()
    noisy[0, rng.random(noisy.shape[1]) < 0.6] = M
    got = np.asarray(ref.denoise_logits(
        TINY, weights, jnp.asarray(clean), jnp.asarray(noisy), first))
    for b0 in range(first, 20, 4):
        ids = np.concatenate([clean[:, :b0], noisy[:, b0 - first:
                                                   b0 - first + 4]], 1)
        want = np.asarray(ref.logits(TINY, weights, jnp.asarray(ids)))
        np.testing.assert_allclose(got[0, b0 - first:b0 - first + 4],
                                   want[0, b0:], atol=2e-4)


def test_denoise_logits_takes_a_traced_first_and_padding(weights):
    """Jitted with `first` traced and both parts padded: the rows of the
    real blocks do not move."""
    rng = np.random.default_rng(8)
    clean = rng.integers(1, M, (1, 16))
    noisy = np.full((1, 8), M)
    want = np.asarray(ref.denoise_logits(
        TINY, weights, jnp.asarray(clean), jnp.asarray(noisy), 8))
    pad_clean = np.concatenate([clean, np.zeros((1, 8), np.int64)], 1)
    pad_noisy = np.concatenate([noisy, np.zeros((1, 4), np.int64)], 1)
    got = np.asarray(jax.jit(
        lambda c, n, f: ref.denoise_logits(TINY, weights, c, n, f))(
            jnp.asarray(pad_clean), jnp.asarray(pad_noisy), jnp.int32(8)))
    np.testing.assert_allclose(got[:, :8], want, atol=2e-4)


def test_placeholders_hold_nothing():
    model = family.build(TINY, training=False)
    assert sum(int(p._value.size) for p in model.parameters()) == 0


def test_model_initialises_itself_and_declares_its_kinds():
    model = family.build(TINY, training=False, init_weights=True)
    model.eval()
    out = model(P.to_tensor(np.arange(12, dtype=np.int32).reshape(2, 6)))
    assert out.shape == [2, 6, TINY["vocab_size"]]
    assert np.isfinite(out.numpy()).all()
    assert model.generation_spec() == {
        "kind": "block_diffusion", "block_length": 4, "mask_token_id": M}
    assert model.kv_cache_spec() == {
        "kind": "kv", "num_heads": 2, "head_dim": 16, "query_heads": 4,
        "causal_block": 4}
    assert model.num_expert_layers == 2


@pytest.mark.parametrize("key, value", [
    ("rope_scaling", {"type": "yarn"}), ("use_sliding_window", True),
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("attention_bias", True)])
def test_from_published_refuses_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        SdarMoeConfig.from_published(dict(TINY, **{key: value}))
