"""models/granitemoehybrid.py (the Mamba-2 mixer's chunked scan and
one-token update, grouped-query attention without positions, the four
multipliers) and distributed/moe.py's softmax router against the plain
reference (benchmark/reference/granitemoehybrid.py) on seeded weights,
float32 — and the reference against the published code."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from benchmark.models import granitemoehybrid as family
from benchmark.reference import common as refc
from benchmark.reference import granitemoehybrid as ref
from paddle_tpu.distributed.moe import DroplessMoELayer, softmax_topk_route
from paddle_tpu.models.granitemoehybrid import (GraniteMoeHybridConfig,
                                                Mamba2Mixer)

TINY = {
    "family": "granitemoehybrid", "hidden_size": 64, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 8, "intermediate_size": 24,
    "shared_intermediate_size": 48, "num_local_experts": 8,
    "num_experts_per_tok": 3, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.2,
    "logits_scaling": 2, "rms_norm_eps": 1e-5,
    "position_embedding_type": "nope", "tie_word_embeddings": True,
    "max_position_embeddings": 128, "vocab_size": 300,
    "initializer_range": 0.1, "conv_kernel_std": 0.29,
    # the tied table a tenth of the rest: a position's own token must not
    # win every argmax (the configuration's `assumed` has the arithmetic)
    "embedding_std": 0.01,
}
# this chip's share of TINY: experts 0-3 of a router 8 wide
HALF = dict(TINY, num_local_experts=4, held=[0, 4],
            published={"num_hidden_layers": 4, "num_local_experts": 8})


def tiny_weights(seed=3, dtype=jnp.float32, cfg=TINY):
    w = refc.make_weights(ref.weight_spec(cfg), seed, dtype)
    # norms, D and the convolution's bias away from their constants, so
    # that a dropped one would show
    key = jax.random.PRNGKey(seed + 1)
    for i, name in enumerate(sorted(w)):
        if name.endswith(("ln1", "ln2", "norm", ".D", "conv_b")):
            w[name] = (w[name] + 0.1 * jax.random.normal(
                jax.random.fold_in(key, i), w[name].shape)).astype(dtype)
    return w


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


# ------------------------------------------- the reference is the model
def _to_hf(cfg, w):
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    if not hasattr(tf, "GraniteMoeHybridForCausalLM"):
        pytest.skip("transformers has no granitemoehybrid")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "shared_intermediate_size", "num_hidden_layers", "layer_types",
            "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_expand",
            "mamba_n_groups", "mamba_chunk_size", "num_local_experts",
            "num_experts_per_tok", "embedding_multiplier",
            "residual_multiplier", "attention_multiplier", "logits_scaling",
            "position_embedding_type", "tie_word_embeddings", "rms_norm_eps")
    model = tf.GraniteMoeHybridForCausalLM(tf.GraniteMoeHybridConfig(
        **{k: cfg[k] for k in keys}, attn_implementation="eager",
        max_position_embeddings=cfg["max_position_embeddings"])
    ).float().eval()

    def t(x):                      # ours are [in, out]; torch's [out, in]
        return torch.tensor(np.asarray(x, np.float32).T.copy())

    def v(x):
        return torch.tensor(np.asarray(x, np.float32))

    sd = {"model.embed_tokens.weight": v(w["embed"]),
          "lm_head.weight": v(w["embed"]), "model.norm.weight": v(w["norm"])}
    for i in range(cfg["num_hidden_layers"]):
        q = f"model.layers.{i}."
        lw = ref.layer_weights(w, i)        # A_log, dt_bias as they stand
        sd.update({
            q + "input_layernorm.weight": v(lw["ln1"]),
            q + "post_attention_layernorm.weight": v(lw["ln2"]),
            q + "block_sparse_moe.router.layer.weight": t(lw["gate"]),
            q + "block_sparse_moe.input_linear.weight": torch.tensor(
                np.asarray(lw["experts.w13"]).transpose(0, 2, 1).copy()),
            q + "block_sparse_moe.output_linear.weight": torch.tensor(
                np.asarray(lw["experts.w2"]).transpose(0, 2, 1).copy()),
            q + "shared_mlp.input_linear.weight": t(lw["shared.w13"]),
            q + "shared_mlp.output_linear.weight": t(lw["shared.w2"])})
        if ref.is_mamba(cfg, i):
            sd.update({
                q + "mamba.in_proj.weight": t(lw["in_proj"]),
                q + "mamba.conv1d.weight": t(lw["conv_w"])[:, None, :],
                q + "mamba.conv1d.bias": v(lw["conv_b"]),
                q + "mamba.dt_bias": v(lw["dt_bias"]),
                q + "mamba.A_log": v(lw["A_log"]), q + "mamba.D": v(lw["D"]),
                q + "mamba.norm.weight": v(lw["mixer_norm"]),
                q + "mamba.out_proj.weight": t(lw["out_proj"])})
        else:
            sd.update({q + f"self_attn.{n}_proj.weight": t(lw[n])
                       for n in "qkvo"})
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and not missing, (missing, unexpected)
    return model, torch


def test_reference_matches_transformers(weights):
    """The same seeded weights in ``transformers``'
    ``GraniteMoeHybridForCausalLM`` (eager attention, the torch path of the
    mixer, float32) give the reference's logits."""
    hf, torch = _to_hf(TINY, weights)
    ids = np.random.default_rng(0).integers(1, TINY["vocab_size"], (2, 27))
    with torch.no_grad():
        want = hf(torch.tensor(ids)).logits.numpy()
    got = np.asarray(ref.logits(TINY, weights, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_assumed_leaves_lie_in_the_familys_ranges(weights):
    lw = ref.layer_weights(weights, 0)
    A = np.exp(np.asarray(lw["A_log"]))
    dt = np.log1p(np.exp(np.asarray(lw["dt_bias"])))
    assert (A >= 1).all() and (A <= 16).all() and A.std() > 1
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 1e-1 * 1.001).all()
    # and every other leaf is itself
    assert lw["in_proj"] is weights["l0.in_proj"]


def test_program_holds_the_assumed_leaves(model, weights):
    params = dict(model.named_parameters())
    lw = ref.layer_weights(weights, 1)
    for name in ("A_log", "dt_bias"):
        np.testing.assert_array_equal(
            np.asarray(params[f"layers.1.mixer.{name}"]._value),
            np.asarray(lw[name]))


# ------------------------------------------------------------- the mixer
def _mixer(weights, i=0):
    config = GraniteMoeHybridConfig.from_published(
        TINY, initializer_range=TINY["initializer_range"])
    mixer = Mamba2Mixer(config)
    lw = ref.layer_weights(weights, i)
    leaves = [lw[k] for k in ("in_proj", "conv_w", "conv_b", "dt_bias",
                              "A_log", "D", "mixer_norm", "out_proj")]
    return mixer, lw, leaves


@pytest.mark.parametrize("length", [3, 8, 21, 29, 32])
def test_chunked_scan_is_the_recurrence(weights, length):
    """Lengths under, at and across the chunk (8), a multiple of it or
    not: the chunked scan's output AND the state it ends in are the
    reference's recurrence over time; so is the convolution's window."""
    mixer, lw, leaves = _mixer(weights)
    h = jax.random.normal(jax.random.PRNGKey(length), (2, length, 64))
    out, window, S = mixer._prompt(None, h, *leaves)
    want, S_ref, window_ref = ref.mixer(TINY, lw, h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(window), np.asarray(window_ref),
                               atol=1e-6)


@pytest.mark.parametrize("bucket", [16, 32, 64])
def test_padding_does_not_reach_the_state(weights, bucket):
    """One prompt of 13 tokens padded to three buckets (random rows past
    its end): the same state, the same window, the same real outputs."""
    mixer, lw, leaves = _mixer(weights, 1)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 64))
    lens = jnp.asarray([13], jnp.int32)
    out, window, S = mixer._prompt(lens, h[:, :bucket], *leaves)
    want, S_ref, window_ref = ref.mixer(TINY, lw, h[:, :13])
    np.testing.assert_allclose(np.asarray(out[:, :13]), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(window), np.asarray(window_ref),
                               atol=1e-6)


def test_one_token_update_continues_the_scan(weights):
    """The scan over 11 tokens, then 9 one-token updates from the state
    and window it left: the reference's recurrence over all 20."""
    mixer, lw, leaves = _mixer(weights, 3)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 20, 64))
    want, S_ref, window_ref = ref.mixer(TINY, lw, h)
    _, window, S = mixer._prompt(None, h[:, :11], *leaves)
    for t in range(11, 20):
        out, window, S = mixer._token(window, S, h[:, t:t + 1], *leaves)
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(want[:, t]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(window), np.asarray(window_ref),
                               atol=1e-6)


def test_reference_state_follows_length(weights):
    _, lw, _ = _mixer(weights)
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 24, 64))
    _, S, window = ref.mixer(TINY, lw, h, length=10)
    _, S10, window10 = ref.mixer(TINY, lw, h[:, :10])
    np.testing.assert_allclose(np.asarray(S), np.asarray(S10), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(window), np.asarray(window10))
    # and a state rounded to bfloat16 after every step is another state
    _, S_low, _ = ref.mixer(TINY, lw, h[:, :10], "f32/state_bf16")
    assert float(jnp.max(jnp.abs(S_low - S10))) > 1e-5


# ----------------------------------------------------------- the experts
def _layer(weights, i, cfg=TINY, held=None, shared=True):
    w = ref.layer_weights(weights, i)
    E = ref.router_width(cfg)
    layer = DroplessMoELayer(
        cfg["hidden_size"], cfg["intermediate_size"], E,
        cfg["num_experts_per_tok"], route="softmax", held=held,
        n_shared=cfg["shared_intermediate_size"] // cfg["intermediate_size"],
        shared=shared)
    first, count = held or (0, E)
    assert not hasattr(layer, "gate_bias")
    layer.gate_weight._set_value(w["gate"])
    layer.w13._set_value(w["experts.w13"][first:first + count])
    layer.w2._set_value(w["experts.w2"][first:first + count])
    if layer.has_shared:
        layer.shared_w13._set_value(w["shared.w13"])
        layer.shared_w2._set_value(w["shared.w2"])
    return layer, w


def test_softmax_router_matches_reference(weights):
    w = ref.layer_weights(weights, 0)
    h = jax.random.normal(jax.random.PRNGKey(11), (37, 64))
    got_w, got_i = softmax_topk_route(h, w["gate"], 3)
    want_w, want_i = ref.route(TINY, w, h)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_w).sum(-1), 1.0, atol=1e-6)


def test_expert_layer_matches_reference(weights):
    layer, w = _layer(weights, 1)
    h = jax.random.normal(jax.random.PRNGKey(12), (3, 11, 64))
    got = layer(P.to_tensor(np.asarray(h))).numpy()
    np.testing.assert_allclose(got, np.asarray(ref.expert_layer(TINY, w, h)),
                               atol=1e-5)
    assert int(np.asarray(layer.last_counts._value).sum()) == 33 * 3


def test_shares_of_the_expert_layer_sum_to_the_whole(weights):
    """``held=(0, E/2)`` + ``held=(E/2, E/2)``, ``Shared`` counted once =
    the uncut reference's layer; each share is the reference's share."""
    h = jax.random.normal(jax.random.PRNGKey(13), (2, 9, 64))
    x = P.to_tensor(np.asarray(h))
    w = ref.layer_weights(weights, 2)
    parts = [_layer(weights, 2, held=(4 * i, 4), shared=(i == 0))[0](x)
             .numpy() for i in range(2)]
    np.testing.assert_allclose(
        sum(parts), np.asarray(ref.expert_layer(TINY, w, h)), atol=1e-5)
    for i, part in enumerate(parts):
        np.testing.assert_allclose(part, np.asarray(ref.expert_layer(
            TINY, w, h, held=(4 * i, 4), shared=(i == 0))), atol=1e-5)


def test_rows_of_experts_held_elsewhere_are_cleared(weights, monkeypatch):
    """On a TPU the grouped product writes nothing past its last group:
    the rows of pairs held elsewhere hold whatever the memory held.  With
    NaN planted there a share's result is still the reference's."""
    real = jax.lax.ragged_dot

    def planted(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        past = jnp.arange(out.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", planted)
    layer, w = _layer(weights, 0, held=(4, 4))
    h = jax.random.normal(jax.random.PRNGKey(14), (2, 9, 64))
    got = layer(P.to_tensor(np.asarray(h))).numpy()
    assert int(np.asarray(layer.last_counts._value).sum()) < 18 * 3
    np.testing.assert_allclose(got, np.asarray(ref.expert_layer(
        TINY, w, h, held=(4, 4))), atol=1e-5)


def test_unknown_router_is_refused():
    with pytest.raises(ValueError, match="router"):
        DroplessMoELayer(8, 4, 4, 2, route="argmax")


# -------------------------------------------------------- the whole model
def test_forward_matches_reference(model, weights):
    ids = np.random.default_rng(1).integers(1, TINY["vocab_size"], (2, 29))
    got = model(P.to_tensor(ids.astype(np.int32))).numpy()
    want = np.asarray(ref.logits(TINY, weights, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_held_share_matches_reference():
    """The model holding experts 0-3 of a router 8 wide (the benchmark's
    cut) against the reference given the same share."""
    w = tiny_weights(cfg=HALF)
    assert w["l0.experts.w13"].shape[0] == 4 and w["l0.gate"].shape[1] == 8
    model = build(w, HALF)
    ids = np.random.default_rng(2).integers(1, 300, (1, 23))
    got = model(P.to_tensor(ids.astype(np.int32))).numpy()
    np.testing.assert_allclose(
        got, np.asarray(ref.logits(HALF, w, jnp.asarray(ids))), atol=1e-4)


def test_placeholders_hold_nothing():
    model = family.build(TINY, training=False)
    assert sum(int(p._value.size) for p in model.parameters()) == 0


def test_model_initialises_itself():
    model = family.build(TINY, training=False, init_weights=True)
    model.eval()
    out = model(P.to_tensor(np.arange(12, dtype=np.int32).reshape(2, 6)))
    assert out.shape == [2, 6, TINY["vocab_size"]]
    assert np.isfinite(out.numpy()).all()


@pytest.mark.parametrize("change, error", [
    ({"position_embedding_type": "rope"}, NotImplementedError),
    ({"mamba_n_groups": 2}, NotImplementedError),
    ({"tie_word_embeddings": False}, NotImplementedError),
    ({"mamba_expand": 3}, ValueError),
    ({"layer_types": ["mamba", "window", "attention", "mamba"]}, ValueError),
], ids=["rope", "groups", "untied", "expand", "layer_type"])
def test_what_is_not_built_is_refused(change, error):
    with pytest.raises(error):
        family.build(dict(TINY, **change), training=False)
