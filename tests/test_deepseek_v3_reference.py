"""benchmark/reference/deepseek_v3.py against the published code: the same
seeded weights in ``transformers.DeepseekV3ForCausalLM`` (eager attention,
float32) give the reference's logits."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import common as refc
from benchmark.reference import deepseek_v3 as ref

TINY = {
    "family": "deepseek_v3", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "q_lora_rank": None,
    "intermediate_size": 96, "moe_intermediate_size": 24,
    "n_routed_experts": 16, "n_shared_experts": 2, "num_experts_per_tok": 3,
    "first_k_dense_replace": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.448,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "vocab_size": 160,
    "max_position_embeddings": 128, "initializer_range": 0.02,
    # large against the sigmoid scores' spread, so the corrected choice
    # differs from the uncorrected one on most tokens
    "e_score_correction_bias_std": 0.1,
}


def tiny_weights(seed=3, dtype=jnp.float32, cfg=TINY):
    w = refc.make_weights(ref.weight_spec(cfg), seed, dtype)
    # norms away from 1 so that a dropped norm weight would show
    key = jax.random.PRNGKey(seed + 1)
    for i, name in enumerate(sorted(w)):
        if name.endswith(("ln1", "ln2", "kvn", "norm")):
            w[name] = (1.0 + 0.1 * jax.random.normal(
                jax.random.fold_in(key, i), w[name].shape)).astype(dtype)
    return w


def _to_hf(cfg, w):
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    hf_cfg = tf.DeepseekV3Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_attention_heads"],
        n_shared_experts=cfg["n_shared_experts"],
        n_routed_experts=cfg["n_routed_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=None,
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], n_group=1, topk_group=1,
        num_experts_per_tok=cfg["num_experts_per_tok"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        norm_topk_prob=True, hidden_act="silu",
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_scaling=None, rope_interleave=True, attention_bias=False,
        tie_word_embeddings=False, attn_implementation="eager")
    model = tf.DeepseekV3ForCausalLM(hf_cfg).float().eval()

    def t(x):                      # ours are [in, out]; torch's [out, in]
        return torch.tensor(np.asarray(x, np.float32).T.copy())

    def v(x):
        return torch.tensor(np.asarray(x, np.float32))

    sd = {"model.embed_tokens.weight": v(w["embed"]),
          "lm_head.weight": t(w["head"]), "model.norm.weight": v(w["norm"])}
    f = cfg["moe_intermediate_size"]
    for i in range(cfg["num_hidden_layers"]):
        p, q = f"l{i}.", f"model.layers.{i}."
        sd.update({
            q + "input_layernorm.weight": v(w[p + "ln1"]),
            q + "post_attention_layernorm.weight": v(w[p + "ln2"]),
            q + "self_attn.q_proj.weight": t(w[p + "q"]),
            q + "self_attn.kv_a_proj_with_mqa.weight": t(w[p + "kva"]),
            q + "self_attn.kv_a_layernorm.weight": v(w[p + "kvn"]),
            q + "self_attn.kv_b_proj.weight": t(w[p + "kvb"]),
            q + "self_attn.o_proj.weight": t(w[p + "o"])})

        def mlp(prefix, w13, w2):
            half = w13.shape[-1] // 2
            sd[prefix + "gate_proj.weight"] = t(w13[:, :half])
            sd[prefix + "up_proj.weight"] = t(w13[:, half:])
            sd[prefix + "down_proj.weight"] = t(w2)

        if ref.is_dense(cfg, i):
            mlp(q + "mlp.", w[p + "mlp.w13"], w[p + "mlp.w2"])
        else:
            sd[q + "mlp.gate.weight"] = t(w[p + "gate"])
            sd[q + "mlp.gate.e_score_correction_bias"] = v(w[p + "gate_bias"])
            mlp(q + "mlp.shared_experts.", w[p + "shared.w13"],
                w[p + "shared.w2"])
            for e in range(cfg["n_routed_experts"]):
                mlp(q + f"mlp.experts.{e}.", w[p + "experts.w13"][e],
                    w[p + "experts.w2"][e])
            assert w[p + "experts.w13"].shape[-1] == 2 * f
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and all("rotary" in m or "inv_freq" in m
                                  for m in missing), (missing, unexpected)
    return model, torch


def test_reference_matches_transformers():
    w = tiny_weights()
    model, torch = _to_hf(TINY, w)
    ids = np.random.default_rng(0).integers(1, TINY["vocab_size"], (2, 37))
    with torch.no_grad():
        want = model(torch.tensor(ids)).logits.numpy()
    got = np.asarray(ref.logits(TINY, w, jnp.asarray(ids)))
    # both float32 over the same weights: what is left is the order of
    # summation (logits here are ~0.3 in size)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_correction_bias_changes_choice_not_weights():
    w = ref.layer_weights(tiny_weights(), 1)
    h = jax.random.normal(jax.random.PRNGKey(5), (64, TINY["hidden_size"]))
    weights, idx = ref.route(TINY, w, h)
    plain_w, plain_idx = ref.route(TINY, dict(
        w, gate_bias=jnp.zeros_like(w["gate_bias"])), h)
    moved = np.sort(np.asarray(idx), -1) != np.sort(np.asarray(plain_idx), -1)
    assert moved.any(-1).mean() > 0.3        # the bias moves the choice
    # ... but a chosen expert's weight is its plain sigmoid score,
    # normalised over the chosen and scaled: the bias is not in it
    sc = jax.nn.sigmoid(h @ w["gate"])
    top = np.take_along_axis(np.asarray(sc), np.asarray(idx), -1)
    want = TINY["routed_scaling_factor"] * top / top.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1),
                               TINY["routed_scaling_factor"], rtol=1e-5)


def test_shares_of_the_expert_layer_sum_to_the_whole():
    w = ref.layer_weights(tiny_weights(), 2)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 9, TINY["hidden_size"]))
    whole = ref.expert_layer(TINY, w, h)
    parts = sum(ref.expert_layer(TINY, w, h, held=(4 * i, 4), shared=(i == 0))
                for i in range(4))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=1e-6)
