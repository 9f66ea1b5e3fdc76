"""Serving programs on the TPU backend: the AOT program cache's
store -> load round trip (a TPU runtime that refused to serialize
executables would make every replica boot a cold one)."""
import os

import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving.aot_cache import AOTProgramCache
from paddle_tpu.utils.compile_cache import serving_aot_dir


def test_aot_cache_round_trip_on_tpu():
    P.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=512, hidden_size=256, num_layers=2, num_heads=4,
        max_seq_len=128, dropout=0.0, attention_dropout=0.0))
    model.to(dtype="bfloat16")

    def cfg():
        return serving.EngineConfig(
            max_num_seqs=4, page_size=16, max_model_len=128,
            prefill_buckets=(32, 128), dtype=jnp.bfloat16)

    # a fixed directory under the compile-cache root: a carried-over
    # cache makes the FIRST boot warm too, which the asserts allow
    cache = AOTProgramCache(os.path.join(serving_aot_dir(), "tests_tpu"))
    prompts = [[5, 6, 7, 8], list(range(1, 41))]
    sps = [serving.SamplingParams(max_new_tokens=8, seed=0),
           serving.SamplingParams(max_new_tokens=8, temperature=0.8,
                                  top_p=0.9, seed=1)]

    first = serving.LLMEngine(model, cfg(), program_cache=cache)
    boot = first.warmup()
    assert boot["programs"] == first.config.compile_bound
    assert cache.store_count == boot["compiled"]
    want = [r.output_token_ids for r in first.generate(prompts, sps)]
    assert first.metrics.decode_fault_recoveries == 0
    first.shutdown()

    events = obs.recompile_log().count
    second = serving.LLMEngine(model, cfg(), program_cache=cache)
    boot2 = second.warmup()
    assert boot2["compiled"] == 0
    assert boot2["cache_loads"] == boot["programs"]
    assert obs.recompile_log().count == events
    got = [r.output_token_ids for r in second.generate(prompts, sps)]
    assert got == want
    assert second.metrics.decode_fault_recoveries == 0
    second.shutdown()
    stats = cache.stats()
    assert stats["errors"] == 0 and stats["serialize_supported"], stats
