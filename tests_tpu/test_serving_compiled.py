"""Serving programs on the TPU backend: the AOT program cache's
store -> load round trip (a TPU runtime that refused to serialize
executables would make every replica boot a cold one), and the ragged
paged-decode kernel — Mosaic's result against the XLA composition, and
the compiled decode / prefill programs free of whole-pool passes."""
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.incubate.nn.paged_attention import paged_attend
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.ops.pallas.paged_attention import paged_decode, to_row_pages
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


# --- the ragged paged-decode kernel on silicon ---------------------------

def _attention_f64(q, k, v, tables, lens, page):
    """Plain float64 attention over each slot's first lens[b] tokens
    (head-major pools), on the host."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    out = np.zeros(q.shape, np.float64)
    for b, n in enumerate(lens):
        if not n:
            continue
        pages = np.asarray(tables)[b, :-(-n // page)]
        kb = np.moveaxis(k[pages], 1, 0).reshape(k.shape[1], -1,
                                                 k.shape[3])[:, :n]
        vb = np.moveaxis(v[pages], 1, 0).reshape(v.shape[1], -1,
                                                 v.shape[3])[:, :n]
        s = np.einsum("hd,htd->ht", q[b, :, 0], kb) / np.sqrt(q.shape[-1])
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        out[b, :, 0] = np.einsum("ht,htd->hd",
                                 p / p.sum(axis=-1, keepdims=True), vb)
    return out


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_paged_decode_at_the_cells_geometry(dtype):
    """Mosaic's kernel and the XLA composition against float64 on the
    host: 16 heads x 64, page 16, ragged lengths with an empty slot, a
    page boundary and one past it, a full row, a permuted table.
    Outputs are of magnitude <= 1.  bf16 rounds the probabilities and
    the output: 2**-6.  f32: the composition's M=1 products are f32
    multiply-reduces, good to ~2e-6, and the kernel asks Mosaic for
    true f32 products (its default, one bf16 pass, read 7.8e-3 here):
    1e-4.  Either way the kernel must not be further from the truth
    than twice the composition it replaces, plus an ulp."""
    rng = np.random.default_rng(0)
    lens = np.array([0, 1, 16, 17, 700, 1280, 2047, 2048], np.int32)
    b, width, page, heads, dim = len(lens), 128, 16, 16, 64
    n = b * width + 1
    k = jnp.asarray(rng.standard_normal((n, heads, page, dim)), dtype)
    v = jnp.asarray(rng.standard_normal((n, heads, page, dim)), dtype)
    q = jnp.asarray(rng.standard_normal((b, heads, 1, dim)), dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, n)).reshape(
        b, width), jnp.int32)
    truth = _attention_f64(q, k, v, tables, lens, page)
    ref = np.asarray(paged_attend(q, k, v, tables, jnp.asarray(lens),
                                  page), np.float64)
    out = np.asarray(paged_decode(q, to_row_pages(k), to_row_pages(v),
                                  tables, jnp.asarray(lens)), np.float64)
    assert not out[0].any()
    err_kernel = np.abs(out[1:] - truth[1:]).max()
    err_xla = np.abs(ref[1:] - truth[1:]).max()
    print(f"paged_decode {jnp.dtype(dtype).name}: max error against "
          f"float64 kernel {err_kernel:.3e}, XLA {err_xla:.3e}")
    eps = float(jnp.finfo(dtype).eps)
    assert err_kernel <= (2 ** -6 if dtype == jnp.bfloat16 else 1e-4)
    assert err_kernel <= 2 * err_xla + 4 * eps


def _cell_engine(monkeypatch, kernel, layers=2):
    """An engine at the serving cell's geometry (32 x 2048, page 16,
    bf16, 16 heads x 64), two layers deep."""
    if not kernel:
        monkeypatch.setattr("paddle_tpu.ops.pallas.kernel_default",
                            lambda: False)
    P.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=512, hidden_size=1024, num_layers=layers, num_heads=16,
        max_seq_len=2048, dropout=0.0, attention_dropout=0.0))
    model.to(dtype="bfloat16")
    engine = serving.LLMEngine(model, serving.EngineConfig(
        max_num_seqs=32, page_size=16, max_model_len=2048,
        prefill_buckets=(128, 2048), dtype=jnp.bfloat16))
    monkeypatch.undo()
    return engine


_WHOLE_POOL = re.compile(
    r"= \w+\[(?:4097|4096),16,(?:16,64|1024)\]\S* "
    r"(copy|convert|fusion)\((?![^\n]*scatter)")


def test_decode_program_reads_no_whole_pool(monkeypatch):
    """The compiled decode AND prefill programs of the cell's geometry:
    a `paged_decode` custom call per layer, and no copy, convert or
    gather fusion the size of a pool (in-place scatters stay).  The XLA
    composition's program, compiled beside it, has all three — which
    proves the pattern finds them."""
    engine = _cell_engine(monkeypatch, kernel=True)
    assert engine.attention_path.startswith("paged_decode/")
    text = engine._get_decode().as_text()
    assert len(re.findall(r"%paged_decode[\w.]* = ", text)) == 2
    assert not _WHOLE_POOL.findall(text)
    assert not _WHOLE_POOL.findall(engine._get_prefill(128).as_text())
    engine.shutdown()

    xla = _cell_engine(monkeypatch, kernel=False)
    assert xla.attention_path == "xla+next_token/1"
    found = set(_WHOLE_POOL.findall(xla._get_decode().as_text()))
    assert found == {"copy", "convert", "fusion"}
    xla.shutdown()


def test_kernel_engine_serves_the_xla_engines_greedy_tokens(monkeypatch):
    prompts = [[5, 6, 7, 8], list(range(1, 200)), [9] * 40]
    sp = serving.SamplingParams(max_new_tokens=8)
    served = {}
    for kernel in (True, False):
        engine = _cell_engine(monkeypatch, kernel)
        served[kernel] = [r.output_token_ids
                          for r in engine.generate(prompts, sp)]
        assert engine.metrics.decode_fault_recoveries == 0
        engine.shutdown()
    assert served[True] == served[False]
