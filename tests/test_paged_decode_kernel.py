"""The ragged paged-decode kernel (Pallas, interpret mode on the CPU)
against the XLA composition ``paged_attend``, the in-place append of the
row-page layout, and what the serving engine says about the path it took.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.incubate.nn.paged_attention import (paged_attend,
                                                    paged_decode_step,
                                                    row_pages_default)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.ops.pallas.paged_attention import (
    PAGED_DECODE_REVISION, from_row_pages, paged_decode,
    paged_decode_supported, to_row_pages)
from paddle_tpu.serving.aot_cache import engine_fingerprint

# Tolerances from the dtype: outputs are O(1) averages of unit normals.
# f32: the online softmax sums in another order than the one-pass
# reference, a few ulp per block.  bf16: probabilities and the output
# are each rounded to bf16 once on both sides but at different points
# (before / after normalising), so two results differ by a few bf16 ulp.
ATOL = {jnp.float32: 64 * float(jnp.finfo(jnp.float32).eps),
        jnp.bfloat16: 4 * float(jnp.finfo(jnp.bfloat16).eps)}

PAGE, HEADS, DIM = 16, 4, 32

# name -> (lens, table width in pages, pages per compute block)
CASES = {
    "ragged": ((5, 37, 16, 120), 8, None),
    "length_zero": ((0, 9, 0, 64), 8, None),
    "all_empty": ((0, 0), 4, None),
    "page_boundary": ((32, 16, 48, 128), 8, None),
    "one_past_boundary": ((33, 17, 49, 1), 8, None),
    "full_2048_row": ((2048, 1), 128, None),
    "blocks_of_two_pages": ((5, 37, 16, 128, 64, 33), 8, 2),
    "blocks_of_three_pages": ((128, 0, 47, 96), 8, 3),
}


def _pools(lens, width, dtype, seed):
    """Seeded head-major pools and a PERMUTED, non-contiguous block
    table.  The last page is all NaN: the kernel's table points every
    entry past a slot's live pages at it (they must never be read); the
    reference's points them at the zero page 0, which it masks."""
    rng = np.random.default_rng(seed)
    b = len(lens)
    n = b * width + 2
    k = rng.standard_normal((n, HEADS, PAGE, DIM)).astype(np.float32)
    v = rng.standard_normal((n, HEADS, PAGE, DIM)).astype(np.float32)
    k[0] = v[0] = 0.0
    k[-1] = v[-1] = np.nan
    tables = rng.permutation(np.arange(1, n - 1)).reshape(b, width)
    live = (np.arange(width)[None, :] * PAGE
            < np.asarray(lens)[:, None])
    q = rng.standard_normal((b, HEADS, 1, DIM)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype),
            jnp.asarray(np.where(live, tables, 0), jnp.int32),
            jnp.asarray(np.where(live, tables, n - 1), jnp.int32),
            jnp.asarray(lens, jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_paged_decode_matches_paged_attend(case, dtype):
    lens, width, ppb = CASES[case]
    q, k, v, ref_tables, tables, lens_a = _pools(lens, width, dtype,
                                                 seed=len(case))
    ref = paged_attend(q, k, v, ref_tables, lens_a, PAGE)
    out = paged_decode(q, to_row_pages(k), to_row_pages(v), tables,
                       lens_a, pages_per_block=ppb, interpret=True)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    live = np.asarray(lens) > 0
    assert np.isfinite(out).all()
    # a slot of length 0 reads nothing and returns zeros (the reference
    # averages its masked garbage there: not compared)
    assert not out[~live].any()
    np.testing.assert_allclose(out[live], ref[live], rtol=0,
                               atol=ATOL[dtype])


def test_row_pages_round_trip():
    k = jnp.arange(3 * HEADS * PAGE * DIM, dtype=jnp.float32).reshape(
        3, HEADS, PAGE, DIM)
    rows = to_row_pages(k)
    assert rows.shape == (3, PAGE, HEADS * DIM)
    # token t of head h is lanes [h*d, (h+1)*d) of row t
    np.testing.assert_array_equal(rows[1, 5, 2 * DIM:3 * DIM], k[1, 2, 5])
    np.testing.assert_array_equal(from_row_pages(rows, HEADS), k)


@pytest.mark.parametrize("dtype,heads,dim,page,ok", [
    (jnp.bfloat16, 16, 64, 16, True), (jnp.float32, 16, 64, 16, True),
    (jnp.float32, 4, 32, 8, True), (jnp.bfloat16, 4, 32, 8, False),
    (jnp.bfloat16, 4, 16, 16, False), (jnp.float16, 16, 64, 16, False),
    (jnp.int8, 16, 64, 32, False)])
def test_supported_geometries(dtype, heads, dim, page, ok):
    assert paged_decode_supported(dtype, heads, dim, page) is ok
    # off a TPU no pool is a row-page pool, whatever its geometry
    assert row_pages_default(dtype, heads, dim, page) is False


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_append_lands_in_place_and_nothing_else_moves(dtype):
    lens = (5, 16, 47, 0)
    q, k, v, _, _, lens_a = _pools(lens, 8, dtype, seed=7)
    k = jnp.nan_to_num(k)
    v = jnp.nan_to_num(v)
    # every live slot owns its own permuted pages, the page its next
    # token falls on among them; slot 3 is empty: its row is the
    # garbage page 0, where its append is absorbed
    tables = np.random.default_rng(11).permutation(
        np.arange(1, 33)).reshape(4, 8)
    tables[3] = 0
    tables = jnp.asarray(tables, jnp.int32)
    rng = np.random.default_rng(3)
    k_new = jnp.asarray(rng.standard_normal((4, HEADS, 1, DIM)), dtype)
    v_new = jnp.asarray(rng.standard_normal((4, HEADS, 1, DIM)), dtype)

    ref_out, ref_k, ref_v = paged_decode_step(
        q, k_new, v_new, k, v, tables, lens_a, PAGE)
    kr, vr = to_row_pages(k), to_row_pages(v)
    out, k2, v2 = paged_decode_step(q, k_new, v_new, kr, vr, tables,
                                    lens_a, PAGE)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32), rtol=0,
                               atol=ATOL[dtype])
    # the same pool, bit for bit, as the XLA composition writes
    np.testing.assert_array_equal(np.asarray(k2, np.float32),
                                  np.asarray(to_row_pages(ref_k),
                                             np.float32))
    np.testing.assert_array_equal(np.asarray(v2, np.float32),
                                  np.asarray(to_row_pages(ref_v),
                                             np.float32))
    for new, pool, before in ((k_new, k2, kr), (v_new, v2, vr)):
        pool = np.asarray(pool, np.float32)
        changed = np.argwhere(
            (pool != np.asarray(before, np.float32)).any(axis=-1))
        want = sorted((int(tables[b, lens[b] // PAGE]), lens[b] % PAGE)
                      for b in range(4))
        assert sorted(map(tuple, changed.tolist())) == want
        for b in range(4):
            np.testing.assert_array_equal(
                pool[int(tables[b, lens[b] // PAGE]), lens[b] % PAGE],
                np.asarray(new[b].reshape(-1), np.float32))


@pytest.fixture(scope="module")
def tiny_model():
    import paddle_tpu as P
    P.seed(0)
    return GPTForCausalLM(GPTConfig(
        vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
        max_seq_len=128, dropout=0.0, attention_dropout=0.0))


def _cfg():
    return serving.EngineConfig(max_num_seqs=4, page_size=8,
                                max_model_len=128,
                                prefill_buckets=(32, 128))


@pytest.fixture
def kernel_engines(tiny_model, monkeypatch, tmp_path):
    """(XLA engine, kernel engine): the second is built as on a TPU —
    ``kernel_default()`` forced true, the kernel in interpret mode."""
    xla = serving.LLMEngine(tiny_model, _cfg(),
                            program_cache=str(tmp_path))
    monkeypatch.setattr("paddle_tpu.ops.pallas.kernel_default",
                        lambda: True)
    kern = serving.LLMEngine(tiny_model, _cfg(),
                             program_cache=str(tmp_path))
    monkeypatch.undo()
    yield xla, kern
    xla.shutdown()
    kern.shutdown()


def test_fingerprint_names_the_attention_path(tiny_model, kernel_engines):
    xla, kern = kernel_engines
    assert xla.attention_path == "xla+next_token/1"
    assert kern.attention_path == \
        f"paged_decode/{PAGED_DECODE_REVISION}+next_token/1"
    assert xla._k_pools[0].ndim == 4 and kern._k_pools[0].ndim == 3
    assert xla.program_fingerprint != kern.program_fingerprint
    args = (tiny_model.config, _cfg(), xla._params, None)
    # stable within a path
    assert engine_fingerprint(*args, attention=xla.attention_path) \
        == xla.program_fingerprint
    assert engine_fingerprint(*args, attention="xla+next_token/1") \
        == xla.program_fingerprint
    assert engine_fingerprint(*args, attention=kern.attention_path) \
        == kern.program_fingerprint
    assert engine_fingerprint(
        *args, attention="paged_decode/0+next_token/1") \
        != kern.program_fingerprint


def test_kernel_engine_serves_the_xla_engines_tokens(kernel_engines):
    xla, kern = kernel_engines
    prompts = [[5, 6, 7, 8], list(range(1, 41)), [9] * 17]
    sps = [serving.SamplingParams(max_new_tokens=10, seed=0),
           serving.SamplingParams(max_new_tokens=10, temperature=0.8,
                                  top_p=0.9, seed=1),
           serving.SamplingParams(max_new_tokens=10, seed=2)]
    want = [r.output_token_ids for r in xla.generate(prompts, sps)]
    got = [r.output_token_ids for r in kern.generate(prompts, sps)]
    assert got == want
    # the two engines never share a stored program
    assert kern.metrics.aot_cache_loads == 0
    assert kern.metrics.compile_count == xla.metrics.compile_count > 0


def test_page_handoff_crosses_pool_layouts(kernel_engines):
    """export_page_state ships head-major blocks whatever the local
    layout: a kernel engine's pages continue on an XLA engine."""
    xla, kern = kernel_engines
    sp = serving.SamplingParams(max_new_tokens=12, seed=4)
    prompt = list(range(3, 30))
    want = xla.generate([prompt], sp)[0].output_token_ids
    rid = kern.add_request(prompt, sp)
    for _ in range(5):
        kern.step()
    state = kern.export_page_state(rid)
    assert state["layers"][0]["k"].shape[1:] == (4, 8, 32)
    new = xla.import_page_state(state)
    while xla.has_unfinished():
        xla.step()
    assert xla.finished_requests[new].output_token_ids == want


@pytest.mark.parametrize("which", ["xla", "kernel"])
def test_decode_span_carries_pages_live_and_kernel(kernel_engines, which):
    engine = kernel_engines[which == "kernel"]
    rec = obs.recorder()
    before = rec.total_recorded
    engine.generate([[1, 2, 3], list(range(1, 20))],
                    serving.SamplingParams(max_new_tokens=4))
    spans = [r for r in rec.spans()[-(rec.total_recorded - before):]
             if r.name == "serving.decode"]
    assert spans
    assert all(s.attrs["kernel"] is (which == "kernel") for s in spans)
    # page 8: a 3-token and a 19-token prompt decode at lengths 3 and 19
    # first — ceil(4/8) + ceil(20/8) pages — and the pass launched ahead
    # of it in the same span at 4 and 20: as many again
    assert spans[0].attrs["live"] == 2
    assert spans[0].attrs["pages_live"] == (1 + 3) + (1 + 3)
    assert engine.metrics.snapshot()["pages"]["live"] \
        == spans[-1].attrs["pages_live"]
