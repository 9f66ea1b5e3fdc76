"""The grouped-query ragged paged-decode kernel (Pallas, interpret mode on
the CPU) against the XLA composition ``grouped_paged_attend`` that
``GroupedKV`` reads through everywhere else, and what the serving engine
says about the path it took for the two models that cache grouped K/V
beside another kind: full layers beside window rings (SmallThinker's
shape) and attention layers beside recurrent state (Granite's)."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.incubate.nn.paged_attention import grouped_paged_attend
from paddle_tpu.ops.pallas.paged_attention import (
    GROUPED_PAGED_DECODE_REVISION, grouped_paged_decode)
from paddle_tpu.serving import kv_pool

# the tolerances of the one-head-a-query kernel's tests: f32 sums in
# another order, bf16 probabilities and outputs rounded at other points
ATOL = {jnp.float32: 64 * float(jnp.finfo(jnp.float32).eps),
        jnp.bfloat16: 4 * float(jnp.finfo(jnp.bfloat16).eps)}

PAGE = 16

# name -> (groups, K/V heads, head_dim, lens, table width in pages,
#          pages per compute block, scale; None: 1/sqrt(d))
CASES = {
    "ragged_g1": (1, 4, 128, (5, 37, 16, 120), 8, None, None),
    "length_zero_g4_small_d": (4, 4, 32, (0, 9, 0, 64), 8, None, None),
    "all_empty_g7": (7, 4, 128, (0, 0), 4, None, None),
    "page_boundary_g7": (7, 4, 128, (32, 16, 48, 128), 8, None, None),
    "one_past_boundary_g4_kv8": (4, 8, 128, (33, 17, 49, 1), 8, None,
                                 None),
    "full_table_g7_small_d": (7, 4, 32, (2048, 1), 128, None, None),
    "blocks_of_two_pages_g1_kv8_small_d": (1, 8, 16, (5, 37, 16, 128, 64,
                                                      33), 8, 2, None),
    "blocks_of_three_pages_g7_kv8_small_d": (7, 8, 16, (128, 0, 47, 96), 8,
                                             3, None),
    "granite_scale_g4_kv8": (4, 8, 128, (5, 130, 0, 77), 16, None,
                             1 / 128),
}


def _pools(groups, kv_heads, dim, lens, width, dtype, seed):
    """Seeded row pages and a PERMUTED, non-contiguous block table.  The
    last page is all NaN: the kernel's table points every entry past a
    slot's live pages at it (they must never be read); the reference's
    points them at the zero page 0, which it masks."""
    rng = np.random.default_rng(seed)
    b = len(lens)
    n = b * width + 2
    k = rng.standard_normal((n, PAGE, kv_heads * dim)).astype(np.float32)
    v = rng.standard_normal((n, PAGE, kv_heads * dim)).astype(np.float32)
    k[0] = v[0] = 0.0
    k[-1] = v[-1] = np.nan
    tables = rng.permutation(np.arange(1, n - 1)).reshape(b, width)
    live = (np.arange(width)[None, :] * PAGE
            < np.asarray(lens)[:, None])
    q = rng.standard_normal((b, groups * kv_heads, dim)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype),
            jnp.asarray(np.where(live, tables, 0), jnp.int32),
            jnp.asarray(np.where(live, tables, n - 1), jnp.int32),
            jnp.asarray(lens, jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_paged_decode_matches_the_xla_read(case, dtype):
    groups, kv_heads, dim, lens, width, ppb, scale = CASES[case]
    scale = dim ** -0.5 if scale is None else scale
    q, k, v, ref_tables, tables, lens_a = _pools(
        groups, kv_heads, dim, lens, width, dtype, seed=len(case))
    ref = grouped_paged_attend(q[:, None], k, v, ref_tables, lens_a,
                               scale)[:, 0]
    out = grouped_paged_decode(q, k, v, tables, lens_a, scale=scale,
                               pages_per_block=ppb, interpret=True)
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


def test_query_head_reads_its_own_kv_head():
    """Every K/V head holds one constant value row: query head ``i`` of
    28 over 4 gets K/V head ``i // 7``'s value, whatever its scores."""
    q, k, v, _, tables, lens = _pools(7, 4, 128, (40, 3), 4, jnp.float32,
                                      seed=9)
    v = jnp.nan_to_num(v)
    v = jnp.broadcast_to(jnp.repeat(jnp.arange(4.0), 128), v.shape)
    out = grouped_paged_decode(q, k, v, tables, lens, scale=0.1,
                               interpret=True)
    want = np.repeat(np.arange(4.0), 7)[None, :, None]
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(want, out.shape), atol=1e-6)


@pytest.mark.parametrize("what,args", [
    ("heads", dict(q=(2, 6, 32), pages=(9, 16, 128))),       # 6 over 4
    ("width", dict(q=(2, 8, 48), pages=(9, 16, 128))),       # 128 % 48
    ("geometry", dict(q=(2, 8, 16), pages=(9, 16, 64))),     # 64 lanes
])
def test_what_the_kernel_refuses(what, args):
    q = jnp.zeros(args["q"], jnp.float32)
    pages = jnp.zeros(args["pages"], jnp.float32)
    with pytest.raises(ValueError, match="grouped_paged_decode"):
        grouped_paged_decode(q, pages, pages, jnp.zeros((2, 4), jnp.int32),
                             jnp.ones((2,), jnp.int32), scale=0.1,
                             interpret=True)


# ----------------------------------------------------------- the engine
def _smallthinker():
    from tests.test_smallthinker_model import TINY, build, tiny_weights
    # 2 K/V heads of 64: 128 lanes a row, a geometry the kernel takes
    cfg = dict(TINY, head_dim=64)
    return build(tiny_weights(cfg=cfg), cfg=cfg)


def _granite():
    from tests.test_granitemoehybrid_model import TINY, build, tiny_weights
    # 4 / 2 heads of 64; the mixer's 16 heads of 16 fill the wider state
    cfg = dict(TINY, hidden_size=256, mamba_n_heads=16, mamba_expand=1)
    return build(tiny_weights(cfg=cfg), cfg=cfg)


MODELS = {"kv_window": _smallthinker, "kv_state": _granite}


@pytest.fixture(scope="module", params=sorted(MODELS))
def engines(request, tmp_path_factory):
    """(model name, XLA engine, kernel engine): the second is built as on
    a TPU — ``kernel_default()`` forced true, the kernels in interpret
    mode."""
    model = MODELS[request.param]()
    cfg = serving.EngineConfig(max_num_seqs=3, page_size=8,
                               max_model_len=64, dtype=jnp.float32)
    cache = str(tmp_path_factory.mktemp("aot"))
    xla = serving.LLMEngine(model, cfg, program_cache=cache)
    mp = pytest.MonkeyPatch()
    mp.setattr("paddle_tpu.ops.pallas.kernel_default", lambda: True)
    try:
        kern = serving.LLMEngine(model, cfg, program_cache=cache)
    finally:
        mp.undo()
    yield request.param, xla, kern
    xla.shutdown()
    kern.shutdown()


def test_kernel_engine_serves_the_xla_engines_tokens(engines):
    name, xla, kern = engines
    assert kern._pool.kv.decode_kernel and not xla._pool.kv.decode_kernel
    rng = np.random.default_rng(4)
    vocab = xla._model.config.vocab_size
    prompts = [rng.integers(1, vocab, n).tolist() for n in (21, 3, 30, 12)]
    sps = [serving.SamplingParams(max_new_tokens=n, temperature=0.0)
           for n in (12, 20, 9, 15)]
    want = [r.output_token_ids for r in xla.generate(prompts, sps)]
    got = [r.output_token_ids for r in kern.generate(prompts, sps)]
    assert got == want, name


def test_decode_span_and_fingerprint_name_the_kernel(engines, monkeypatch):
    name, xla, kern = engines
    path = f"grouped_paged_decode/{GROUPED_PAGED_DECODE_REVISION}"
    assert kern.attention_path.startswith(f"kv:{path}+prefill:")
    assert path not in xla.attention_path
    assert kern.program_fingerprint != xla.program_fingerprint
    # another revision of the kernel never loads this one's executables
    from paddle_tpu.serving.aot_cache import engine_fingerprint
    monkeypatch.setattr("paddle_tpu.ops.pallas.kernel_default",
                        lambda: True)       # as the engine was built
    args = (kern._model.config, kern.config, kern._params, None)
    assert engine_fingerprint(*args, attention=kern.attention_path,
                              experts=kern.experts_path) \
        == kern.program_fingerprint
    assert engine_fingerprint(
        *args, attention=kern.attention_path.replace(
            path, "grouped_paged_decode/0"),
        experts=kern.experts_path) != kern.program_fingerprint
    monkeypatch.undo()
    for engine, kernel in ((xla, False), (kern, True)):
        rec = obs.recorder()
        before = rec.total_recorded
        engine.generate([[1, 2, 3], list(range(1, 20))],
                        serving.SamplingParams(max_new_tokens=4))
        spans = [r for r in rec.spans()[-(rec.total_recorded - before):]
                 if r.name == "serving.decode"]
        assert spans, name
        assert all(s.attrs["kernel"] is kernel for s in spans), name


def test_block_pass_and_unfit_geometry_keep_the_xla_read(monkeypatch):
    """A block-causal cache (SDAR's blocks of 4) and a row the kernel does
    not take (32 lanes) name the XLA read on a TPU too."""
    monkeypatch.setattr("paddle_tpu.ops.pallas.kernel_default", lambda: True)
    cfg = serving.EngineConfig(max_num_seqs=2, page_size=16,
                               max_model_len=64, dtype=jnp.bfloat16)
    block = kv_pool.GroupedKV(cfg, 1, 4, 128, query_heads=32, scale=0.1,
                              causal_block=4)
    narrow = kv_pool.GroupedKV(cfg, 1, 2, 16, query_heads=4, scale=0.1)
    full = kv_pool.GroupedKV(cfg, 1, 4, 128, query_heads=28, scale=0.1)
    for pool in (block, narrow):
        assert not pool.decode_kernel
        assert pool.attention_path.startswith("xla/row_pages")
    assert full.decode_kernel
    assert full.attention_path.startswith(
        f"grouped_paged_decode/{GROUPED_PAGED_DECODE_REVISION}")
    layered = kv_pool.LayeredPool(cfg, [
        {"kind": "kv", "num_heads": 4, "head_dim": 128, "query_heads": 28},
        {"kind": "window", "num_heads": 4, "head_dim": 128,
         "query_heads": 28, "window": 16}])
    assert layered.decode_kernel
    assert layered.attention_path.startswith("kv:grouped_paged_decode/")
