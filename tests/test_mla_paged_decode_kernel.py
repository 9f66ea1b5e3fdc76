"""The latent (MLA) ragged paged-decode kernel (Pallas, interpret mode on
the CPU) against the XLA composition ``latent_attend``."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.nn.paged_attention import latent_attend
from paddle_tpu.ops.pallas.mla_paged_attention import (
    mla_paged_decode, mla_paged_decode_supported)

# as tests/test_paged_decode_kernel.py: outputs are O(1) averages of unit
# normals.  f32: the online softmax sums in another order, a few ulp a
# block.  bf16: probabilities and output are each rounded once on both
# sides but at different points, a few bf16 ulp.
ATOL = {jnp.float32: 64 * float(jnp.finfo(jnp.float32).eps),
        jnp.bfloat16: 4 * float(jnp.finfo(jnp.bfloat16).eps)}

PAGE, HEADS, RANK, WIDTH = 16, 8, 128, 256      # a row of 128 + 64, padded

CASES = {
    "ragged": ((5, 37, 16, 120), 8, None),
    "length_zero": ((0, 9, 0, 64), 8, None),
    "all_empty": ((0, 0), 4, None),
    "one_past_boundary": ((33, 17, 49, 1), 8, None),
    "blocks_of_two_pages": ((5, 37, 16, 128, 64, 33), 8, 2),
    "blocks_of_three_pages": ((128, 0, 47, 96), 8, 3),
}


def _pool(lens, width, dtype, seed):
    """A seeded pool with a permuted block table; the last page is NaN and
    every table entry past a slot's live pages points at it in the
    kernel's table (never read) and at the zero page 0 in the
    reference's (masked)."""
    rng = np.random.default_rng(seed)
    b = len(lens)
    n = b * width + 2
    pool = rng.standard_normal((n, PAGE, WIDTH)).astype(np.float32)
    pool[..., RANK + 64:] = 0.0                 # the pad columns
    pool[0] = 0.0
    pool[-1] = np.nan
    tables = rng.permutation(np.arange(1, n - 1)).reshape(b, width)
    live = np.arange(width)[None, :] * PAGE < np.asarray(lens)[:, None]
    q = rng.standard_normal((b, HEADS, WIDTH)).astype(np.float32)
    q[..., RANK + 64:] = 0.0
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(np.where(live, tables, n - 1), jnp.int32),
            jnp.asarray(np.where(live, tables, 0), jnp.int32),
            jnp.asarray(lens, jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_composition(case, dtype):
    lens, width, ppb = CASES[case]
    q, pool, t_kernel, t_ref, n = _pool(lens, width, dtype, len(case))
    scale = 192 ** -0.5
    got = mla_paged_decode(q, pool, t_kernel, n, rank=RANK, scale=scale,
                           pages_per_block=ppb, interpret=True)
    want = latent_attend(q, jnp.nan_to_num(pool), t_ref, n, RANK, PAGE,
                         scale)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    empty = np.asarray(lens) == 0
    assert not got[empty].any()                 # a dead slot reads zeros
    np.testing.assert_allclose(got[~empty], want[~empty],
                               atol=ATOL[dtype], rtol=0)


def test_supported_pools():
    assert mla_paged_decode_supported(jnp.bfloat16, 512, 640, 16)
    assert mla_paged_decode_supported(jnp.float32, 128, 256, 8)
    assert not mla_paged_decode_supported(jnp.bfloat16, 512, 576, 16)
    assert not mla_paged_decode_supported(jnp.bfloat16, 512, 640, 8)
    assert not mla_paged_decode_supported(jnp.int8, 512, 640, 32)
