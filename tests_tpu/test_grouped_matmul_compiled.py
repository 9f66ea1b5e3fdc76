"""The grouped expert product through Mosaic on the chip: the Pallas kernel
``grouped_matmul`` against ``jax.lax.ragged_dot`` at the expert cells'
decode shapes, with the tiles ``pick_tiles`` gives them, and at a few
smaller ones that reach every branch of the walk (empty groups, one group
for every row, rows past the last group, a row count that is no tile
multiple).  Without a TPU the suite errors (conftest)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul, pick_tiles


def _counts(rng, rows, groups, held=None):
    held = groups if held is None else held
    idx = rng.integers(0, groups, rows)
    return np.bincount(idx[idx < held], minlength=held)[:held].astype(
        np.int32)


CASES = {
    # (m, K), (G, K, N), router width (> G: a share of the experts)
    "sdar_w13": ((1024, 2048), (128, 2048, 1536), 128),
    "sdar_w2": ((1024, 768), (128, 768, 2048), 128),
    "kanana_w13": ((192, 2048), (128, 2048, 1536), 128),
    "granite_w13": ((640, 4096), (36, 4096, 1536), 72),
    "granite_w2": ((640, 768), (36, 768, 4096), 72),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot_at_the_cells(case):
    (m, k), (g, _, n), width = CASES[case]
    rng = np.random.default_rng(0)
    counts = _counts(rng, m, width, g)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((g, k, n)) * 0.05, jnp.bfloat16)
    tiles = pick_tiles(m, g, k, n, jnp.bfloat16)
    assert tiles is not None
    inside = int(counts.sum())
    got = np.asarray(grouped_matmul(x, w, jnp.asarray(counts), tiles))
    want = np.asarray(jax.lax.ragged_dot(x, w, jnp.asarray(counts),
                                         preferred_element_type=jnp.float32))
    np.testing.assert_allclose(got[:inside], want[:inside], rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("counts,m,tiles", [
    ([0, 0, 256, 0], 256, (128, 256)),
    ([5, 0, 0, 120, 3], 192, (128, 256)),
    ([40, 0, 30, 0], 192, (64, 128)),
    ([0, 0, 0, 0], 64, (64, 256)),
])
def test_kernel_walk_branches(counts, m, tiles):
    rng = np.random.default_rng(1)
    counts = np.asarray(counts, np.int32)
    k, n = 256, 256
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((len(counts), k, n)) * 0.05,
                    jnp.bfloat16)
    inside = int(counts.sum())
    got = np.asarray(grouped_matmul(x, w, jnp.asarray(counts), tiles))
    want = np.asarray(jax.lax.ragged_dot(x, w, jnp.asarray(counts),
                                         preferred_element_type=jnp.float32))
    np.testing.assert_allclose(got[:inside], want[:inside], rtol=2e-3,
                               atol=2e-3)
