"""ops/pallas/grouped_matmul.py: the grouped expert product (Pallas,
interpret mode here) against ``jax.lax.ragged_dot`` and a float32 numpy
reference, bf16 operands and f32 results; its backward; the walk's
visits; the path rule at the expert cells' shapes; and
``dropless_experts`` on the kernel's path."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.distributed import moe
from paddle_tpu.ops.pallas import grouped_matmul as gm


def _operands(counts, m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((len(counts), k, n)) * 0.1,
                    jnp.bfloat16)
    return x, w, jnp.asarray(np.asarray(counts, np.int32))


def _numpy(x, w, counts):
    """float32 reference of the rows that lie in a group."""
    xf, wf = np.asarray(x, np.float32), np.asarray(w, np.float32)
    out, start = [], 0
    for g, c in enumerate(np.asarray(counts)):
        out.append(xf[start:start + c] @ wf[g])
        start += c
    return np.concatenate(out)


def _eights(groups, seed=5):
    """~8 rows a group over ``groups`` groups, some empty, one heavy."""
    rng = np.random.default_rng(seed)
    c = rng.poisson(8, groups)
    c[3], c[7] = 0, 29
    return c.tolist()


# name: (counts, m, K, N, tiles)
CASES = {
    "empty_groups": ([0, 5, 0, 11, 0], 16, 32, 128, (16, 128)),
    "one_group_every_row": ([0, 0, 48, 0], 48, 32, 128, (16, 128)),
    "group_straddles_tiles": ([5, 40, 3], 48, 32, 256, (16, 128)),
    "rows_past_last_group": ([10, 30, 0, 7], 64, 32, 128, (16, 128)),
    "no_rows_in_any_group": ([0, 0, 0], 32, 32, 128, (16, 128)),
    "m192_tile128": (_eights(24)[:23] + [192 - sum(_eights(24)[:23])], 192,
                     64, 128, (128, 128)),
    "m640_tile256_share": ([20] * 16, 640, 64, 256, (256, 128)),
    "groups128_of_8": (_eights(128), sum(_eights(128)), 32, 128, (128, 128)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot_and_numpy(case):
    counts, m, k, n, tiles = CASES[case]
    x, w, c = _operands(counts, m, k, n)
    inside = int(sum(counts))
    got = np.asarray(gm.grouped_matmul(x, w, c, tiles))
    assert got.dtype == np.float32 and got.shape == (m, n)
    want = np.asarray(jax.lax.ragged_dot(x, w, c,
                                         preferred_element_type=jnp.float32))
    np.testing.assert_allclose(got[:inside], want[:inside], rtol=1e-5,
                               atol=1e-5)
    if inside:
        np.testing.assert_allclose(got[:inside], _numpy(x, w, counts),
                                   rtol=1e-4, atol=1e-4)
    # rows past the last group in a tile some group visits read 0
    tm = tiles[0]
    visited_end = -(-inside // tm) * tm if inside else 0
    assert not np.any(got[inside:min(visited_end, m)])


@pytest.mark.parametrize("counts,m,tm", [
    ([0, 5, 0, 11, 0], 16, 16), ([5, 40, 3], 48, 16),
    ([10, 30, 0, 7], 64, 16), ([0, 0, 0], 32, 16),
    (_eights(128), 1024, 128), (_eights(128), 1024, 64)])
def test_visit_list_covers_each_group_tile_once(counts, m, tm):
    rows = -(-m // tm) * tm
    offsets, gid, mid, real = (np.asarray(a) for a in gm._visits(
        jnp.asarray(np.asarray(counts, np.int32)), rows, tm))
    real = int(real[0])
    assert len(gid) == rows // tm + len(counts) - 1
    want = []
    start = 0
    for g, c in enumerate(counts):
        want += [(g, t) for t in range(start // tm, (start + c - 1) // tm + 1)
                 ] if c else []
        start += c
    assert list(zip(gid[:real].tolist(), mid[:real].tolist())) == want
    np.testing.assert_array_equal(offsets, np.concatenate(
        [[0], np.cumsum(counts)]))
    if real:   # the steps past the last visit repeat it: no new DMA
        assert set(gid[real:]) <= {gid[real - 1]}
        assert set(mid[real:]) <= {mid[real - 1]}


def test_backward_is_ragged_dots():
    counts, m, k, n, tiles = CASES["group_straddles_tiles"]
    x, w, c = _operands(counts, m, k, n, seed=2)
    ct = jnp.asarray(np.random.default_rng(3).standard_normal((m, n)),
                     jnp.float32)

    def loss(product):
        return lambda a, b: jnp.sum(product(a, b) * ct)

    got = jax.grad(loss(lambda a, b: gm.grouped_matmul(a, b, c, tiles)),
                   argnums=(0, 1))(x, w)
    want = jax.grad(loss(lambda a, b: jax.lax.ragged_dot(
        a, b, c, preferred_element_type=jnp.float32)), argnums=(0, 1))(x, w)
    for g, h in zip(got, want):
        assert g.dtype == h.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(h, np.float32))


# the grouped products the three expert cells' programs trace:
# (rows, groups held, K, N) -> what `pick_tiles` names on a TPU (PERF.md
# §6, PR 39: the sweep's table)
RULE = {
    # sdar30b_serve_chat: block pass and commit [32, 4] x top-8, and its
    # 1,024-token prefill
    (1024, 128, 2048, 1536): (128, 1536),
    (1024, 128, 768, 2048): (128, 2048),
    (8192, 128, 2048, 1536): (128, 1536),
    # kanana2_serve_reasoning: decode, 32 x top-6, and the prefills up to
    # its 4,096 bucket (192 rows a group)
    (192, 128, 2048, 1536): (128, 1536),
    (192, 128, 768, 2048): (128, 2048),
    (6144, 128, 768, 2048): (128, 2048),
    (24576, 128, 2048, 1536): (128, 1536),
    # granite4h_serve_chat: decode, 64 x top-10 over 36 held, and the
    # 1,024-token prefill (284 rows a group, the most the sweep measured)
    (640, 36, 4096, 1536): (128, 1536),
    (640, 36, 768, 4096): (128, 4096),
    (10240, 36, 4096, 1536): (128, 1536),
    (10240, 36, 768, 4096): (128, 4096),
    # its 2,048 bucket (569 rows a group; booted, no prompt of the cell's
    # reaches it): past the measured rows, XLA's
    (20480, 36, 4096, 1536): None,
    # a weight tile past 24 MB is cut along N
    (1024, 64, 8192, 2048): (128, 1024),
}


@pytest.mark.parametrize("shape", sorted(RULE), ids=str)
def test_path_rule_at_the_cells_shapes(shape):
    rows, groups, k, n = shape
    assert gm.pick_tiles(rows, groups, k, n, jnp.bfloat16,
                         kernel=True) == RULE[shape]
    # where no kernel may run (the CPU, a GSPMD program): ragged_dot
    assert gm.pick_tiles(rows, groups, k, n, jnp.bfloat16,
                         kernel=False) is None
    assert gm.pick_tiles(rows, groups, k, n, jnp.bfloat16) is None


@pytest.mark.parametrize("share", [False, True])
def test_dropless_experts_on_the_kernel_path(monkeypatch, share):
    """The layer's whole computation with both products on the kernel
    (interpreted) equals it on ragged_dot; the tally says which ran."""
    rng = np.random.default_rng(11)
    n, d, f, experts, k = 24, 32, 64, 8, 2
    held, first = (4, 2) if share else (experts, 0)
    h = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
    w13 = jnp.asarray(rng.standard_normal((held, d, 2 * f)) * 0.1,
                      jnp.bfloat16)
    w2 = jnp.asarray(rng.standard_normal((held, f, d)) * 0.1, jnp.bfloat16)
    weights, idx = moe.softmax_topk_route(
        h, jnp.asarray(rng.standard_normal((d, experts)), jnp.float32), k)
    with moe.grouped_tally() as took:
        want, counts = moe.dropless_experts(h, weights, idx, w13, w2,
                                            first, share)
    assert took == [False, False]
    monkeypatch.setattr(gm, "pick_tiles",
                        lambda rows, groups, k, n, dtype: (16, n))
    with moe.grouped_tally() as took:
        got, counts2 = moe.dropless_experts(h, weights, idx, w13, w2,
                                            first, share)
    assert took == [True, True]
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts2))
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2,
                               rtol=2e-2)
