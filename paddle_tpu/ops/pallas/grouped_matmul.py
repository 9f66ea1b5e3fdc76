"""Grouped matrix product for TPU (Pallas): the expert products of
``distributed.moe.dropless_experts``, ``out[r] = lhs[r] @ rhs[g(r)]``
where the rows of ``lhs`` are sorted by group and ``counts`` gives the
group sizes (``jax.lax.ragged_dot``'s contract).

The walk is the one ``jax.experimental.pallas.ops.tpu.megablox.gmm``
uses.  Rows are cut into tiles of ``tm``; a VISIT is one (row tile, group)
pair whose rows meet, so a tile that holds the tail of one group and the
head of the next is visited twice and a group of many tiles once a tile.
The grid is ``(N / tn, visits)``: a step multiplies the visit's row tile
``[tm, K]`` by its group's ``[K, tn]`` weight tile and stores the rows of
that group alone (the others keep what an earlier visit of the tile
wrote: visits of one tile are consecutive, so its output block stays in
VMEM between them).  Each group's weight tiles are read once for each row
tile that holds its rows — at a few rows a group, once — and a group with
no rows is not visited: its weights are never read.  The visit list is
built on the device from ``counts`` and reaches the index maps by scalar
prefetch; the grid is static (``tiles + groups - 1`` visits, the most
there can be) and the steps past the last visit repeat its block indices
(no DMA) and run no body.

Rows past the last group (padding, or the rows a holder of a SHARE of the
experts does not hold) are not this product's: a visited tile writes 0
there, a tile no group reaches is not written (as ``ragged_dot`` on a TPU
writes nothing there); callers clear them.

Operands in their own dtype, the product and the result in float32.  The
backward is XLA's (``ragged_dot``'s own VJP), so a gradient through an
expert layer is what it was.  The function around ``pallas_call`` is
``jax.jit``ted with the tiles static: the layers of a program trace and
lower the kernel once a shape.

``pick_tiles`` is the one place the path is chosen, from what the call
can see (rows, groups, K, N, dtype): the kernel over the rows a group
that ``tools/sweep_grouped.py`` measured it faster than ``ragged_dot`` on
a TPU v5e, ``ragged_dot`` past them and wherever
``ops.pallas.kernel_default()`` says no kernel (the CPU, a multi-device
GSPMD program).  Its docstring says on which rows of the sweep each
choice rests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["GROUPED_MATMUL_REVISION", "grouped_matmul", "pick_tiles"]

# folded into the serving AOT fingerprint of an engine with expert
# layers: bump with any change to the kernel or to `pick_tiles`
GROUPED_MATMUL_REVISION = 1

_NN = (((1,), (0,)), ((), ()))      # a b


def _round_up(n, m):
    return -(-n // m) * m


def _visits(counts, rows, tm):
    """The visit list of ``rows`` rows (a multiple of ``tm``) in groups of
    ``counts``: (group offsets ``[G+1]``, each visit's group and row tile
    ``[V]``, the number of real visits ``[1]``), all int32.  ``V = tiles +
    G - 1``; the entries past the real visits repeat the last one."""
    groups = counts.shape[0]
    tiles = rows // tm
    size = tiles + groups - 1
    ends = jnp.cumsum(counts)
    starts = ends - counts
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    spans = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    real = jnp.sum(spans)
    # visit v belongs to the group whose visits it falls among: the
    # number of groups whose visits end at or before it (a [V, G] compare,
    # one fusion; a search would be a loop)
    at = jnp.minimum(jnp.arange(size, dtype=jnp.int32),
                     jnp.maximum(real - 1, 0))
    done = jnp.cumsum(spans)
    begin = done - spans
    gid = jnp.sum(done[None, :] <= at[:, None], axis=1, dtype=jnp.int32)
    gid = jnp.minimum(gid, groups - 1)
    mid = jnp.clip(first[gid] + at - begin[gid], 0, tiles - 1)
    return offsets, gid, mid.astype(jnp.int32), real.reshape(1)


def _kernel(offsets, gid, mid, real, lhs_ref, rhs_ref, out_ref, *, tm):
    v = pl.program_id(1)

    @pl.when(v < real[0])
    def _():
        g = gid[v]
        lo, hi = offsets[g], offsets[g + 1]
        tile = mid[v]

        # the tile's first visit: rows no group of it holds read 0
        @pl.when(jnp.logical_or(v == 0, tile != mid[jnp.maximum(v - 1, 0)]))
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        prod = jax.lax.dot_general(lhs_ref[...], rhs_ref[...], _NN,
                                   preferred_element_type=jnp.float32)
        row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, prod.shape, 0)
        out_ref[...] = jnp.where((row >= lo) & (row < hi), prod,
                                 out_ref[...])


def _vmem_bytes(tm, k, tn, itemsize):
    """Double-buffered row tile, weight tile and output tile, and the
    product's f32 temporary."""
    return 2 * (tm * k + k * tn) * itemsize + 3 * tm * tn * 4


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _grouped_matmul(lhs, rhs, counts, tm, tn, interpret):
    m, k = lhs.shape
    groups, _, n = rhs.shape
    rows = _round_up(max(m, 1), tm)
    x = jnp.pad(lhs, ((0, rows - m), (0, 0))) if rows != m else lhs
    meta = _visits(counts.astype(jnp.int32), rows, tm)
    size = rows // tm + groups - 1
    itemsize = jnp.dtype(rhs.dtype).itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        name="grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, size),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, v, offs, gid, mid, real: (mid[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, offs, gid, mid, real:
                             (gid[v], 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, offs, gid, mid, real: (mid[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(tm, k, tn, itemsize) + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(groups * k * n + m * k) * itemsize + m * n * 4),
        interpret=interpret,
    )(*meta, x, rhs)
    return out[:m] if rows != m else out


def _ragged_dot(lhs, rhs, counts):
    return jax.lax.ragged_dot(lhs, rhs, counts,
                              preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _kernel_product(lhs, rhs, counts, tm, tn, interpret):
    return _grouped_matmul(lhs, rhs, counts, tm, tn, interpret)


def _kernel_product_fwd(lhs, rhs, counts, tm, tn, interpret):
    return (_grouped_matmul(lhs, rhs, counts, tm, tn, interpret),
            (lhs, rhs, counts))


def _kernel_product_bwd(tm, tn, interpret, res, g):
    lhs, rhs, counts = res
    _, vjp = jax.vjp(lambda a, b: _ragged_dot(a, b, counts), lhs, rhs)
    return (*vjp(g), None)


_kernel_product.defvjp(_kernel_product_fwd, _kernel_product_bwd)


# the sweep's largest rows a group (granite4h_serve_chat's 1,024-token
# prefill: 10,240 rows over 36 experts, 284 a group) and a little room:
# past it no row was measured and XLA's product is kept
_MEASURED_ROWS_A_GROUP = 320
_ROW_TILE = 128
# a weight tile larger than this is cut along N (VMEM holds two)
_WEIGHT_TILE_BYTES = 24 << 20


def pick_tiles(rows, groups, k, n, dtype, kernel=None):
    """``(tm, tn)`` for the Pallas kernel, or None for ``ragged_dot``: the
    one place the path of a grouped product is chosen, from what the call
    can see.  ``kernel``: whether a Pallas kernel may run at all
    (``ops.pallas.kernel_default()`` when None: a TPU, and not a
    multi-device GSPMD program).

    The rows of ``tools/sweep_grouped.py`` on a TPU v5e (PERF.md §6, PR
    39), every grouped product the three expert cells trace, 1.5 to 284
    rows a group:

    - the kernel is faster than ``ragged_dot`` at every one: 2.3x at 8 rows
      a group (the block pass: 1.01 against 2.29 ms, 89% of the HBM peak
      against 39%), 1.1-1.6x at Kanana's and Granite's decode, 1.8x at
      284 rows a group (Granite's 1,024-token prefill), where the MXU is
      34% busy; past the measured rows a group, ``ragged_dot``;
    - rows in tiles of 128: at most 3% from the best row tile at every
      shape (64 adds visits, 256 and 512 add MXU work: 512 loses 40-70% in
      the prefills);
    - N whole: halving it costs 2-10% (twice the steps, each weight tile
      half as long); a weight tile past 24 MB is halved so that VMEM
      holds two (no cell's is: Granite's ``[4096, 1536]`` is 12.6 MB).
    """
    if kernel is None:
        from paddle_tpu.ops.pallas import kernel_default
        kernel = kernel_default()
    if not kernel or rows == 0 or groups == 0:
        return None
    if rows > _MEASURED_ROWS_A_GROUP * groups:
        return None
    tn = n
    itemsize = jnp.dtype(dtype).itemsize
    while k * tn * itemsize > _WEIGHT_TILE_BYTES and tn % 256 == 0:
        tn //= 2
    return _ROW_TILE, tn


def grouped_matmul(lhs, rhs, counts, tiles=None, interpret=None):
    """``lhs [m, K]`` (rows sorted by group) times ``rhs [G, K, N]`` in
    groups of ``counts [G]`` -> ``[m, N]`` float32.  ``tiles``: ``(tm,
    tn)`` runs the Pallas kernel (``pick_tiles`` chooses; interpreted
    off a TPU unless ``interpret`` says), None ``jax.lax.ragged_dot``."""
    if tiles is None:
        return _ragged_dot(lhs, rhs, counts)
    if interpret is None:
        from paddle_tpu.ops.pallas import on_tpu
        interpret = not on_tpu()
    tm, tn = tiles
    return _kernel_product(lhs, rhs, counts, int(tm), int(tn),
                           bool(interpret))
