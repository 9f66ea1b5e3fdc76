"""Ragged paged decode attention for TPU (Pallas): one query token per
slot over that slot's pages, reading only the pages that are live.

Pool layout — "row pages": ``[num_pages, page_size, heads * head_dim]``.
A token's K (or V) of all heads is one lane-dense row, a page is one
contiguous ``page_size x (heads*head_dim)`` tile (32 KB at 16 x 1024
bf16), and the device's default layout for that shape is row-major.  The
head-major pool ``[num_pages, heads, page_size, head_dim]`` of the XLA
composition (``incubate/nn/paged_attention.py``) is NOT: with a minor
dim of 64 the TPU's default layout puts the PAGE axis minor-most
(``{0,3,2,1}``), so every reader and writer first re-lays the whole
pool out — the ``copy bf16[4097,16,16,64]`` that set the decode step
before this kernel existed (PERF.md §6, PR 27).
:func:`to_row_pages` / :func:`from_row_pages` convert between the two.

The kernel (``name="paged_decode"``): block tables and lengths arrive
by scalar prefetch, the pools stay in HBM, and each grid step (one
slot) walks ``ceil(len / block_tokens)`` compute blocks, DMA-ing page
by page into a double buffer — the next block, or the next slot's
first, is in flight while this one is computed.  A slot of length 0
starts no DMA and returns zeros.  All heads share one MXU product per
block: the query enters as a block-diagonal ``[heads, heads*head_dim]``
matrix, so ``Qbd @ K^T`` is ``[heads, block_tokens]`` with no per-head
lane slicing; the value product's ``[heads, heads*head_dim]`` result
carries each head's output on its diagonal block.

Numerics are those of ``paged_attend``: both contractions accumulate in
f32, the softmax statistics are f32, probabilities are rounded to the
pool's dtype once before the value product and the output once.  The
softmax is the online form, so the rounding points differ from the
one-pass XLA composition by the usual flash-attention amount.

``grouped_paged_decode`` (``name="grouped_paged_decode"``) walks the same
way for GROUPED queries: ``H_q`` query heads over a pool of ``H_kv`` K/V
heads, query head ``i`` reading K/V head ``i // (H_q / H_kv)``, at the
model's own score scale (``serving.kv_pool.GroupedKV``).  Its query is
block-diagonal at the pool's width, ``[H_q, H_kv*d]``; row ``i``'s output
is its K/V head's lane block of the value product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["GROUPED_PAGED_DECODE_REVISION", "PAGED_DECODE_REVISION",
           "from_row_pages", "grouped_paged_decode", "paged_decode",
           "paged_decode_supported", "to_row_pages"]

# Names the compiled programs that hold this kernel: folded into the
# serving AOT fingerprint, so bump it with any change to the kernel or
# to the row-page layout — a stored executable is never shared across.
PAGED_DECODE_REVISION = 1
# the same, for ``grouped_paged_decode``
GROUPED_PAGED_DECODE_REVISION = 1

_NEG_INF = -1e30
# one K (or V) buffer holds at most this many bytes; two buffers each
_BLOCK_BYTES = 512 * 1024


def to_row_pages(pages):
    """``[N, heads, page, d]`` (head-major) -> ``[N, page, heads*d]``;
    a jax or a numpy array, returned in kind."""
    n, h, p, d = pages.shape
    return pages.swapaxes(1, 2).reshape(n, p, h * d)


def from_row_pages(pages, num_heads):
    """``[N, page, heads*d]`` -> ``[N, heads, page, d]`` (head-major);
    a jax or a numpy array, returned in kind."""
    n, p, hd = pages.shape
    return pages.reshape(n, p, num_heads, hd // num_heads).swapaxes(1, 2)


def paged_decode_supported(dtype, num_heads, head_dim, page_size):
    """Pool geometries the kernel takes: bf16 or f32, a lane-dense row
    (``heads*head_dim`` a multiple of 128) and a page that is whole
    sublane tiles of the dtype (16 rows of bf16, 8 of f32)."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bfloat16:
        sublanes = 16
    elif dtype == jnp.float32:
        sublanes = 8
    else:
        return False
    return ((num_heads * head_dim) % 128 == 0
            and page_size % sublanes == 0)


def _pages_per_block(page_size, row_bytes, max_pages):
    """Pages per compute block: as many as fit `_BLOCK_BYTES`, at least
    one, no more than a table row holds."""
    return max(1, min(max_pages, _BLOCK_BYTES // (page_size * row_bytes)))


def _walk(lens_ref, tables_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems,
          first_buf, query, *, page_size, pages_per_block, pages_per_seq,
          batch, scale=None):
    """The ragged walk both decode kernels share, for grid step ``b`` (one
    slot): the slot's live pages DMA-ed block by block into the double
    buffer, an online softmax over them, ``o_ref`` written.  ``query()``
    is called once the slot is known to be live and returns ``(qbd,
    finish)``: the ``[rows, width]`` query matrix whose product with a
    block of rows gives ``[rows, block_tokens]`` scores, and
    ``finish(acc, l)``, the slot's output from the f32 value product
    ``acc [rows, width]`` and the softmax sums ``l [rows, 1]``.  ``scale``
    (static) multiplies the f32 scores; None leaves them as the product
    gives them."""
    b = pl.program_id(0)
    T = pages_per_block * page_size
    length = lens_ref[b]
    # f32 pools: true f32 products.  Mosaic's default is one bf16 pass,
    # 4000 times further from the truth than the XLA composition, whose
    # M=1 products run as f32 multiply-reduces (PERF.md §6, PR 27)
    precision = (jax.lax.Precision.HIGHEST
                 if kbuf.dtype == jnp.float32 else None)

    def block_copies(bb, i, buf, j):
        page = tables_ref[bb * pages_per_seq + i * pages_per_block + j]
        rows = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf, rows],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf, rows],
                                      sems.at[1, buf]))

    def for_live_pages(bb, i, buf, act):
        """`act` on the copies of block i's LIVE pages only: the tail
        block of a slot leaves the rest of its buffer as it was (masked
        below; finite, since the buffers start zeroed)."""
        n = jnp.minimum(pages_per_block,
                        pl.cdiv(lens_ref[bb], page_size)
                        - i * pages_per_block)

        def body(j, _):
            for c in block_copies(bb, i, buf, j):
                act(c)
            return ()

        jax.lax.fori_loop(0, n, body, ())

    def start(bb, i, buf):
        for_live_pages(bb, i, buf, lambda c: c.start())

    def wait(bb, i, buf):
        for_live_pages(bb, i, buf, lambda c: c.wait())

    @pl.when(b == 0)
    def _first_slot():
        first_buf[0] = 0
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(length == 0)
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _attend():
        buf0 = first_buf[0]
        nb = pl.cdiv(length, T)
        # the slot before this one, if it ran, put block 0 in flight
        prev = jnp.maximum(b - 1, 0)
        prefetched = jnp.logical_and(b > 0, lens_ref[prev] > 0)

        @pl.when(jnp.logical_not(prefetched))
        def _():
            start(b, 0, buf0)

        qbd, finish = query()
        R, W = qbd.shape
        col = jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)

        def block(i, carry):
            m_prev, l_prev, acc = carry
            buf = (buf0 + i) % 2
            nxt = 1 - buf

            @pl.when(i + 1 < nb)
            def _():
                start(b, i + 1, nxt)

            succ = jnp.minimum(b + 1, batch - 1)

            @pl.when(jnp.logical_and(
                i + 1 == nb,
                jnp.logical_and(b + 1 < batch, lens_ref[succ] > 0)))
            def _():
                start(succ, 0, nxt)

            wait(b, i, buf)
            k = kbuf[buf]                                 # [T, W]
            v = vbuf[buf]
            s = jax.lax.dot_general(
                qbd, k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)       # [R, T] f32
            if scale is not None:
                s = s * scale
            s = jnp.where(i * T + col < length, s, _NEG_INF)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32)       # [R, W]
            return m_new, l_new, acc

        _, l, acc = jax.lax.fori_loop(
            0, nb, block,
            (jnp.full((R, 1), _NEG_INF, jnp.float32),
             jnp.zeros((R, 1), jnp.float32),
             jnp.zeros((R, W), jnp.float32)))
        first_buf[0] = (buf0 + nb) % 2
        o_ref[...] = finish(acc, l).astype(o_ref.dtype)


def _decode_kernel(lens_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, sems, first_buf, *, num_heads, head_dim,
                   **walk):
    H, D = num_heads, head_dim

    def query():
        # block-diagonal query: row h holds head h's d lanes, so one
        # product over the full row serves every head
        row = jax.lax.broadcasted_iota(jnp.int32, (H, H * D), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (H, H * D), 1)
        diag = lane // D == row
        # (selected in f32: Mosaic has no 16-bit mask relayout)
        q = q_ref[...]                                    # [1, H*D]
        qbd = jnp.where(diag, jnp.broadcast_to(
            q.astype(jnp.float32), (H, H * D)), 0.0).astype(q.dtype)

        def finish(acc, l):
            out = jnp.where(diag, acc / l, 0.0)
            return jnp.sum(out, axis=0, keepdims=True)

        return qbd, finish

    _walk(lens_ref, tables_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems,
          first_buf, query, **walk)


def _grouped_decode_kernel(lens_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
                           kbuf, vbuf, sems, first_buf, *, kv_heads,
                           head_dim, **walk):
    D = head_dim

    def query():
        qbd = q_ref[...]                                  # [H_q, H_kv*D]

        def finish(acc, l):
            # row i's output is its own K/V head's lane block
            out = acc / l
            head = jax.lax.broadcasted_iota(
                jnp.int32, (out.shape[0], D), 0) // (out.shape[0] // kv_heads)
            res = out[:, :D]
            for h in range(1, kv_heads):
                res = jnp.where(head == h, out[:, h * D:(h + 1) * D], res)
            return res                                    # [H_q, D]

        return qbd, finish

    _walk(lens_ref, tables_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems,
          first_buf, query, **walk)


def _paged_call(kernel, name, lens, tables, q, k_pages, v_pages, q_block,
                out_block, out_shape, pages_per_block, interpret):
    """The ``pallas_call`` of a walk over row pages: tables and lengths by
    scalar prefetch, the pools in HBM, one grid step a slot, two
    buffers of ``pages_per_block`` pages for K and two for V."""
    T = pages_per_block * k_pages.shape[1]
    hd = k_pages.shape[-1]
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(q.shape[0],),
            in_specs=[q_block, any_space, any_space],
            out_specs=out_block,
            scratch_shapes=[
                pltpu.VMEM((2, T, hd), k_pages.dtype),
                pltpu.VMEM((2, T, hd), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lens.astype(jnp.int32), tables.astype(jnp.int32).reshape(-1),
      q, k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_block",
                                             "interpret"))
def paged_decode(q, k_pages, v_pages, tables, lens, scale=None,
                 pages_per_block=None, interpret=False):
    """Attention of ``q [b, heads, 1, d]`` over each slot's first
    ``lens[b]`` cached tokens -> ``[b, heads, 1, d]``.

    ``k_pages`` / ``v_pages``: row pages ``[N, page, heads*d]``;
    ``tables [b, P]`` int32 page ids; ``lens [b]`` int32.  Entries of
    ``tables`` past a slot's live pages are never read.  A slot of
    length 0 returns zeros.  ``pages_per_block`` (default: what fills a
    512 KB buffer) is the compute block, in pages.
    """
    b, h, one, d = q.shape
    n, page_size, hd = k_pages.shape
    if one != 1 or hd != h * d or v_pages.shape != k_pages.shape:
        raise ValueError(
            f"paged_decode: q {q.shape} does not fit row pages "
            f"{k_pages.shape} / {v_pages.shape}")
    if not paged_decode_supported(k_pages.dtype, h, d, page_size):
        raise ValueError(
            f"paged_decode: unsupported pool {k_pages.dtype} heads={h} "
            f"head_dim={d} page_size={page_size}")
    pages_per_seq = tables.shape[1]
    sc = scale if scale is not None else 1.0 / float(d) ** 0.5
    ppb = pages_per_block or _pages_per_block(
        page_size, hd * k_pages.dtype.itemsize, pages_per_seq)
    kernel = functools.partial(
        _decode_kernel, num_heads=h, head_dim=d, page_size=page_size,
        pages_per_block=ppb, pages_per_seq=pages_per_seq, batch=b)
    row = pl.BlockSpec((None, 1, hd), lambda i, *_: (i, 0, 0))
    # the same pre-scaling, in q's dtype, as paged_attend
    qs = (q * sc).astype(k_pages.dtype).reshape(b, 1, hd)
    out = _paged_call(kernel, "paged_decode", lens, tables, qs, k_pages,
                      v_pages, row, row,
                      jax.ShapeDtypeStruct((b, 1, hd), q.dtype), ppb,
                      interpret)
    return out.reshape(b, h, 1, d)


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_block",
                                             "interpret"))
def grouped_paged_decode(q, k_pages, v_pages, tables, lens, scale,
                         pages_per_block=None, interpret=False):
    """Grouped-query attention of ``q [b, H_q, d]`` over each slot's
    first ``lens[b]`` cached positions -> ``[b, H_q, d]``: query head
    ``i`` reads K/V head ``i // (H_q / H_kv)``, scores ``scale x q.k``.

    ``k_pages`` / ``v_pages``: row pages ``[N, page, H_kv*d]``; ``tables
    [b, P]`` int32 page ids; ``lens [b]`` int32.  Entries of ``tables``
    past a slot's live pages are never read.  A slot of length 0 returns
    zeros.  ``pages_per_block`` (default: what fills a 512 KB buffer) is
    the compute block, in pages.

    The walk is :func:`paged_decode`'s.  The query enters block-diagonal
    at the pool's width: row ``i`` of ``[H_q, H_kv*d]`` holds head ``i``'s
    ``d`` lanes in its K/V head's lane block, so ``Q @ K^T`` is ``[H_q,
    block_tokens]`` and row ``i`` of ``P @ V`` carries its output in the
    same lane block.  The products do ``H_kv`` times the FLOPs attention
    needs and stay bound by bytes (~28 FLOPs a byte at 28 x 512 in bf16).
    Numerics are ``GroupedKV.decode``'s: f32 products, scale and
    statistics, probabilities rounded to the pool's dtype once before the
    value product, the output once; the softmax is the online form.
    """
    b, hq, d = q.shape
    n, page_size, hd = k_pages.shape
    hk = hd // d
    if (hd % d or hq % hk or tables.shape[0] != b
            or v_pages.shape != k_pages.shape):
        raise ValueError(
            f"grouped_paged_decode: q {q.shape} does not fit row pages "
            f"{k_pages.shape} / {v_pages.shape}")
    if not paged_decode_supported(k_pages.dtype, hk, d, page_size):
        raise ValueError(
            f"grouped_paged_decode: unsupported pool {k_pages.dtype} "
            f"kv_heads={hk} head_dim={d} page_size={page_size}")
    pages_per_seq = tables.shape[1]
    ppb = pages_per_block or _pages_per_block(
        page_size, hd * k_pages.dtype.itemsize, pages_per_seq)
    kernel = functools.partial(
        _grouped_decode_kernel, kv_heads=hk, head_dim=d,
        page_size=page_size, pages_per_block=ppb,
        pages_per_seq=pages_per_seq, batch=b, scale=float(scale))
    # row i of the query carries head i in lane block i // (H_q / H_kv)
    block = jnp.arange(hd)[None, :] // d == jnp.arange(hq)[:, None] // (
        hq // hk)
    qbd = jnp.where(block, jnp.tile(q, (1, 1, hk)),
                    0).astype(k_pages.dtype)
    return _paged_call(
        kernel, "grouped_paged_decode", lens, tables, qbd, k_pages, v_pages,
        pl.BlockSpec((None, hq, hd), lambda i, *_: (i, 0, 0)),
        pl.BlockSpec((None, hq, d), lambda i, *_: (i, 0, 0)),
        jax.ShapeDtypeStruct((b, hq, d), q.dtype), ppb, interpret)
