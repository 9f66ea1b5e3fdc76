"""Ragged paged decode attention over a LATENT pool (MLA, absorbed form)
for TPU (Pallas): one query token per slot over that slot's latent rows,
reading only the pages that are live and each row once.

Pool layout — row pages ``[num_pages, page_size, width]``: a token's row
is ``[c | k_r]`` (``models/deepseek_v3.py``) padded with zeros to whole
lane tiles (576 -> 640: the device's tiled layout pads the minor
dimension so in any case, and a DMA slice must be whole tiles).  The key of every head is
the whole row and the value of every head its first ``rank`` columns, so
where ``paged_decode`` keeps a K and a V buffer and a block-diagonal
query, this kernel keeps ONE buffer and the query enters as a dense
``[heads, rank + rope]`` matrix: ``Q @ rows^T`` is ``[heads,
block_tokens]`` (the pad columns add nothing) and ``P @ rows[:, :rank]``
is ``[heads, rank]``, both read from the same buffered rows.

The walk is ``paged_decode``'s (``ops/pallas/paged_attention.py``): block
tables and lengths by scalar prefetch, the pool stays in HBM, one grid
step a slot, page-by-page DMA into a double buffer with the next block —
or the next slot's first — in flight while this one is computed.  A slot
of length 0 starts no DMA and returns zeros.

Numerics are those of ``latent_attend``
(``incubate/nn/paged_attention.py``): both contractions accumulate in
f32, f32 softmax statistics, probabilities rounded to the pool's dtype
once before the value product, the output once; the softmax is the
online form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["MLA_PAGED_DECODE_REVISION", "mla_paged_decode",
           "mla_paged_decode_supported"]

# folded into the serving AOT fingerprint: bump with any change here
MLA_PAGED_DECODE_REVISION = 1

_NEG_INF = -1e30
_BLOCK_TOKENS = 512


def mla_paged_decode_supported(dtype, rank, width, page_size):
    """Pools the kernel takes: bf16 or f32, the row and its value part
    whole lane tiles (``width`` and ``rank`` multiples of 128), a page of
    whole sublane tiles."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bfloat16:
        sublanes = 16
    elif dtype == jnp.float32:
        sublanes = 8
    else:
        return False
    return (rank % 128 == 0 and width % 128 == 0 and width > rank
            and page_size % sublanes == 0)


def _kernel(lens_ref, tables_ref, q_ref, pool_hbm, o_ref, buf, sems,
            first_buf, *, rank, page_size, pages_per_block, pages_per_seq,
            batch):
    b = pl.program_id(0)
    T = pages_per_block * page_size
    H = q_ref.shape[0]
    length = lens_ref[b]
    precision = (jax.lax.Precision.HIGHEST
                 if buf.dtype == jnp.float32 else None)

    def copy(bb, i, slot, j):
        page = tables_ref[bb * pages_per_seq + i * pages_per_block + j]
        rows = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
        return pltpu.make_async_copy(pool_hbm.at[page], buf.at[slot, rows],
                                     sems.at[slot])

    def for_live_pages(bb, i, slot, act):
        n = jnp.minimum(pages_per_block,
                        pl.cdiv(lens_ref[bb], page_size)
                        - i * pages_per_block)

        def body(j, _):
            act(copy(bb, i, slot, j))
            return ()

        jax.lax.fori_loop(0, n, body, ())

    def start(bb, i, slot):
        for_live_pages(bb, i, slot, lambda c: c.start())

    def wait(bb, i, slot):
        for_live_pages(bb, i, slot, lambda c: c.wait())

    @pl.when(b == 0)
    def _first_slot():
        first_buf[0] = 0
        buf[...] = jnp.zeros_like(buf)

    @pl.when(length == 0)
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _attend():
        buf0 = first_buf[0]
        nb = pl.cdiv(length, T)
        prev = jnp.maximum(b - 1, 0)
        prefetched = jnp.logical_and(b > 0, lens_ref[prev] > 0)

        @pl.when(jnp.logical_not(prefetched))
        def _():
            start(b, 0, buf0)

        q = q_ref[...]                                    # [H, W]
        col = jax.lax.broadcasted_iota(jnp.int32, (H, T), 1)

        def block(i, carry):
            m_prev, l_prev, acc = carry
            slot = (buf0 + i) % 2
            nxt = 1 - slot

            @pl.when(i + 1 < nb)
            def _():
                start(b, i + 1, nxt)

            succ = jnp.minimum(b + 1, batch - 1)

            @pl.when(jnp.logical_and(
                i + 1 == nb,
                jnp.logical_and(b + 1 < batch, lens_ref[succ] > 0)))
            def _():
                start(succ, 0, nxt)

            wait(b, i, slot)
            rows = buf[slot]                              # [T, W]
            lat = rows[:, :rank]
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)       # [H, T] f32
            s = jnp.where(i * T + col < length, s, _NEG_INF)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p.astype(lat.dtype), lat, (((1,), (0,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32)       # [H, rank]
            return m_new, l_new, acc

        _, l, acc = jax.lax.fori_loop(
            0, nb, block,
            (jnp.full((H, 1), _NEG_INF, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, rank), jnp.float32)))
        first_buf[0] = (buf0 + nb) % 2
        o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale",
                                             "pages_per_block", "interpret"))
def mla_paged_decode(q, pages, tables, lens, rank, scale,
                     pages_per_block=None, interpret=False):
    """``q [b, heads, w]`` (``[q~ | q_r | 0]``, unscaled) over each
    slot's first ``lens[b]`` rows of ``pages [N, page, w]`` -> ``[b,
    heads, rank]``.  Entries of ``tables [b, P]`` past a slot's live pages are
    never read; a slot of length 0 returns zeros."""
    b, h, w = q.shape
    n, page_size, width = pages.shape
    if width != w or not mla_paged_decode_supported(
            pages.dtype, rank, width, page_size):
        raise ValueError(
            f"mla_paged_decode: q {q.shape} rank {rank} does not fit the "
            f"latent pool {pages.shape} {pages.dtype}")
    pages_per_seq = tables.shape[1]
    ppb = pages_per_block or max(1, min(pages_per_seq,
                                        _BLOCK_TOKENS // page_size))
    T = ppb * page_size
    kernel = functools.partial(
        _kernel, rank=rank, page_size=page_size, pages_per_block=ppb,
        pages_per_seq=pages_per_seq, batch=b)
    qs = (q * scale).astype(pages.dtype)
    return pl.pallas_call(
        kernel,
        name="mla_paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((None, h, w), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, h, rank), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, T, w), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lens.astype(jnp.int32), tables.astype(jnp.int32).reshape(-1),
      qs, pages)
