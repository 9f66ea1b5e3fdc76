"""Paged KV-cache attention — pool-shared decode memory.

Motivated by Ragged Paged Attention (TPU inference kernel,
arXiv:2604.15464, see PAPERS.md) / vLLM's PagedAttention: instead of a
dense per-sequence [max_len] KV buffer, KV lives in a SHARED pool of
fixed-size pages and each sequence owns a small block table of page
ids. Memory scales with TOKENS IN FLIGHT, not batch * max_len, and
sequences grow by appending pages — no re-padding, no fragmentation.

TPU-native rendering (pure XLA, static shapes — the Pallas kernel form
of the paper is a later specialization; the semantics and the memory
model are here):

- pools:        k/v  [num_pages, n_head, page_size, head_dim]
- block table:  [batch, max_pages_per_seq] int32 page ids
- seq lens:     [batch] int32

Decode writes each sequence's new token into page
``table[b, len_b // page]`` at offset ``len_b % page`` (one scatter),
then attends over the sequence's gathered pages with a length mask.
Everything jits; the tape differentiates through the gathers if ever
needed (serving is no_grad).

Two layers of API:

- :class:`PageAllocator` — the HOST-side page bookkeeping alone
  (free list, per-slot ownership, leak guards). `paddle_tpu.serving`'s
  engine uses one allocator across all transformer layers and keeps the
  block tables and lengths on the host, while the device pools live as
  per-layer jnp arrays inside its compiled steps.
- pure jnp step functions (:func:`paged_decode_step`,
  :func:`paged_prefill_append`, :func:`paged_attend`) — trace-safe
  building blocks usable inside any jit/to_static program.  Which of
  them a cache kind composes is `paddle_tpu/serving/kv_pool.py`'s.

Two pool layouts, told apart by rank: the head-major 4-D pool above —
the XLA composition, the definition of the mathematics, the only path
on the CPU, under a multi-device mesh and for quantized pools — and ROW
pages ``[num_pages, page_size, n_head*head_dim]``, which decode through
the Pallas kernel :func:`paddle_tpu.ops.pallas.paged_attention.
paged_decode` and whose appends are row scatters XLA does in place.
:func:`row_pages_default` says which of the two a pool on this platform
should be; the step functions follow the pool they are handed.

Quantized pools: the per-page-scaled int8/fp8 variants of the step
functions live in :mod:`paddle_tpu.quantization.kv_cache` (same page
geometry, pools become ``(codes, scales)`` pairs, ~0.52x bytes/token
vs bf16) — the serving engine selects them via
``EngineConfig(kv_cache_dtype=)``; see docs/quantization.md.

Latent pools (MLA, ``models/deepseek_v3.py``): ONE pool a layer of row
pages ``[num_pages, page_size, width]`` — a token's row is ``[c | k_r]``
padded with zeros to whole lane tiles (:func:`latent_pool_width`; the
device's tiled layout pads the minor dimension so in any case), keys and
values are both read from it.  :func:`latent_prefill_append`
scatters a prompt's rows, :func:`latent_decode_step` appends one row a slot
and attends in the absorbed form (:func:`latent_attend` is the XLA
composition and the definition of the mathematics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "PageAllocator",
    "grouped_causal_attention",
    "grouped_paged_attend",
    "latent_attend",
    "latent_decode_path",
    "latent_decode_step",
    "latent_pool_width",
    "latent_prefill_append",
    "paged_attend",
    "paged_decode_step",
    "paged_prefill_append",
    "row_pages_default",
]


class PageAllocator:
    """Host-side page bookkeeping for a shared pool.

    Page 0 is the reserved GARBAGE page: released slots' block tables
    point at it, so a batch-wide append from an inactive row scatters
    into page 0 and can never corrupt a live sequence.  The allocator
    therefore hands out pages ``1 .. num_pages-1``.

    Invariant (the "no leak" contract): every page is either in the
    free list or owned by exactly one slot.  ``release`` is idempotent
    and guards against double-frees — an eviction mid-decode must
    restore the free list exactly.
    """

    def __init__(self, num_pages, batch, max_pages_per_seq):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.num_pages = int(num_pages)
        self.batch = int(batch)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self._free = list(range(num_pages - 1, 0, -1))
        self._free_set = set(self._free)
        self._owned = [[] for _ in range(batch)]

    @property
    def num_free_pages(self):
        return len(self._free)

    def owned_pages(self, b):
        return list(self._owned[b])

    def pages_needed(self, n_tokens, page_size):
        return -(-int(n_tokens) // int(page_size))

    def can_allocate(self, b, need):
        """Can slot `b` grow to `need` pages right now?"""
        have = len(self._owned[b])
        if need <= have:
            return True
        if need > self.max_pages_per_seq:
            return False
        return need - have <= len(self._free)

    def allocate(self, b, need):
        """Grow slot `b` to `need` pages; returns [(slot_idx, page_id)]
        newly assigned entries for the caller's block-table update."""
        have = len(self._owned[b])
        if need <= have:
            return []
        if need > self.max_pages_per_seq:
            raise ValueError(
                f"sequence {b} needs {need} pages but max_pages_per_seq "
                f"is {self.max_pages_per_seq}")
        if need - have > len(self._free):
            raise RuntimeError("paged KV cache: out of pages")
        assigned = []
        while len(self._owned[b]) < need:
            pg = self._free.pop()
            self._free_set.discard(pg)
            assigned.append((len(self._owned[b]), pg))
            self._owned[b].append(pg)
        return assigned

    def release(self, b):
        """Return slot `b`'s pages to the pool; returns the freed page
        ids.  Idempotent; raises on a double-free (a page already in the
        free list means the bookkeeping leaked somewhere)."""
        pages = self._owned[b]
        if not pages:
            return []
        dupes = [p for p in pages if p in self._free_set]
        if dupes:
            raise RuntimeError(
                f"paged KV cache: double-free of page(s) {dupes} "
                f"releasing slot {b}")
        self._free.extend(reversed(pages))
        self._free_set.update(pages)
        self._owned[b] = []
        return pages

    def check_invariant(self):
        """All pages accounted for exactly once (free or owned)."""
        owned = [p for o in self._owned for p in o]
        if len(set(owned)) != len(owned):
            raise RuntimeError("paged KV cache: page owned twice")
        if set(owned) & self._free_set:
            raise RuntimeError("paged KV cache: page both owned and free")
        if len(owned) + len(self._free) != self.num_pages - 1:
            raise RuntimeError(
                f"paged KV cache: leak — {len(owned)} owned + "
                f"{len(self._free)} free != {self.num_pages - 1}")
        return True


def row_pages_default(dtype, num_heads, head_dim, page_size):
    """Whether a plain pool of this geometry is stored as ROW pages and
    decoded by the Pallas kernel: on a TPU, outside any program GSPMD
    has to partition (``ops.pallas.kernel_default``), for the dtypes and
    page shapes the kernel takes.  Everything else keeps the head-major
    pool and the XLA composition."""
    from paddle_tpu.ops.pallas import kernel_default
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_decode_supported
    return kernel_default() and paged_decode_supported(
        dtype, num_heads, head_dim, page_size)


def paged_attend(q, k_pages, v_pages, tables, lens, page_size, scale=None):
    """Shared attention core: [b, h, 1, d] queries over each row's
    gathered pages, masked at `lens` — the read-only form, and the read
    of :func:`paged_decode_step` over a head-major pool."""
    b, h, one, d = q.shape
    sc = scale if scale is not None else 1.0 / float(d) ** 0.5
    k_seq = k_pages[tables]                               # [b, P, h, p, d]
    v_seq = v_pages[tables]
    P = tables.shape[1]
    k_seq = jnp.moveaxis(k_seq, 2, 1).reshape(b, h, P * page_size, d)
    v_seq = jnp.moveaxis(v_seq, 2, 1).reshape(b, h, P * page_size, d)
    pos = jnp.arange(P * page_size)
    mask = pos[None, None, None, :] < lens[:, None, None, None]
    # narrow (bf16/fp16/quantized-dequant) pools: accumulate both
    # contractions WIDE and round once (numlint NL101) — the value
    # matmul reduces over the ENTIRE cached history, the deepest sum in
    # the serving path.  f32 pools take the identical pre-fix jaxpr.
    narrow = q.dtype in (jnp.bfloat16, jnp.float16)
    pet = {"preferred_element_type": jnp.float32} if narrow else {}
    s = jnp.matmul(q * sc, jnp.swapaxes(k_seq, -1, -2),
                   **pet)                                 # [b, h, 1, Pp]
    s = jnp.where(mask, s.astype(jnp.float32),
                  jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.matmul(p, v_seq, **pet).astype(q.dtype)    # [b, h, 1, d]


def paged_decode_step(q, k_new, v_new, k_pages, v_pages, tables, lens,
                      page_size, scale=None):
    """Pure decode step WITHOUT length bookkeeping: write each row's new
    token at position ``lens[b]``, attend over ``lens[b]+1`` tokens.
    Returns (out, k_pages, v_pages); the caller owns the lens update —
    a multi-layer engine calls this once per layer with the SAME lens
    and advances lens once per step.

    Row pages (3-D pools) take the Pallas kernel: the new rows are
    scattered first — in place, the kernel is the pool's only reader —
    and the kernel then reads the ``ceil((lens[b]+1)/page_size)`` live
    pages of each row instead of the whole table width.
    """
    lens = lens.astype(jnp.int32)
    page_idx = lens // page_size
    offs = lens % page_size
    page_ids = jnp.take_along_axis(tables, page_idx[:, None],
                                   axis=1)[:, 0]          # [b]
    if k_pages.ndim == 3:
        from paddle_tpu.ops.pallas import on_tpu
        from paddle_tpu.ops.pallas.paged_attention import paged_decode
        b, hd = q.shape[0], k_pages.shape[-1]
        k_pages = k_pages.at[page_ids, offs].set(
            k_new.reshape(b, hd).astype(k_pages.dtype))
        v_pages = v_pages.at[page_ids, offs].set(
            v_new.reshape(b, hd).astype(v_pages.dtype))
        out = paged_decode(q, k_pages, v_pages, tables, lens + 1,
                           scale=scale, interpret=not on_tpu())
        return out, k_pages, v_pages
    # scatter each row's token into its page/offset — the pool-dtype
    # narrowing is EXPLICIT (numlint-visible cast, and jax deprecates
    # the implicit f32->bf16 scatter cast) rather than hidden in the
    # scatter
    kt = jnp.swapaxes(k_new, 1, 2)[:, 0].astype(k_pages.dtype)
    vt = jnp.swapaxes(v_new, 1, 2)[:, 0].astype(v_pages.dtype)
    k_pages = k_pages.at[page_ids, :, offs].set(kt)
    v_pages = v_pages.at[page_ids, :, offs].set(vt)
    out = paged_attend(q, k_pages, v_pages, tables, lens + 1,
                       page_size, scale)
    return out, k_pages, v_pages


def paged_prefill_append(k_new, v_new, k_pages, v_pages, tables, lens,
                         page_size):
    """Batched multi-sequence prompt scatter (pure): token t of row b
    lands in page ``tables[b, t // page_size]`` at offset
    ``t % page_size``; positions >= lens[b] go to the garbage page 0.

    k_new/v_new: [b, h, S, d].  Returns (k_pages, v_pages).  Row pages
    (3-D pools) take each token's heads as one row.
    """
    b, h, S, d = k_new.shape
    t = jnp.arange(S, dtype=jnp.int32)
    page_idx = t // page_size                              # [S]
    offs = t % page_size                                   # [S]
    # clamp in case S spans more pages than the table width — the
    # valid-mask below routes those to garbage anyway
    page_idx = jnp.minimum(page_idx, tables.shape[1] - 1)
    page_ids = tables[:, page_idx]                         # [b, S]
    valid = t[None, :] < lens[:, None].astype(jnp.int32)
    page_ids = jnp.where(valid, page_ids, 0)
    flat_pages = page_ids.reshape(-1)                      # [b*S]
    flat_offs = jnp.tile(offs, b)
    if k_pages.ndim == 3:
        kt = jnp.swapaxes(k_new, 1, 2).reshape(b * S, h * d)
        vt = jnp.swapaxes(v_new, 1, 2).reshape(b * S, h * d)
        return (k_pages.at[flat_pages, flat_offs].set(
                    kt.astype(k_pages.dtype)),
                v_pages.at[flat_pages, flat_offs].set(
                    vt.astype(v_pages.dtype)))
    # explicit pool-dtype narrowing (see paged_decode_step)
    kt = jnp.swapaxes(k_new, 1, 2).reshape(b * S, h, d) \
        .astype(k_pages.dtype)                             # [b*S, h, d]
    vt = jnp.swapaxes(v_new, 1, 2).reshape(b * S, h, d) \
        .astype(v_pages.dtype)
    k_pages = k_pages.at[flat_pages, :, flat_offs].set(kt)
    v_pages = v_pages.at[flat_pages, :, flat_offs].set(vt)
    return k_pages, v_pages


def grouped_causal_attention(q, k, v, scale, block=1, window=None):
    """``q [b, s, H, d]`` over ``k`` / ``v [b, s, H_kv, d]``, query head
    ``i`` reading K/V head ``i // (H / H_kv)``; ``scale`` multiplies the
    scores; causal, float32 softmax, both contractions accumulated wide.
    ``block`` > 1 makes the mask causal over BLOCKS of that many positions:
    ``i`` sees ``j`` iff ``j // block <= i // block`` (its whole block and
    every earlier one).  ``window``: ``i`` sees ``j`` iff ``i - window < j
    <= i`` (a sliding window; with ``block`` 1 only)."""
    b, s, H, d = q.shape
    g = H // k.shape[2]
    qg = q.reshape(b, s, k.shape[2], g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if window is not None:
        gap = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        seen = (gap >= 0) & (gap < window)
    elif block == 1:
        seen = jnp.tril(jnp.ones((s, s), jnp.bool_))
    else:
        at = jnp.arange(s) // block
        seen = at[None, :] <= at[:, None]
    scores = jnp.where(seen, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, H, d).astype(q.dtype)


def grouped_paged_attend(q, k_pages, v_pages, tables, lens, scale):
    """``q [b, rows, H, d]`` over each slot's first ``lens[b]`` positions
    of ROW pages ``[N, page, H_kv*d]`` (the whole table gathered as rows
    ``[b, positions, H_kv, d]``, masked at ``lens``), query head ``i``
    reading K/V head ``i // (H / H_kv)``; ``scale`` multiplies the scores;
    float32 softmax, both contractions accumulated wide -> ``[b, rows, H,
    d]``.  A block's rows see every position under ``lens`` (the caller
    counts the block in).  The XLA composition of a grouped-query cache's
    decode read, and the definition the kernel ``grouped_paged_decode`` is
    held to."""
    b, rows, H, d = q.shape
    hk = k_pages.shape[-1] // d
    keys = k_pages[tables].reshape(b, -1, hk, d)
    vals = v_pages[tables].reshape(b, -1, hk, d)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q.reshape(b, rows, hk, H // hk, d),
        keys, preferred_element_type=jnp.float32) * scale
    live = jnp.arange(keys.shape[1])[None, :] < lens[:, None]
    scores = jnp.where(live[:, None, None, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vals,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, rows, H, d).astype(q.dtype)


# --------------------------------------------------------- latent pools
def latent_pool_width(row_width):
    """A latent pool's minor dimension: the row, padded to whole lane
    tiles of 128."""
    return -(-int(row_width) // 128) * 128


def latent_decode_path(dtype, rank, width, page_size):
    """What decode attention over a latent pool of this geometry runs on
    this platform: ``"mla_paged_decode"`` (the Pallas kernel: on a TPU,
    outside any program GSPMD has to partition, for the pools it takes)
    or ``"xla"`` (:func:`latent_attend`)."""
    from paddle_tpu.ops.pallas import kernel_default
    from paddle_tpu.ops.pallas.mla_paged_attention import \
        mla_paged_decode_supported
    if kernel_default() and mla_paged_decode_supported(dtype, rank, width,
                                                       page_size):
        return "mla_paged_decode"
    return "xla"


def _pad_to(x, width):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def latent_prefill_append(rows, pages, tables, lens, page_size):
    """Scatter a prompt's latent rows: row t of sequence b lands in page
    ``tables[b, t // page_size]`` at offset ``t % page_size``; positions
    >= lens[b] go to the garbage page 0.  ``rows [b, S, w]``, ``pages [N,
    page, W >= w]``.  Returns the pool."""
    rows = _pad_to(rows, pages.shape[-1])
    b, S, w = rows.shape
    t = jnp.arange(S, dtype=jnp.int32)
    page_idx = jnp.minimum(t // page_size, tables.shape[1] - 1)
    page_ids = jnp.where(t[None, :] < lens[:, None].astype(jnp.int32),
                         tables[:, page_idx], 0)             # [b, S]
    return pages.at[page_ids.reshape(-1), jnp.tile(t % page_size, b)].set(
        rows.reshape(b * S, w).astype(pages.dtype))


def latent_attend(q, pages, tables, lens, rank, page_size, scale):
    """Absorbed latent attention, the XLA composition: ``q [b, H, W]``
    (``[q~ | q_r | 0]``, unscaled) over each slot's first ``lens[b]`` rows
    of ``pages [N, page, W]``; scores over the whole row, the weighted sum
    over its first ``rank`` columns -> ``[b, H, rank]``.  Both
    contractions accumulate in float32, the softmax is float32, the
    probabilities are rounded to the pool's dtype once."""
    b = q.shape[0]
    P = tables.shape[1]
    seq = pages[tables].reshape(b, P * page_size, pages.shape[-1])
    s = jnp.einsum("bhw,btw->bht", (q * scale).astype(pages.dtype), seq,
                   preferred_element_type=jnp.float32)
    live = jnp.arange(P * page_size)[None, None, :] < lens[:, None, None]
    s = jnp.where(live, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(pages.dtype)
    return jnp.einsum("bht,btr->bhr", p, seq[..., :rank],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def latent_decode_step(q, row_new, pages, tables, lens, rank, page_size,
                       scale):
    """One decode step over a latent pool WITHOUT length bookkeeping:
    write each slot's new row at position ``lens[b]`` (in place — a row
    scatter), then attend over ``lens[b] + 1`` rows.  ``q [b, H, w]``,
    ``row_new [b, w]``, ``pages [N, page, W >= w]``.  Returns (u ``[b, H,
    rank]``, pool).  The attention is what :func:`latent_decode_path`
    says: the Pallas kernel ``mla_paged_decode``, which reads only the
    live pages and each row once, or :func:`latent_attend`."""
    lens = lens.astype(jnp.int32)
    width = pages.shape[-1]
    q, row_new = _pad_to(q, width), _pad_to(row_new, width)
    page_ids = jnp.take_along_axis(tables, (lens // page_size)[:, None],
                                   axis=1)[:, 0]
    pages = pages.at[page_ids, lens % page_size].set(
        row_new.astype(pages.dtype))
    if latent_decode_path(pages.dtype, rank, width, page_size) != "xla":
        from paddle_tpu.ops.pallas.mla_paged_attention import \
            mla_paged_decode
        return mla_paged_decode(q, pages, tables, lens + 1, rank=rank,
                                scale=scale), pages
    return latent_attend(q, pages, tables, lens + 1, rank, page_size,
                         scale), pages
