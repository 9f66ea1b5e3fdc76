"""Paged KV-cache attention (incubate/nn/paged_attention.py — pool-
shared decode memory; see PAPERS.md Ragged Paged Attention), driven as
``serving.LLMEngine`` drives it: a :class:`PageAllocator`, block tables
and lengths kept on the HOST, and the pure step functions over the pool
arrays."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.incubate.nn.paged_attention import (PageAllocator,
                                                    paged_attend,
                                                    paged_decode_step,
                                                    paged_prefill_append)

B, H, D = 3, 2, 8
PAGE = 4


class _Host:
    """The engine's cache bookkeeping for one layer: host-canonical
    tables and lengths (``LLMEngine._tables`` / ``_lens``), one
    allocator, the K and V pools as arrays."""

    def __init__(self, num_pages, batch, max_pages_per_seq):
        self.alloc = PageAllocator(num_pages, batch, max_pages_per_seq)
        self.tables = np.zeros((batch, max_pages_per_seq), np.int32)
        self.lens = np.zeros((batch,), np.int32)
        self.k = jnp.zeros((num_pages, H, PAGE, D), jnp.float32)
        self.v = jnp.zeros((num_pages, H, PAGE, D), jnp.float32)

    def ensure_capacity(self, b, new_len):
        for pos, page in self.alloc.allocate(
                b, self.alloc.pages_needed(new_len, PAGE)):
            self.tables[b, pos] = page

    def release(self, b):
        """``LLMEngine._release_slot``: pages back to the pool, the row
        pointed at the garbage page, its length 0."""
        self.alloc.release(b)
        self.tables[b, :] = 0
        self.lens[b] = 0

    def decode(self, q, k_new, v_new):
        """One batch-wide decode step; only rows that own pages advance
        (a released row's append lands in the garbage page)."""
        out, self.k, self.v = paged_decode_step(
            jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
            self.k, self.v, jnp.asarray(self.tables),
            jnp.asarray(self.lens), PAGE)
        for b in range(len(self.lens)):
            if self.alloc.owned_pages(b):
                self.lens[b] += 1
        return np.asarray(out)

    def prefill(self, k_new, v_new, lens):
        self.k, self.v = paged_prefill_append(
            jnp.asarray(k_new), jnp.asarray(v_new), self.k, self.v,
            jnp.asarray(self.tables), jnp.asarray(lens), PAGE)
        self.lens = np.where(lens > 0, lens, self.lens).astype(np.int32)

    def attend(self, q):
        return np.asarray(paged_attend(
            jnp.asarray(q), self.k, self.v, jnp.asarray(self.tables),
            jnp.asarray(self.lens), PAGE))


def _dense_attn(q, ks, vs):
    """Oracle over each row's real keys."""
    out = np.zeros_like(q)
    for b in range(q.shape[0]):
        k = ks[b]  # [h, t, d]
        s = np.einsum("hod,htd->hot", q[b], k) / np.sqrt(D)
        e = np.exp(s - s.max(-1, keepdims=True))
        pm = e / e.sum(-1, keepdims=True)
        out[b] = np.einsum("hot,htd->hod", pm, vs[b])
    return out


@pytest.mark.smoke
def test_ragged_decode_with_release_and_reuse():
    """Continuation batching proper: rows finish at different lengths,
    release their pages, and RESTART as new sequences — lengths diverge
    (genuinely ragged) and freed pages are recycled across rows; every
    live row must still match the dense oracle each step."""
    rng = np.random.default_rng(0)
    cache = _Host(num_pages=10, batch=B, max_pages_per_seq=3)
    lens = [0, 0, 0]
    hist_k = [[] for _ in range(B)]
    hist_v = [[] for _ in range(B)]
    limits = [5, 9, 2]  # row restarts after reaching its limit
    seen_ragged = False
    for t in range(12):
        q = rng.standard_normal((B, H, 1, D)).astype(np.float32)
        kn = rng.standard_normal((B, H, 1, D)).astype(np.float32)
        vn = rng.standard_normal((B, H, 1, D)).astype(np.float32)
        for b in range(B):
            if lens[b] >= limits[b]:       # finished: release + restart
                cache.release(b)
                lens[b] = 0
                hist_k[b] = []
                hist_v[b] = []
            cache.ensure_capacity(b, lens[b] + 1)
        out = cache.decode(q, kn, vn)
        for b in range(B):
            hist_k[b].append(kn[b, :, 0])
            hist_v[b].append(vn[b, :, 0])
            lens[b] += 1
        if len(set(lens)) == B:
            seen_ragged = True
        ks = [np.stack(hist_k[b], axis=1) for b in range(B)]
        vs = [np.stack(hist_v[b], axis=1) for b in range(B)]
        want = _dense_attn(q, ks, vs)
        np.testing.assert_allclose(out, want, atol=1e-5,
                                   err_msg=f"step {t} lens={lens}")
    assert seen_ragged  # the schedule genuinely diverged row lengths


def test_pool_sharing_and_release():
    # 5 pages = 1 reserved garbage page + 4 allocatable
    cache = _Host(num_pages=5, batch=2, max_pages_per_seq=3)
    # row 0 takes 2 pages (8 tokens), row 1 takes 2: pool exhausted
    cache.ensure_capacity(0, 8)
    cache.ensure_capacity(1, 8)
    with pytest.raises(RuntimeError, match="out of pages"):
        cache.ensure_capacity(0, 12)
    with pytest.raises(ValueError, match="max_pages_per_seq"):
        cache.ensure_capacity(0, 100)
    # releasing row 0 returns its pages for reuse
    cache.release(0)
    cache.ensure_capacity(1, 8)   # no-op, already sized
    cache.ensure_capacity(0, 4)   # reallocates from freed pages
    assert cache.tables[0, 0] != 0


def test_functional_read_only_decode():
    rng = np.random.default_rng(1)
    cache = _Host(num_pages=6, batch=B, max_pages_per_seq=2)
    # write 3 tokens per row through the decode step
    hist_k = [[] for _ in range(B)]
    hist_v = [[] for _ in range(B)]
    for t in range(3):
        q = rng.standard_normal((B, H, 1, D)).astype(np.float32)
        kn = rng.standard_normal((B, H, 1, D)).astype(np.float32)
        vn = rng.standard_normal((B, H, 1, D)).astype(np.float32)
        for b in range(B):
            cache.ensure_capacity(b, t + 1)
        cache.decode(q, kn, vn)
        for b in range(B):
            hist_k[b].append(kn[b, :, 0])
            hist_v[b].append(vn[b, :, 0])
    q = rng.standard_normal((B, H, 1, D)).astype(np.float32)
    out = cache.attend(q)
    ks = [np.stack(hist_k[b], axis=1) for b in range(B)]
    vs = [np.stack(hist_v[b], axis=1) for b in range(B)]
    np.testing.assert_allclose(out, _dense_attn(q, ks, vs),
                               atol=1e-5)


def test_free_list_restored_after_100_interleaved_sequences():
    """Satellite regression: 100 sequences allocated/released interleaved
    across batch slots (including mid-decode evictions while other rows
    keep decoding) must fully restore the free list — no leaked pages,
    no duplicates, and the every-page-accounted-for invariant holds at
    every step."""
    rng = np.random.default_rng(7)
    NB, NP = 4, 17  # 16 allocatable pages
    cache = _Host(num_pages=NP, batch=NB, max_pages_per_seq=3)
    q = rng.standard_normal((NB, H, 1, D)).astype(np.float32)
    lens = [0] * NB
    started = 0
    while started < 100:
        b = int(rng.integers(0, NB))
        if lens[b]:                      # evict mid-decode
            cache.release(b)
            cache.release(b)             # idempotent double-release
            lens[b] = 0
        want = int(rng.integers(1, 3 * PAGE + 1))
        cache.ensure_capacity(b, want)
        lens[b] = want
        started += 1
        # other rows keep decoding while this slot churns
        cache.decode(q, q, q)
        for r in range(NB):
            if lens[r]:
                lens[r] = min(lens[r] + 1, 3 * PAGE)
                cache.ensure_capacity(r, lens[r])
        cache.alloc.check_invariant()
    for b in range(NB):
        cache.release(b)
    cache.alloc.check_invariant()
    assert cache.alloc.num_free_pages == NP - 1
    free = cache.alloc._free
    assert sorted(free) == list(range(1, NP))  # every page, exactly once


def test_released_row_does_not_advance_or_corrupt_reused_slot():
    """The mid-decode-eviction bug: a released row's length used to
    keep advancing with every batch-wide append, so a REUSED slot wrote
    its first token at a stale offset. Released rows must stay at len 0
    (their appends absorbed by the garbage page) and a fresh sequence in
    the slot must match the dense oracle."""
    rng = np.random.default_rng(3)
    cache = _Host(num_pages=9, batch=2, max_pages_per_seq=2)
    mk = lambda: rng.standard_normal((2, H, 1, D)).astype(np.float32)
    for t in range(3):
        cache.ensure_capacity(0, t + 1)
        cache.ensure_capacity(1, t + 1)
        cache.decode(mk(), mk(), mk())
    cache.release(0)
    for t in range(3, 6):                # row 0 idle, row 1 decoding
        cache.ensure_capacity(1, t + 1)
        cache.decode(mk(), mk(), mk())
    assert int(cache.lens[0]) == 0             # did not advance
    # slot 0 reused: first append must land at offset 0 and attend over
    # exactly one token
    cache.ensure_capacity(0, 1)
    q, kn, vn = mk(), mk(), mk()
    out = cache.decode(q, kn, vn)
    assert int(cache.lens[0]) == 1
    want = _dense_attn(q[0:1], [kn[0]], [vn[0]])  # one token of history
    np.testing.assert_allclose(out[0:1], want, atol=1e-5)


def test_append_prefill_matches_token_by_token():
    """Batched multi-sequence prompt write: paged_prefill_append over
    ragged prompt lengths, read back by paged_attend, is dense attention
    over each row's real tokens."""
    rng = np.random.default_rng(5)
    plens = np.array([5, 2, 7], np.int32)
    S = int(plens.max())
    k_new = rng.standard_normal((B, H, S, D)).astype(np.float32)
    v_new = rng.standard_normal((B, H, S, D)).astype(np.float32)

    fast = _Host(num_pages=10, batch=B, max_pages_per_seq=3)
    for b in range(B):
        fast.ensure_capacity(b, int(plens[b]))
    fast.prefill(k_new, v_new, plens)

    # oracle: read-only decode over the prefilled pages vs dense attn
    q = rng.standard_normal((B, H, 1, D)).astype(np.float32)
    out = fast.attend(q)
    ks = [k_new[b, :, :plens[b]] for b in range(B)]
    vs = [v_new[b, :, :plens[b]] for b in range(B)]
    np.testing.assert_allclose(out, _dense_attn(q, ks, vs),
                               atol=1e-5)
    np.testing.assert_array_equal(fast.lens, plens)
