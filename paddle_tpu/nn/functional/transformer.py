"""Attention functionals.

Reference: python/paddle/nn/functional/ (scaled_dot_product_attention appears
in later paddle; incubate flash_attention). TPU-first: where the Pallas
kernels are the default (``ops.pallas.kernel_default``) unmasked, dropout-free
attention calls the flash-attention kernel
(paddle_tpu/ops/pallas/flash_attention.py); everything else is the XLA einsum
softmax composition (still MXU-bound).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.dispatch import apply
from paddle_tpu.framework.state import next_key


def _sdpa_ref(q, k, v, mask, dropout_p, causal, scale, dropout_key=None):
    # q, k, v: [batch, seq, heads, head_dim] (paddle layout)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    # narrow (bf16/fp16) q/k: accumulate the score contraction WIDE
    # (numlint NL101) — the pre-fix chain (bf16-accumulated logits, one
    # rounding, then the softmax's f32 upcast) was also a double
    # rounding (NL102); f32 inputs take the identical old path
    narrow = q.dtype in (jnp.bfloat16, jnp.float16)
    pet = {"preferred_element_type": jnp.float32} if narrow else {}
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, **pet) \
        * jnp.asarray(s, jnp.float32 if narrow else q.dtype)
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(cm, logits, jnp.asarray(-1e30, logits.dtype))
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_key is not None and dropout_p > 0.0:
        keep = jax.random.bernoulli(
            dropout_key, 1.0 - dropout_p, probs.shape).astype(probs.dtype)
        probs = probs * keep / (1.0 - dropout_p)
    # probs @ v contracts over the WHOLE key length — the deepest
    # reduction in the model; accumulate wide, round once at the output
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, **pet).astype(q.dtype)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 scale=None, name=None):
    """query/key/value: [batch, seq, num_heads, head_dim] (paddle convention)."""
    from paddle_tpu.ops.pallas import kernel_default
    apply_dropout = dropout_p > 0.0 and training
    if attn_mask is None and not apply_dropout and kernel_default():
        from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
        return apply(lambda q, k, v: flash_attention_bshd(
            q, k, v, causal=is_causal, scale=scale), query, key, value)

    def fn(q, k, v, m):
        key_ = next_key() if apply_dropout else None
        return _sdpa_ref(q, k, v, m, dropout_p if apply_dropout else 0.0,
                         is_causal, scale, dropout_key=key_)
    return apply(fn, query, key, value, attn_mask)


_block_mask_cache = {}          # digest key -> (block_mask, block) | None
_BLOCK_MASK_CACHE_CAP = 64
_pattern_identity_memo = {}     # (id(offs), id(cols), ql, kl) -> digest key
_PATTERN_MEMO_CAP = 256


def _cache_put(cache, cap, key, value):
    if len(cache) >= cap:
        cache.pop(next(iter(cache)))   # FIFO eviction
    cache[key] = value


def _to_np(x):
    import numpy as np
    return np.asarray(x.numpy() if hasattr(x, "numpy") else x)


def _csr_shared_mask(offs_np, cols_np, ql, kl):
    """The single [ql, kl] token mask all (b, h) share, or None. Built
    ONCE per pattern (the per-block-size alignment checks below reuse
    it)."""
    import numpy as np
    b, h = offs_np.shape[:2]
    base = None
    for bi in range(b):
        for hi in range(h):
            m = np.zeros((ql, kl), bool)
            o, c = offs_np[bi, hi], cols_np[bi, hi]
            for r in range(ql):
                m[r, c[o[r]:o[r + 1]]] = True
            if base is None:
                base = m
            elif not np.array_equal(base, m):
                return None
    return base


def _mask_block_aligned(base, ql, kl, block):
    """[nq, nk] block mask if `base` is exactly block-aligned, else None."""
    import numpy as np
    if ql % block or kl % block:
        return None
    blocks = base.reshape(ql // block, block, kl // block, block)
    frac = blocks.mean(axis=(1, 3))
    if not np.all((frac == 0.0) | (frac == 1.0)):
        return None
    return frac.astype(bool)


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """Block-sparse attention. Reference: nn/functional/sparse_attention.py.

    TPU note: when the CSR pattern is shared across (batch, head) and
    exactly block-aligned (the practical patterns — sliding window,
    global tokens, blocked causal), this routes to the Pallas
    block-sparse flash kernel
    (ops/pallas/block_sparse_attention.py): work and K/V DMA scale with
    the ACTIVE block count, not seq². Other patterns fall back to dense
    attention with the CSR mask (XLA fuses the masked softmax)."""
    hit = None
    if key_padding_mask is None and attn_mask is None:
        import hashlib

        import numpy as np
        try:
            # host-side pattern analysis only — a failure here (traced
            # offsets, exotic inputs) falls back to dense; a failure in
            # the KERNEL below must surface, not be swallowed
            ql = query.shape[2]
            kl = key.shape[2]
            # serving loops pass the SAME offset/column objects each
            # step: an identity memo skips the device->host copy + hash
            # on the hot path
            import weakref
            ident = (id(sparse_csr_offset), id(sparse_csr_columns),
                     ql, kl)
            def _ver(t):
                return getattr(t, "_version", None)

            memo = _pattern_identity_memo.get(ident)
            key_ = None
            if memo is not None:
                # id() can be reused after GC, and in-place mutation
                # (set_value/__setitem__) keeps id but bumps _version:
                # the memo only counts for the same LIVE objects at the
                # same versions
                k, r1, r2, v1, v2 = memo
                if r1() is sparse_csr_offset and \
                        r2() is sparse_csr_columns and \
                        v1 == _ver(sparse_csr_offset) and \
                        v2 == _ver(sparse_csr_columns):
                    key_ = k
            if key_ is None:
                offs_np = _to_np(sparse_csr_offset)
                cols_np = _to_np(sparse_csr_columns)
                dig = hashlib.sha256()
                dig.update(offs_np.tobytes())
                dig.update(cols_np.tobytes())
                key_ = (dig.hexdigest(), ql, kl)
                try:
                    _cache_put(
                        _pattern_identity_memo, _PATTERN_MEMO_CAP, ident,
                        (key_, weakref.ref(sparse_csr_offset),
                         weakref.ref(sparse_csr_columns),
                         _ver(sparse_csr_offset),
                         _ver(sparse_csr_columns)))
                except TypeError:
                    pass  # plain ndarrays/lists may not be weakref-able
            else:
                offs_np = cols_np = None
            if key_ in _block_mask_cache:
                hit = _block_mask_cache[key_]
            else:
                if offs_np is None:
                    offs_np = _to_np(sparse_csr_offset)
                    cols_np = _to_np(sparse_csr_columns)
                hit = None
                base = _csr_shared_mask(offs_np, cols_np, ql, kl)
                if base is not None:
                    for block in (512, 256, 128, 64):
                        bm = _mask_block_aligned(base, ql, kl, block)
                        if bm is not None and bm.any():
                            # all-empty patterns stay on the dense path
                            # (defined zero output, no kernel tables)
                            hit = (bm, block)
                            break
                _cache_put(_block_mask_cache, _BLOCK_MASK_CACHE_CAP,
                           key_, hit)
        except Exception:
            hit = None
    if hit is not None:
        bm, block = hit
        from paddle_tpu.ops.pallas.block_sparse_attention import (
            block_sparse_attention,
        )
        return apply(
            lambda q, k, v: block_sparse_attention(
                q, k, v, bm, block_q=block, block_k=block),
            query, key, value)

    def fn(q, k, v, offs, cols):
        b, h, ql, d = q.shape
        kl = k.shape[2]
        # CSR rows -> dense mask (static pattern assumed)
        import numpy as np
        offs_np = np.asarray(offs)
        cols_np = np.asarray(cols)
        m = np.zeros((b, h, ql, kl), dtype=bool)
        for bi in range(b):
            for hi in range(h):
                o = offs_np[bi, hi]
                c = cols_np[bi, hi]
                for r in range(ql):
                    m[bi, hi, r, c[o[r]:o[r + 1]]] = True
        mask = jnp.asarray(m)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
            jnp.asarray(d, jnp.float32)).astype(q.dtype)
        logits = jnp.where(mask, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        # a row with NO stored entries attends nothing: zero output (the
        # softmax over the all -1e30 row would fabricate a uniform
        # average of V) — same convention as the block-sparse kernel and
        # sparse.nn.functional.attention
        p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return apply(fn, query, key, value, sparse_csr_offset, sparse_csr_columns)
