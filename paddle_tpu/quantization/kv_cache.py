"""Quantized KV-cache pages — per-page-scaled int8/fp8 paged pools.

Plane 1 of the quantization subsystem (ROADMAP item 2): the serving
ceiling for "millions of users" is KV pages per chip, and KV values are
*storage*, not accumulators — they are written once and read through an
f32-accumulated attention contraction.  Quantizing the paged pools to
int8 (or fp8 where the dtype exists) with one scale per (page, head)
halves bytes/token vs bf16 and quarters them vs f32, which is exactly
that many more concurrent sequences inside the same HBM budget.

Storage format (the per-page-scale design implied by Ragged Paged
Attention's paged pools, arXiv:2604.15464 — see PAPERS.md):

- code pools:   ``[num_pages, n_head, page_size, head_dim]`` in the
  code dtype (int8 / float8_e4m3fn / float8_e5m2);
- scale pools:  ``[num_pages, n_head]`` float32 — one scale per
  (page, head), so the overhead is 4 bytes per ``page_size*head_dim``
  codes (~3% at the default 8x16 geometry) and a hot head cannot
  coarsen a cold head's grid;
- value ≈ code * scale, with ``scale = absmax / qmax`` over the page's
  real tokens.

Write paths:

- **prefill** quantizes each (row-page, head) block against the absmax
  of the real tokens landing in it (padding tokens are masked out of
  the scale), then scatters codes token-wise and scales page-wise —
  the same garbage-page-0 routing as the f32 pools.
- **decode** appends one token per row with *rescale-on-append*: the
  target page's scale grows monotonically (``new = max(old,
  tok_absmax/qmax)``), and only when it actually grows are the page's
  existing codes re-gridded (``round(code * old/new)``).  The common
  no-growth step multiplies by exactly 1.0 — bit-identical codes — so
  the quantization error per value stays bounded by a few grid steps
  instead of accumulating per append.  A page at offset 0 is FRESH for
  its row: its stale scale (from a previous owner) is ignored.

Read path: :func:`quantized_attend` dequantizes in-trace — gather int8
codes + per-page scales, one ``convert`` + one adjacent scale multiply
(the numlint NL301-clean shape), then f32 score/value contractions and
one rounding back to the query dtype (the NL101-clean pattern PR 12
established for narrow pools).  XLA fuses the dequant into the
contraction, so HBM sees code-width reads while the MXU sees floats.

Determinism contract (docs/quantization.md "Tolerance contracts"):
every function here is a pure per-row computation — row ``b``'s codes
depend only on row ``b``'s tokens — so continuous batching stays
token-identical to sequential serving under quantized pools.  An
EVICTION replay re-quantizes prompt+generated wholesale through
prefill (batch scales) where the original run quantized incrementally
(grown scales), so post-replay logits differ at quantization-error
order; the serving tolerance contract bounds that divergence.

Module-level imports are jax/numpy only so the analysis CLIs can
import the package light.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

__all__ = [
    "KVQuantSpec",
    "KV_CACHE_DTYPES",
    "dequantize_codes",
    "encode_int_codes",
    "kv_bytes_per_token",
    "quantize_block",
    "quantized_attend",
    "quantized_decode_step",
    "quantized_prefill_append",
    "resolve_kv_cache_dtype",
]


@dataclass(frozen=True)
class KVQuantSpec:
    """One supported code dtype for the quantized KV pools."""

    name: str           # canonical config string ("int8", "fp8_e4m3", ...)
    dtype_name: str     # jnp dtype attribute name
    qmax: float         # largest representable magnitude on the code grid
    is_int: bool        # int codes round+clip; fp8 codes cast

    @property
    def code_dtype(self):
        return getattr(jnp, self.dtype_name)

    @property
    def code_bytes(self):
        return jnp.dtype(self.code_dtype).itemsize


KV_CACHE_DTYPES = {
    "int8": KVQuantSpec("int8", "int8", 127.0, True),
    "fp8_e4m3": KVQuantSpec(
        "fp8_e4m3", "float8_e4m3fn",
        float(jnp.finfo(jnp.float8_e4m3fn).max), False),
    "fp8_e5m2": KVQuantSpec(
        "fp8_e5m2", "float8_e5m2",
        float(jnp.finfo(jnp.float8_e5m2).max), False),
}


def resolve_kv_cache_dtype(name):
    """Config string -> :class:`KVQuantSpec` (None passes through).

    Accepts ``None`` (un-quantized pools at ``EngineConfig.dtype``) or
    one of :data:`KV_CACHE_DTYPES`.  Unknown names raise with the
    supported set spelled out.
    """
    if name is None or isinstance(name, KVQuantSpec):
        return name
    spec = KV_CACHE_DTYPES.get(str(name))
    if spec is None:
        raise ValueError(
            f"kv_cache_dtype {name!r} is not supported here; choose "
            f"None or one of {sorted(KV_CACHE_DTYPES)}")
    return spec


def kv_bytes_per_token(num_heads, head_dim, page_size, spec=None,
                       dtype=jnp.float32):
    """Pool storage bytes per token of KV capacity for ONE layer
    (K + V): the honest per-token cost the perfgate/bench budgets
    gate — quantized pools pay ``code_bytes`` per element plus the
    per-(page, head) f32 scale amortized over the page's tokens."""
    if spec is None:
        return 2 * num_heads * head_dim * jnp.dtype(dtype).itemsize
    per_head = head_dim * spec.code_bytes + 4.0 / page_size
    return 2 * num_heads * per_head


# ----------------------------------------------------------- primitives
def encode_int_codes(scaled, qmax, key=None, dtype=jnp.int8):
    """THE int-code rounding core — round (deterministic, or stochastic
    floor+Bernoulli when a `key` rides along), clip to ±qmax, cast.
    Shared by the KV-page codec below, the EQuARX collective
    (quantization/collectives.py), and the legacy int32-wire collective
    (distributed/quantized_collective.py), so the rounding/clip
    contract has exactly one definition."""
    if key is not None:
        lo = jnp.floor(scaled)
        frac = scaled - lo
        scaled = lo + jax.random.bernoulli(key, frac).astype(jnp.float32)
    else:
        scaled = jnp.round(scaled)
    return jnp.clip(scaled, -qmax, qmax).astype(dtype)


def _encode(scaled, spec):
    """Scaled values (value/scale) -> codes on the spec's grid."""
    if spec.is_int:
        return encode_int_codes(scaled, spec.qmax,
                                dtype=spec.code_dtype)
    # fp8: the cast IS the rounding; clip keeps outliers finite
    return jnp.clip(scaled, -spec.qmax, spec.qmax).astype(spec.code_dtype)


def quantize_block(values, spec, axes):
    """Quantize `values` with one scale per block.

    `axes`: the axes REDUCED into each scale (the block extent).
    Returns ``(codes, scales)`` with ``scales = absmax/qmax`` keeping
    the reduced axes as size-1 (broadcast-ready).  All-zero blocks get
    scale 0 and all-zero codes (0 * 0 == 0 round-trips exactly).
    """
    v = values.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(v), axis=axes, keepdims=True)
    scales = absmax / spec.qmax
    safe = jnp.where(scales > 0, scales, 1.0)
    return _encode(v / safe, spec), scales


def dequantize_codes(codes, scales, spec=None):
    """codes * scales in f32 — `scales` must already be shaped to
    broadcast (size-1 reduced axes).  The scale multiply sits adjacent
    to the convert: the NL301-clean consumption shape."""
    del spec
    return codes.astype(jnp.float32) * scales


# ------------------------------------------------------------- prefill
def quantized_prefill_append(k_new, v_new, kq, vq, tables, lens,
                             page_size, spec):
    """Batched prompt write into quantized pools.

    k_new/v_new: ``[b, h, S, d]`` float; kq/vq: ``(codes, scales)``
    pool pairs; tables ``[b, P]``; lens ``[b]`` (0 = row not being
    prefilled — nothing scatters, the f32 contract).  Returns updated
    ``(kq, vq)``.

    Each (row-page, head) block's scale comes from the absmax of the
    REAL tokens landing in that page (positions >= lens[b] are masked
    to zero first); codes scatter token-wise exactly like the f32
    :func:`paged_prefill_append`, scales scatter page-wise.  Page ids
    for masked positions route to the garbage page 0.
    """
    b, h, S, d = k_new.shape
    lens = lens.astype(jnp.int32)
    t = jnp.arange(S, dtype=jnp.int32)
    page_idx = jnp.minimum(t // page_size, tables.shape[1] - 1)   # [S]
    offs = t % page_size
    page_ids = tables[:, page_idx]                                # [b, S]
    valid = t[None, :] < lens[:, None]
    page_ids = jnp.where(valid, page_ids, 0)
    flat_pages = page_ids.reshape(-1)
    flat_offs = jnp.tile(offs, b)

    n_slots = -(-S // page_size)          # row-page slots covering S
    pad = n_slots * page_size - S
    slot_ids = jnp.where(
        (jnp.arange(n_slots, dtype=jnp.int32) * page_size)[None, :]
        < lens[:, None],
        tables[:, :n_slots], 0)                                   # [b, n]

    def write(pool, vals):
        codes_pool, scales_pool = pool
        vv = jnp.where(valid[:, None, :, None], vals.astype(jnp.float32),
                       0.0)                                       # [b,h,S,d]
        blocks = jnp.pad(vv, ((0, 0), (0, 0), (0, pad), (0, 0)))
        blocks = blocks.reshape(b, h, n_slots, page_size, d)
        # one scale per (row-page slot, head) over its real tokens
        scales = jnp.max(jnp.abs(blocks), axis=(3, 4)) / spec.qmax
        safe = jnp.where(scales > 0, scales, 1.0)                 # [b,h,n]
        per_tok = jnp.repeat(safe, page_size, axis=2)[:, :, :S]   # [b,h,S]
        codes = _encode(vv / per_tok[..., None], spec)
        ct = jnp.swapaxes(codes, 1, 2).reshape(b * S, h, d)
        codes_pool = codes_pool.at[flat_pages, :, flat_offs].set(ct)
        page_scales = jnp.moveaxis(scales, 1, 2).reshape(b * n_slots, h)
        scales_pool = scales_pool.at[slot_ids.reshape(-1)].set(page_scales)
        return codes_pool, scales_pool

    return write(kq, k_new), write(vq, v_new)


# -------------------------------------------------------------- decode
def _append_token(pool, tok, page_ids, offs, spec):
    """Rescale-on-append of one token per row into its target page.

    pool: ``(codes [N,h,p,d], scales [N,h])``; tok ``[b, h, d]`` float;
    page_ids/offs ``[b]``.  The page scale grows monotonically; a
    no-growth append multiplies existing codes by exactly 1.0 (bit-
    identical), and an offset-0 append treats the page as fresh (the
    previous owner's scale is dead state, not a floor).
    """
    codes_pool, scales_pool = pool
    p = codes_pool.shape[2]
    page = codes_pool[page_ids]                            # [b, h, p, d]
    old_scale = jnp.where(offs[:, None] == 0, 0.0,
                          scales_pool[page_ids])           # [b, h]
    tok32 = tok.astype(jnp.float32)
    tok_scale = jnp.max(jnp.abs(tok32), axis=-1) / spec.qmax
    new_scale = jnp.maximum(old_scale, tok_scale)
    safe = jnp.where(new_scale > 0, new_scale, 1.0)
    ratio = old_scale / safe                               # [b, h]
    regrid = dequantize_codes(page, ratio[..., None, None])
    tok_codes = _encode(tok32 / safe[..., None], spec)     # [b, h, d]
    at = jnp.arange(p, dtype=jnp.int32)
    here = at[None, None, :, None] == offs[:, None, None, None]
    page = jnp.where(here, tok_codes[:, :, None, :].astype(page.dtype),
                     _encode(regrid, spec))
    return (codes_pool.at[page_ids].set(page),
            scales_pool.at[page_ids].set(new_scale))


def quantized_decode_step(q, k_new, v_new, kq, vq, tables, lens,
                          page_size, spec, scale=None):
    """Quantized analogue of :func:`paged_decode_step`: write each
    row's new token at position ``lens[b]`` (rescale-on-append), attend
    over ``lens[b]+1`` tokens with f32 accumulation.  Returns
    ``(out, kq, vq)``; the caller owns the lens update (the multi-layer
    engine contract)."""
    lens = lens.astype(jnp.int32)
    page_idx = lens // page_size
    offs = lens % page_size
    page_ids = jnp.take_along_axis(tables, page_idx[:, None],
                                   axis=1)[:, 0]           # [b]
    kt = jnp.swapaxes(k_new, 1, 2)[:, 0]                   # [b, h, d]
    vt = jnp.swapaxes(v_new, 1, 2)[:, 0]
    kq = _append_token(kq, kt, page_ids, offs, spec)
    vq = _append_token(vq, vt, page_ids, offs, spec)
    out = quantized_attend(q, kq, vq, tables, lens + 1, page_size, spec,
                           scale)
    return out, kq, vq


# -------------------------------------------------------------- attend
def quantized_attend(q, kq, vq, tables, lens, page_size, spec,
                     scale=None):
    """Attention of ``[b, h, 1, d]`` queries over quantized pages.

    Dequantization is in-trace and adjacent to its scale (NL301-clean),
    and BOTH contractions accumulate in f32 with one rounding back to
    the query dtype at the output (NL101-clean) — the score matmul and
    the value matmul reduce over the entire cached history, the deepest
    sums in the serving path.
    """
    del spec
    b, h, one, d = q.shape
    sc = scale if scale is not None else 1.0 / float(d) ** 0.5
    k_codes, k_scales = kq
    v_codes, v_scales = vq
    P = tables.shape[1]

    def seq(codes, scales):
        pages = codes[tables]                         # [b, P, h, p, d]
        psc = scales[tables]                          # [b, P, h]
        x = dequantize_codes(pages, psc[..., None, None])
        return jnp.moveaxis(x, 2, 1).reshape(b, h, P * page_size, d)

    k_seq = seq(k_codes, k_scales)
    v_seq = seq(v_codes, v_scales)
    pos = jnp.arange(P * page_size)
    mask = pos[None, None, None, :] < lens[:, None, None, None]
    s = jnp.matmul(q.astype(jnp.float32) * sc,
                   jnp.swapaxes(k_seq, -1, -2))       # [b, h, 1, Pp] f32
    s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.matmul(p, v_seq).astype(q.dtype)       # [b, h, 1, d]
