"""The serving cache's one seam: a page pool OF A KIND.

What a layer caches, in which layout, how a program writes and reads it,
how its pages cross a process, how it shards and what it adds to the AOT
fingerprint is ONE decision — the cache kind — made here.
:func:`make_page_pool` picks the kind once, from the model's
``kv_cache_spec()`` and ``EngineConfig(kv_cache_dtype=, dtype=, mesh=)``;
:class:`~paddle_tpu.serving.engine.LLMEngine` keeps the pool ARRAYS
(engine state, donated every step), the allocator, the scheduler and the
programs, and asks the pool object for everything about the format.

- :class:`PlainKV` — K and V at the engine's dtype; row pages + the
  Pallas kernel ``paged_decode`` off-mesh on a TPU, head-major +
  ``paged_attend`` elsewhere; wire blocks ``k`` / ``v``, head-major.
- :class:`QuantizedKV` — K and V as ``(codes, scales)`` pairs,
  head-major, dequantized in-trace; wire ``k_codes`` / ``k_scales`` /
  ``v_codes`` / ``v_scales``.
- :class:`LatentPool` — ONE pool a layer of rows ``[c | k_r | 0]``;
  the Pallas kernel ``mla_paged_decode`` on a TPU, ``latent_attend``
  elsewhere; wire block ``rows``.  Refuses a mesh and a narrow dtype.

docs/serving.md "The page pool" has the table.  The step functions (the
mathematics) stay in :mod:`paddle_tpu.incubate.nn.paged_attention` and
:mod:`paddle_tpu.quantization.kv_cache`.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from paddle_tpu.incubate.nn.paged_attention import (latent_decode_path,
                                                    latent_decode_step,
                                                    latent_pool_width,
                                                    latent_prefill_append,
                                                    paged_decode_step,
                                                    paged_prefill_append,
                                                    row_pages_default)
from paddle_tpu.ops.pallas.mla_paged_attention import \
    MLA_PAGED_DECODE_REVISION
from paddle_tpu.ops.pallas.paged_attention import (PAGED_DECODE_REVISION,
                                                   from_row_pages,
                                                   to_row_pages)
from paddle_tpu.quantization.kv_cache import (quantized_decode_step,
                                              quantized_prefill_append,
                                              resolve_kv_cache_dtype)

__all__ = ["LatentPool", "PagePool", "PlainKV", "QuantizedKV",
           "make_page_pool"]


def _dense_causal_attention(q, k, v):
    """[b, h, s, d] causal attention (fp32 softmax, deterministic) —
    every kind's prefill read.

    Narrow (bf16/fp16) inputs accumulate both contractions wide and
    round once at the output (numlint NL101); the f32 path is
    byte-identical to the pre-fix jaxpr.
    """
    d = q.shape[-1]
    s = q.shape[2]
    narrow = q.dtype in (jnp.bfloat16, jnp.float16)
    pet = {"preferred_element_type": jnp.float32} if narrow else {}
    scores = jnp.matmul(q / jnp.sqrt(jnp.float32(d)).astype(q.dtype),
                        jnp.swapaxes(k, -1, -2), **pet)  # [b, h, s, s]
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
    scores = jnp.where(causal[None, None], scores.astype(jnp.float32),
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.matmul(probs, v, **pet).astype(q.dtype)


class PagePool:
    """What every kind shares: geometry, allocation, sharding, bytes and
    the hand-off over named per-layer blocks.  ``pools`` is the engine's
    pair ``(k_pools, v_pools)`` of per-layer entry lists (a kind with
    one entry a layer leaves the second list empty).

    A kind sets: ``kind`` (the model's declaration), ``_struct`` (the
    ``ShapeDtypeStruct`` pytree of one entry), ``_wire`` (for each list
    it fills, the pytree of an entry's block names), ``attention_path``
    (the AOT fingerprint's term for the decode read), ``decode_kernel``
    (whether that read is a Pallas kernel — the ``serving.decode``
    span's ``kernel``), and the traced ``prefill`` / ``decode``.
    """

    attention_path = "xla"
    decode_kernel = False

    def __init__(self, cfg, num_layers, mesh=None, spec=None):
        self.page_size = cfg.page_size
        self.num_layers = int(num_layers)
        # an entry's placement under a multi-device mesh: pinning the
        # programs' pool outputs to it keeps the arrays reusable call
        # over call without a resharding copy
        self.sharding = (None if mesh is None
                         else NamedSharding(mesh, spec))
        self.geometry = {"page_size": cfg.page_size,
                         "num_layers": self.num_layers,
                         "dtype": str(np.dtype(cfg.dtype))}

    def _per_list(self, entry):
        """``(k_pools, v_pools)`` with ``entry()`` a layer in each list
        this kind fills; the other stays empty."""
        lists = [[entry() for _ in range(self.num_layers)]
                 for _ in self._wire]
        return tuple(lists + [[]] * (2 - len(lists)))

    def allocate(self, device=None):
        """Fresh zero pools, allocated where they live (``device=``
        takes a sharding too): a pinned replica must not stage its pools
        through device 0."""
        where = self.sharding or device
        return self._per_list(lambda: jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype, device=where),
            self._struct))

    @property
    def nbytes(self):
        """Bytes of all layers' entries — quantized pools count codes
        AND their per-page scales."""
        entry = sum(math.prod(s.shape) * np.dtype(s.dtype).itemsize
                    for s in jax.tree_util.tree_leaves(self._struct))
        return entry * self.num_layers * len(self._wire)

    def out_shardings(self):
        """The step programs' ``out_shardings`` for the pools under a
        mesh."""
        one = jax.tree_util.tree_map(lambda _: self.sharding, self._struct)
        return self._per_list(lambda: one)

    def scale_overflow(self, pools, tables, lens, limit):
        """Traced guard column ``[B]`` bool: a page scale gone bad on a
        page a slot uses.  Only a quantized kind has scales."""
        return jnp.zeros(tables.shape[0], jnp.bool_)

    # ------------------------------------------------------- hand-off
    def _to_wire(self, block):
        return block

    _from_wire = _to_wire

    def export(self, pools, pages):
        """The blocks of `pages` (page ids), one ``{name: ndarray}`` a
        layer — what ``serving.fleet.wire`` packs."""
        leaves = jax.tree_util.tree_leaves
        layers = [{} for _ in range(self.num_layers)]
        for half, names in zip(pools, self._wire):
            for blocks, entry in zip(layers, half):
                for name, arr in zip(leaves(names), leaves(entry)):
                    blocks[name] = self._to_wire(np.asarray(arr)[pages])
        return layers

    def import_(self, pools, idx, layers):
        """`pools` with the exported `layers` written at page ids `idx`
        (an eager scatter: no compiled program)."""
        filled = [
            [jax.tree_util.tree_map(
                lambda arr, name: arr.at[idx].set(jnp.asarray(
                    self._from_wire(np.asarray(blocks[name])))),
                entry, names) for entry, blocks in zip(half, layers)]
            for half, names in zip(pools, self._wire)]
        return (*filled, *pools[len(filled):])


class PlainKV(PagePool):
    """K and V of ``heads x head_dim`` at the engine's dtype.  Off-mesh
    on a TPU the pools are ROW pages and decode through the Pallas
    kernel; everywhere else the head-major pool of the XLA composition
    (``row_pages_default`` observes the platform; the step functions
    follow the pool's rank).  The wire blocks are head-major whatever
    the local layout is."""

    kind = "kv"
    _wire = ("k", "v")
    _append = staticmethod(paged_prefill_append)
    _step = staticmethod(paged_decode_step)

    def __init__(self, cfg, num_layers, num_heads, head_dim, mesh=None,
                 rows=None):
        # head-major [pages, heads, page, head_dim]: axis 1 IS the head
        # axis (of the codes and of a quantized pool's scales alike)
        super().__init__(cfg, num_layers, mesh, PartitionSpec(None, "tp"))
        self.num_heads = num_heads
        if rows is None:            # what the platform says
            rows = mesh is None and row_pages_default(
                cfg.dtype, num_heads, head_dim, cfg.page_size)
        self.rows = rows
        if rows:
            self.decode_kernel = True
            self.attention_path = f"paged_decode/{PAGED_DECODE_REVISION}"
        self._shape = ((cfg.num_pages, cfg.page_size, num_heads * head_dim)
                       if rows else
                       (cfg.num_pages, num_heads, cfg.page_size, head_dim))
        self._struct = jax.ShapeDtypeStruct(self._shape, cfg.dtype)
        self.geometry.update(num_heads=num_heads, head_dim=head_dim,
                             kv_cache_dtype=cfg.kv_cache_dtype)

    def _to_wire(self, block):
        return from_row_pages(block, self.num_heads) if self.rows else block

    def _from_wire(self, block):
        return to_row_pages(block) if self.rows else block

    def prefill(self, q, k, v, kp, vp, tables, lens):
        """``q/k/v [b, s, h, d]``: dense causal attention over the
        (padded) prompt, the real tokens' K/V scattered into the pages.
        Returns (out ``[b, s, h, d]``, kp, vp)."""
        qT, kT, vT = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        out = _dense_causal_attention(qT, kT, vT)
        kp, vp = self._append(kT, vT, kp, vp, tables, lens, self.page_size)
        return jnp.swapaxes(out, 1, 2), kp, vp

    def decode(self, q, k, v, kp, vp, tables, lens):
        """``q/k/v [b, 1, h, d]``: one-token append, then attention over
        each row's pages at its own length (ragged)."""
        qT, kT, vT = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        out, kp, vp = self._step(qT, kT, vT, kp, vp, tables, lens,
                                 self.page_size)
        return jnp.swapaxes(out, 1, 2), kp, vp


class QuantizedKV(PlainKV):
    """K and V as per-(page, head)-scaled ``(codes, scales)`` pairs
    (quantization/kv_cache.py; docs/quantization.md has the format and
    the tolerance contract): head-major, the XLA composition, decode
    dequantizes in-trace with f32 accumulation."""

    _wire = (("k_codes", "k_scales"), ("v_codes", "v_scales"))

    def __init__(self, cfg, num_layers, num_heads, head_dim, mesh, quant):
        super().__init__(cfg, num_layers, num_heads, head_dim, mesh,
                         rows=False)
        self._struct = (
            jax.ShapeDtypeStruct(self._shape, quant.code_dtype),
            jax.ShapeDtypeStruct(self._shape[:2], jnp.float32))
        self._append = functools.partial(quantized_prefill_append,
                                         spec=quant)
        self._step = functools.partial(quantized_decode_step, spec=quant)

    def scale_overflow(self, pools, tables, lens, limit):
        """A non-finite — or above `limit` — scale on any page a row
        actually uses, any layer.  The gathers touch only the tiny
        ``[N, h]`` scale planes."""
        used = ((jnp.arange(tables.shape[1], dtype=jnp.int32)
                 * self.page_size)[None, :] < (lens + 1)[:, None])  # [B, P]
        bad_scale = jnp.zeros(tables.shape[0], jnp.bool_)
        for kq, vq in zip(*pools):
            for _codes, scales in (kq, vq):
                s = scales[tables]                               # [B,P,h]
                bad = ~jnp.isfinite(s)
                if limit is not None:
                    bad = bad | (s > limit)
                bad_scale = bad_scale | jnp.any(
                    bad & used[:, :, None], axis=(1, 2))
        return bad_scale


class LatentPool(PagePool):
    """ONE pool a layer of row pages ``[pages, page, W]``: a token's row
    is ``[c | k_r]`` padded with zeros to whole lane tiles; keys and
    values are read from the same row.  Plain and on one device."""

    kind = "latent"
    _wire = ("rows",)

    def __init__(self, cfg, spec, mesh=None):
        if cfg.kv_cache_dtype is not None:
            raise ValueError(
                f"kv_cache_dtype={cfg.kv_cache_dtype!r}: a 'latent' "
                f"pool is stored plain (no quantized latent pool)")
        if mesh is not None:
            raise ValueError(
                "mesh: a 'latent' pool has no head axis to shard "
                "and lives on one device")
        super().__init__(cfg, spec["num_layers"])
        row, width = int(spec["row_width"]), latent_pool_width(
            spec["row_width"])
        path = latent_decode_path(cfg.dtype, int(spec["value_width"]),
                                  width, cfg.page_size)
        self.decode_kernel = path != "xla"
        self.attention_path = "latent/" + (
            path if path == "xla"
            else f"{path}/{MLA_PAGED_DECODE_REVISION}")
        self._struct = jax.ShapeDtypeStruct(
            (cfg.num_pages, cfg.page_size, width), cfg.dtype)
        self.geometry.update(kind="latent", row_width=row)

    def prefill(self, q, k, v, rows, pages, tables, lens):
        """Expanded form: dense causal attention of ``q`` / ``k [b, s,
        H, d_qk]`` and ``v [b, s, H, d_v]`` -> ``[b, s, H, d_v]``; the
        prompt's latent ``rows [b, s, w]`` scattered into the pool."""
        out = _dense_causal_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2))
        pages = latent_prefill_append(rows, pages, tables, lens,
                                      self.page_size)
        return jnp.swapaxes(out, 1, 2), pages

    def decode(self, q, rows, rank, scale, pages, tables, lens):
        """Absorbed form: append each slot's new row ``rows [b, 1, w]``,
        then attend ``q [b, 1, H, w]`` (unscaled) over the slot's live
        rows, the sum over a row's first ``rank`` columns -> ``[b, 1, H,
        rank]``."""
        u, pages = latent_decode_step(q[:, 0], rows[:, 0], pages, tables,
                                      lens, rank, self.page_size, scale)
        return u[:, None], pages


def make_page_pool(model, cfg, mesh=None):
    """The pool of the kind `model` declares (``kv_cache_spec()``; no
    declaration is GPT's K and V of ``num_heads x head_dim``), at
    `cfg`'s page geometry, dtype and ``kv_cache_dtype``, for the
    RESOLVED `mesh` (None off-mesh).  A kind that cannot shard or cannot
    narrow raises ``ValueError`` by name."""
    spec = (model.kv_cache_spec() if hasattr(model, "kv_cache_spec")
            else {"kind": "kv"})
    if spec["kind"] == "latent":
        return LatentPool(cfg, spec, mesh)
    if spec["kind"] != "kv":
        raise ValueError(f"unknown kv cache kind {spec['kind']!r}")
    mc = model.config
    heads = int(mc.num_heads)
    args = (cfg, mc.num_layers, heads, int(mc.hidden_size) // heads, mesh)
    # kv_cache_dtype narrows the pool STORAGE only
    quant = resolve_kv_cache_dtype(cfg.kv_cache_dtype)
    return PlainKV(*args) if quant is None else QuantizedKV(*args, quant)
