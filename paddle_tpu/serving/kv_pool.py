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
- :class:`LayeredPool` — a kind PER LAYER: a :class:`GroupedKV` for the
  layers that declare ``kv`` (row pages at their own K/V head count,
  grouped queries reading the live pages through the Pallas kernel
  ``grouped_paged_decode`` on a TPU, the XLA composition elsewhere), a
  :class:`SlotState` for those that declare ``state`` — a recurrent
  layer's state, indexed by SLOT and not by page (``[max_num_seqs,
  ...]``: the convolution's window at the engine's dtype, the
  recurrence's matrix — Mamba-2's state-space matrix, a delta rule's
  key-value memory — in float32), overwritten by the prefill that
  admits a request and advanced in place by decode; wire blocks
  ``conv`` / ``ssm`` — and a :class:`WindowKV` for those that declare
  ``window``: a sliding-window layer's K and V of a slot's last ``W``
  positions, in a ring indexed by SLOT (``[max_num_seqs, W, H_kv,
  d]``, position ``p`` at row ``p % W``), no pages and no hand-off.
  Refuses a mesh and a narrow dtype.

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

from paddle_tpu.incubate.nn.paged_attention import (grouped_causal_attention,
                                                    grouped_paged_attend,
                                                    latent_decode_path,
                                                    latent_decode_step,
                                                    latent_pool_width,
                                                    latent_prefill_append,
                                                    paged_decode_step,
                                                    paged_prefill_append,
                                                    row_pages_default)
from paddle_tpu.ops.pallas import on_tpu
from paddle_tpu.ops.pallas.flash_attention import (FLASH_ATTENTION_REVISION,
                                                   flash_attention_bshd)
from paddle_tpu.ops.pallas.mla_paged_attention import \
    MLA_PAGED_DECODE_REVISION
from paddle_tpu.ops.pallas.paged_attention import (
    GROUPED_PAGED_DECODE_REVISION, PAGED_DECODE_REVISION, from_row_pages,
    grouped_paged_decode, paged_decode_supported, to_row_pages)
from paddle_tpu.quantization.kv_cache import (quantized_decode_step,
                                              quantized_prefill_append,
                                              resolve_kv_cache_dtype)

__all__ = ["GroupedKV", "LatentPool", "LayeredPool", "PagePool",
           "PlainKV", "QuantizedKV", "SlotState", "WindowKV",
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
    state_layers = 0          # layers cached by slot (SlotState)
    state_nbytes = 0
    window_layers = 0         # layers cached in a ring by slot (WindowKV)

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

    def live_rows(self, lens):
        """Ring rows of one window layer a decode pass over slots at
        `lens` reads (a kind without window layers: 0)."""
        return 0

    def layer_step(self, li, mode, slot=None):
        """The traced write-and-read ``(q, k, v, kp, vp, tables, lens) ->
        (out, kp, vp)`` of layer `li` in a program of `mode` ("prefill" |
        "decode"); `slot` is a prefill's admitted slot, for a kind that
        caches by slot."""
        return self.prefill if mode == "prefill" else self.decode

    # ------------------------------------------- what a slot adds
    def slot_operands(self, slot):
        """Operands a prefill program takes beyond the slot's page table
        (host values; none for a kind that caches by page alone)."""
        return ()

    def prefill_attrs(self, tokens, bucket):
        """Attributes this kind adds to a ``serving.prefill`` span."""
        return {}

    def decode_attrs(self, live):
        """Attributes this kind adds to a ``serving.decode`` span of
        `live` running slots."""
        return {}

    # ------------------------------------------------------- hand-off
    def _to_wire(self, block):
        return block

    _from_wire = _to_wire

    def exported_pages(self, layers):
        """How many pages the exported `layers` hold."""
        return len(next(iter(layers[0].values())))

    def export(self, pools, pages, slot=None):
        """The blocks of `pages` (page ids), one ``{name: ndarray}`` a
        layer — what ``serving.fleet.wire`` packs.  `slot` is for a kind
        that caches by slot."""
        leaves = jax.tree_util.tree_leaves
        layers = [{} for _ in range(self.num_layers)]
        for half, names in zip(pools, self._wire):
            for blocks, entry in zip(layers, half):
                for name, arr in zip(leaves(names), leaves(entry)):
                    blocks[name] = self._to_wire(np.asarray(arr)[pages])
        return layers

    def import_(self, pools, idx, layers, slot=None):
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


class GroupedKV(PlainKV):
    """K and V of ``num_heads x head_dim`` read by ``query_heads`` query
    heads (head ``i`` reads K/V head ``i // groups``) at the model's own
    score ``scale``: ROW pages on every platform (a head-major pool is
    re-laid whole by every program that appends to it).  Decode of one
    position a slot reads the live pages through the Pallas kernel
    ``grouped_paged_decode`` where it runs (on a TPU, in a program no mesh
    partitions, for the geometries it takes); a block-causal cache
    (``causal_block`` > 1) is decoded by blocks of that many rows, and
    that read, like every read elsewhere, is the XLA composition over the
    table.  On one device."""

    def __init__(self, cfg, num_layers, num_heads, head_dim, query_heads,
                 scale, causal_block=1):
        super().__init__(cfg, num_layers, num_heads, head_dim, rows=True)
        self.groups = query_heads // num_heads
        self.scale = float(scale)
        self.causal_block = int(causal_block)
        # where the kernels run (on a TPU, in a program no mesh
        # partitions), a causal prefill reads through the Pallas flash
        # kernel — no [s, s] score table — and the decode of one position
        # a slot through grouped_paged_decode; a block-causal cache keeps
        # the XLA composition for both
        self.flash = self.causal_block == 1 and _flash_prefill()
        self.decode_kernel = self.flash and paged_decode_supported(
            cfg.dtype, num_heads, head_dim, cfg.page_size)
        self.attention_path = (
            f"grouped_paged_decode/{GROUPED_PAGED_DECODE_REVISION}"
            if self.decode_kernel else "xla/row_pages")
        if self.causal_block > 1:
            self.geometry["causal_block"] = self.causal_block
        if self.flash:
            self.attention_path += f"+prefill:{FLASH_ATTENTION_REVISION}"

    def prefill(self, q, k, v, kp, vp, tables, lens):
        if self.flash:
            out = flash_attention_bshd(q, k, v, causal=True,
                                       scale=self.scale,
                                       interpret=not on_tpu())
        else:
            out = grouped_causal_attention(q, k, v, self.scale,
                                           block=self.causal_block)
        kp, vp = self._append(jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
                              kp, vp, tables, lens, self.page_size)
        return out, kp, vp

    def decode(self, q, k, v, kp, vp, tables, lens):
        """A pass over each slot's ``rows`` positions (``q [b, rows, H,
        d]``; one, or a BLOCK of a block-causal cache): their K/V rows
        written FIRST, at ``len .. len + rows - 1`` (in place, in pages the
        slot owns; a block's are overwritten by its next pass), then each
        of the ``rows x H`` query rows attends over the slot's ``len +
        rows`` positions — the stored prefix and the whole block, so no
        mask is needed inside it.  One position a slot reads the live
        pages alone through ``grouped_paged_decode`` where
        :attr:`decode_kernel` says so; every other pass gathers the whole
        table (:func:`grouped_paged_attend`).  ``len`` is not advanced
        here: the caller advances it (a block's, when the pass was its
        last)."""
        b, rows, H, d = q.shape
        page, hk = self.page_size, self.num_heads
        lens = lens.astype(jnp.int32)
        at = lens[:, None] + jnp.arange(rows, dtype=jnp.int32)   # [b, rows]
        page_ids = jnp.take_along_axis(tables, at // page, axis=1)
        kp = kp.at[page_ids, at % page].set(
            k.reshape(b, rows, hk * d).astype(kp.dtype))
        vp = vp.at[page_ids, at % page].set(
            v.reshape(b, rows, hk * d).astype(vp.dtype))
        if rows == 1 and self.decode_kernel:
            out = grouped_paged_decode(q[:, 0], kp, vp, tables, lens + 1,
                                       scale=self.scale,
                                       interpret=not on_tpu())
            return out[:, None], kp, vp
        return (grouped_paged_attend(q, kp, vp, tables, lens + rows,
                                     self.scale), kp, vp)


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


class SlotState:
    """A recurrent layer's cache: indexed by SLOT, not by page.  An entry
    is the pair (``conv [slots, *conv]`` at the engine's dtype — the
    convolution's last inputs —, ``ssm [slots, *ssm]`` float32 — the
    recurrence's matrix, whatever recurrence the model runs through
    :meth:`recur`).  A prefill starts from nothing and overwrites its
    slot's entry, so a finished request's state never reaches the next;
    decode advances every slot's entry in place.  The declaration may name
    its ``recurrence`` (default ``"mamba2"``; the spans say it) and the
    ``chunk`` its prompts run in (``scan_chunks`` counts them)."""

    wire = ("conv", "ssm")

    def __init__(self, cfg, spec):
        slots = cfg.max_num_seqs
        self.struct = (
            jax.ShapeDtypeStruct((slots, *spec["conv"]), cfg.dtype),
            jax.ShapeDtypeStruct((slots, *spec["ssm"]), jnp.float32))
        self.recurrence = str(spec.get("recurrence", "mamba2"))
        self.chunk = spec.get("chunk")
        # lists: what a hand-off's JSON header brings back
        self.geometry = {"conv": list(spec["conv"]),
                         "ssm": list(spec["ssm"])}

    @property
    def slot_nbytes(self):
        """Bytes of ONE slot's entry in ONE layer."""
        return sum(math.prod(s.shape[1:]) * np.dtype(s.dtype).itemsize
                   for s in self.struct)

    def chunks(self, bucket):
        """Chunks one layer's prompt of ``bucket`` positions runs, padding
        included (one where no chunk is declared)."""
        if not self.chunk:
            return 1
        return -(-bucket // min(int(self.chunk), bucket))

    def recur(self, fn, conv, ssm, lens, slot, values):
        """``fn(conv, ssm, lens, *values) -> (out, conv', ssm')`` on the
        rows the program runs: every slot's entry (decode, `slot` None),
        or a fresh zero entry whose result lands at `slot` (prefill)."""
        if slot is None:
            return fn(conv, ssm, lens, *values)
        out, conv_new, ssm_new = fn(
            *(jnp.zeros((1, *s.shape[1:]), s.dtype) for s in self.struct),
            lens, *values)
        at = slot.astype(jnp.int32)[0]
        return (out,
                jax.lax.dynamic_update_slice_in_dim(
                    conv, conv_new.astype(conv.dtype), at, axis=0),
                jax.lax.dynamic_update_slice_in_dim(
                    ssm, ssm_new.astype(ssm.dtype), at, axis=0))


class WindowKV:
    """A sliding-window layer's cache: K and V of a slot's last ``window``
    positions in a RING indexed by slot — ``[max_num_seqs, window, H_kv,
    d]`` at the engine's dtype, position ``p`` at row ``p % window`` — read
    by ``query_heads`` query heads at the model's ``scale``.  No pages: a
    slot's ring is its own whatever its length.

    A prefill attends over the prompt under the window (the Pallas flash
    kernel on a TPU, :func:`grouped_causal_attention` elsewhere) and writes
    the prompt's last ``min(len, window)`` rows.  Decode writes row ``len %
    window``, then reads the slot's ring masked to the positions ``(len -
    window, len]``: row ``r`` is read only once ``len >= r`` or ``len >=
    window``, so a row a slot's earlier request left is never read, and a
    pass that is launched and discarded overwrites only the row of the
    position that falls out of every later query's window."""

    def __init__(self, cfg, spec):
        heads, dim = int(spec["num_heads"]), int(spec["head_dim"])
        self.window = int(spec["window"])
        self.num_heads = heads
        self.groups = int(spec.get("query_heads", heads)) // heads
        self.scale = float(spec.get("scale", dim ** -0.5))
        self.struct = jax.ShapeDtypeStruct(
            (cfg.max_num_seqs, self.window, heads, dim), cfg.dtype)
        self.flash = _flash_prefill()
        self.attention_path = f"window/{self.window}:xla/ring" + (
            f"+prefill:{FLASH_ATTENTION_REVISION}" if self.flash else "")
        self.geometry = {"window": self.window, "window_heads": heads,
                         "window_head_dim": dim}

    def prefill(self, q, k, v, kr, vr, tables, lens, slot):
        """``q [1, s, H, d]`` / ``k``, ``v [1, s, H_kv, d]`` of a padded
        prompt of ``lens[0]`` tokens admitted at ``slot [1]``: windowed
        causal attention, and the slot's ring rewritten — row ``r`` gets
        the latest position ``p < len`` with ``p % window == r`` (the
        first row's position where there is none: such a row is not read
        before decode writes it)."""
        if self.flash:
            out = flash_attention_bshd(q, k, v, causal=True,
                                       scale=self.scale, window=self.window,
                                       interpret=not on_tpu())
        else:
            out = grouped_causal_attention(q, k, v, self.scale,
                                           window=self.window)
        n = lens.astype(jnp.int32)[0]
        r = jnp.arange(self.window, dtype=jnp.int32)
        held = n - 1 - jnp.remainder(n - 1 - r, self.window)
        take = jnp.clip(held, 0, k.shape[1] - 1)
        at = slot.astype(jnp.int32)[0]
        kr = jax.lax.dynamic_update_slice_in_dim(
            kr, k[:, take].astype(kr.dtype), at, axis=0)
        vr = jax.lax.dynamic_update_slice_in_dim(
            vr, v[:, take].astype(vr.dtype), at, axis=0)
        return out, kr, vr

    def decode(self, q, k, v, kr, vr, tables, lens):
        """``q [slots, 1, H, d]``: each slot's new K/V row written at row
        ``len % window`` (in place), then its query heads attend over its
        ring, masked to the positions ``(len - window, len]``; float32
        softmax, both contractions accumulated wide."""
        if q.shape[1] > 1:
            raise NotImplementedError("a window layer decodes one position "
                                      "a slot (no kind of generation by "
                                      "blocks)")
        b, _, H, d = q.shape
        hk, W = self.num_heads, self.window
        lens = lens.astype(jnp.int32)
        rows = jnp.arange(b)
        kr = kr.at[rows, lens % W].set(k[:, 0].astype(kr.dtype))
        vr = vr.at[rows, lens % W].set(v[:, 0].astype(vr.dtype))
        scores = jnp.einsum(
            "bhgd,bkhd->bhgk", q.reshape(b, hk, self.groups, d), kr,
            preferred_element_type=jnp.float32) * self.scale
        row = jnp.arange(W)[None, :]
        live = (row <= lens[:, None]) | (lens[:, None] >= W)
        scores = jnp.where(live[:, None, None, :], scores,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhgk,bkhd->bhgd", probs, vr,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, 1, H, d).astype(q.dtype), kr, vr

    def live_rows(self, lens):
        """Rows a decode pass over slots at ``lens`` (host ints, the
        positions stored before the pass) reads in ONE window layer."""
        return int(np.sum(np.minimum(np.asarray(lens) + 1, self.window)))


def _flash_prefill():
    """Whether a causal prefill runs the flash kernel here: on a TPU, in a
    program no mesh partitions (``kernel_default``)."""
    from paddle_tpu.ops.pallas import kernel_default
    return kernel_default()


class LayeredPool(PagePool):
    """A cache kind PER LAYER (``{"kind": "layers", "layers": [...]}``):
    one :class:`GroupedKV` for the layers declaring ``kv`` (one geometry:
    ``num_heads x head_dim``, optionally ``query_heads`` and ``scale``),
    one :class:`SlotState` for those declaring
    ``state`` and one :class:`WindowKV` for those declaring ``window``
    (``num_heads``, ``head_dim``, ``window``, optionally ``query_heads``
    and ``scale``) — each kind with its own geometry.  A layer's entry is
    a pair in the engine's two lists whatever its kind: (K pages, V
    pages), (conv, ssm) or (K ring, V ring).  The pool is plain and on one
    device."""

    kind = "layers"

    def __init__(self, cfg, layers, mesh=None):
        self.kinds = [layer["kind"] for layer in layers]
        unknown = set(self.kinds) - {"kv", "state", "window"}
        if unknown:
            raise ValueError(f"unknown kv cache kind {sorted(unknown)[0]!r} "
                             f"in a per-layer declaration")
        kv = [layer for layer in layers if layer["kind"] == "kv"]
        state = [layer for layer in layers if layer["kind"] == "state"]
        window = [layer for layer in layers if layer["kind"] == "window"]
        if any(layer != group[0] for group in (kv, state, window)
               for layer in group):
            raise ValueError("the layers of one cache kind must share "
                             "one geometry")
        if cfg.kv_cache_dtype is not None:
            raise ValueError(
                f"kv_cache_dtype={cfg.kv_cache_dtype!r}: a per-layer pool "
                f"is stored plain (a 'state' layer keeps its state in "
                f"float32, a 'kv' layer its K and V at the engine's dtype)")
        if mesh is not None:
            raise ValueError(
                "mesh: a per-layer pool is not sharded (a 'state' layer's "
                "per-slot state lives on one device)")
        super().__init__(cfg, len(layers))
        self.kv = self.state = self.window = None
        if kv:
            self.kv = _grouped_kv(cfg, len(kv), kv[0])
            self.decode_kernel = self.kv.decode_kernel
            self.geometry.update(self.kv.geometry, num_layers=len(layers))
        if state:
            self.state = SlotState(cfg, state[0])
            self.state_layers = len(state)
            self.geometry.update(self.state.geometry)
        if window:
            self.window = WindowKV(cfg, window[0])
            self.window_layers = len(window)
            self.geometry.update(self.window.geometry)
        self.geometry["kinds"] = list(self.kinds)
        self.attention_path = "+".join(
            ([f"kv:{self.kv.attention_path}"] if kv else [])
            + ["state:xla/float32"] * bool(state)
            # with rings beside pages, which layer is which enters the
            # AOT fingerprint too (a letter a layer)
            + ([self.window.attention_path, "kinds:" + "".join(
                k[0] for k in self.kinds)] if window else []))

    def _struct_of(self, li):
        kind = self.kinds[li]
        if kind == "kv":
            return (self.kv._struct,) * 2
        if kind == "window":
            return (self.window.struct,) * 2
        return self.state.struct

    def _per_layer(self, make):
        """``(firsts, seconds)``: ``make(struct leaf)`` over each layer's
        pair of entries."""
        halves = ([], [])
        for li in range(self.num_layers):
            for half, struct in zip(halves, self._struct_of(li)):
                half.append(jax.tree_util.tree_map(make, struct))
        return halves

    def allocate(self, device=None):
        return self._per_layer(
            lambda s: jnp.zeros(s.shape, s.dtype, device=device))

    @property
    def state_nbytes(self):
        if self.state is None:
            return 0
        return self.state_layers * sum(
            math.prod(s.shape) * np.dtype(s.dtype).itemsize
            for s in self.state.struct)

    @property
    def window_nbytes(self):
        """Bytes of every window layer's two rings."""
        if self.window is None:
            return 0
        s = self.window.struct
        return (2 * self.window_layers * math.prod(s.shape)
                * np.dtype(s.dtype).itemsize)

    @property
    def nbytes(self):
        return ((self.kv.nbytes if self.kv else 0) + self.state_nbytes
                + self.window_nbytes)

    # a page layer's read and write are its PlainKV's
    def prefill(self, *args):
        return self.kv.prefill(*args)

    def decode(self, *args):
        return self.kv.decode(*args)

    def layer_step(self, li, mode, slot=None):
        if self.kinds[li] != "window":
            return super().layer_step(li, mode, slot)
        if mode == "prefill":
            return functools.partial(self.window.prefill, slot=slot)
        return self.window.decode

    def recur(self, fn, conv, ssm, lens, slot, values):
        return self.state.recur(fn, conv, ssm, lens, slot, values)

    def slot_operands(self, slot):
        return ((np.array([slot], np.int32),)
                if self.state or self.window else ())

    def prefill_attrs(self, tokens, bucket):
        attrs = {}
        if self.state is not None:
            attrs.update(scan_tokens=tokens,
                         recurrence=self.state.recurrence,
                         scan_chunks=self.state_layers
                         * self.state.chunks(bucket))
        if self.window is not None:
            # `window_rows`: the rows this prefill writes into rings
            attrs.update(window=self.window.window,
                         window_layers=self.window_layers,
                         window_rows=self.window_layers * min(
                             tokens, self.window.window))
        return attrs

    def decode_attrs(self, live):
        return ({"state_rows": live * self.state_layers,
                 "recurrence": self.state.recurrence} if self.state
                else {})

    def live_rows(self, lens):
        return self.window.live_rows(lens) if self.window else 0

    # ------------------------------------------------------- hand-off
    def exported_pages(self, layers):
        if self.kv is None:
            return 0
        return self.kv.exported_pages([layers[self.kinds.index("kv")]])

    def _no_window_handoff(self):
        if self.window is not None:
            raise ValueError(
                "a 'window' layer's ring is not handed off: export / "
                "import of a per-layer pool with window layers is not "
                "built")

    def export(self, pools, pages, slot=None):
        self._no_window_handoff()
        layers = []
        for li, kind in enumerate(self.kinds):
            first, second = pools[0][li], pools[1][li]
            if kind == "state":
                layers.append({"conv": np.asarray(first[slot]),
                               "ssm": np.asarray(second[slot])})
            else:
                layers.append(self.kv.export(([first], [second]), pages)[0])
        return layers

    def import_(self, pools, idx, layers, slot=None):
        self._no_window_handoff()
        halves = ([], [])
        for li, kind in enumerate(self.kinds):
            first, second = pools[0][li], pools[1][li]
            if kind == "state":
                first = first.at[slot].set(jnp.asarray(layers[li]["conv"]))
                second = second.at[slot].set(jnp.asarray(layers[li]["ssm"]))
            else:
                (first,), (second,) = self.kv.import_(
                    ([first], [second]), idx, [layers[li]])
            halves[0].append(first)
            halves[1].append(second)
        return halves


def _grouped_kv(cfg, num_layers, spec):
    """The :class:`GroupedKV` a ``kv`` declaration with its own geometry
    asks for: ``num_heads x head_dim``, optionally ``query_heads``,
    ``scale`` and ``causal_block``."""
    heads, dim = int(spec["num_heads"]), int(spec["head_dim"])
    return GroupedKV(cfg, num_layers, heads, dim,
                     int(spec.get("query_heads", heads)),
                     spec.get("scale", dim ** -0.5),
                     spec.get("causal_block", 1))


def make_page_pool(model, cfg, mesh=None):
    """The pool of the kind `model` declares (``kv_cache_spec()``; no
    declaration is GPT's K and V of ``num_heads x head_dim``; a ``"kv"``
    declaration with a geometry of its own — ``num_heads``, ``head_dim``,
    ``query_heads`` — is grouped-query K/V for the whole model;
    ``"layers"`` is a declaration per layer), at
    `cfg`'s page geometry, dtype and ``kv_cache_dtype``, for the
    RESOLVED `mesh` (None off-mesh).  A kind that cannot shard or cannot
    narrow raises ``ValueError`` by name."""
    spec = (model.kv_cache_spec() if hasattr(model, "kv_cache_spec")
            else {"kind": "kv"})
    if spec["kind"] == "latent":
        return LatentPool(cfg, spec, mesh)
    if spec["kind"] == "layers":
        return LayeredPool(cfg, spec["layers"], mesh)
    if spec["kind"] != "kv":
        raise ValueError(f"unknown kv cache kind {spec['kind']!r}")
    mc = model.config
    if "num_heads" in spec:
        if cfg.kv_cache_dtype is not None:
            raise ValueError(
                f"kv_cache_dtype={cfg.kv_cache_dtype!r}: grouped-query "
                f"K/V pages are stored plain at the engine's dtype")
        if mesh is not None:
            raise ValueError(
                "mesh: grouped-query K/V pages are row pages on one "
                "device (no head axis to shard)")
        return _grouped_kv(cfg, mc.num_layers, spec)
    heads = int(mc.num_heads)
    args = (cfg, mc.num_layers, heads, int(mc.hidden_size) // heads, mesh)
    # kv_cache_dtype narrows the pool STORAGE only
    quant = resolve_kv_cache_dtype(cfg.kv_cache_dtype)
    return PlainKV(*args) if quant is None else QuantizedKV(*args, quant)
