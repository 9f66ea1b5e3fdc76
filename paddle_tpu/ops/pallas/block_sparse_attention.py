"""Block-sparse flash attention (Pallas) — the sparse_attention fast path.

Reference role: python/paddle/nn/functional/sparse_attention.py wraps a
CUDA kernel that computes attention only at the CSR-described positions.
TPU-native design: sparsity is expressed at BLOCK granularity (the MXU
computes (block_q x block_k) tiles or nothing), and the kernel never
visits an inactive block at all — a host-built table lists, for every
q-block, its active k-blocks padded to the row maximum, and the grid's
innermost dimension walks that table (the splash-attention structure:
work is proportional to the ACTIVE block count, not seq²). The table
rides in scalar-prefetch memory so the K/V BlockSpec index maps read it
to DMA exactly the active blocks.

Supports the patterns block-sparse attention exists for — sliding
window, global tokens, blocked-causal, arbitrary static masks — via
`make_block_mask` helpers or any [nq, nk] boolean array. The pattern
must be CONCRETE (host numpy): sparsity layouts are architectural
constants, not data.

Backward: custom VJP recomputes with the same active-block tables
(dq walks the q-row tables; dk/dv walk the transposed k-column tables),
so the backward is block-sparse too.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.flash_attention import (_LOG2E, _LN2, _NEG_INF,
                                                  _LSE_LANES, _compiler_params,
                                                  _pad_to)

__all__ = ["block_sparse_attention", "block_sparse_flash_attention",
           "prepare_block_mask", "make_sliding_window_mask",
           "make_global_plus_window_mask", "block_mask_tables"]


def make_sliding_window_mask(nq, nk, window_blocks, causal=True):
    """[nq, nk] bool: each q-block attends its diagonal neighborhood."""
    qi = np.arange(nq)[:, None]
    ki = np.arange(nk)[None, :]
    m = np.abs(qi - ki) < window_blocks
    if causal:
        m &= ki <= qi
    return m


def make_global_plus_window_mask(nq, nk, window_blocks, global_blocks,
                                 causal=True):
    """Sliding window + the first `global_blocks` k-blocks visible to
    every query (the Longformer/BigBird pattern at block granularity)."""
    m = make_sliding_window_mask(nq, nk, window_blocks, causal)
    m[:, :global_blocks] = True
    if causal:
        m &= np.arange(nk)[None, :] <= np.arange(nq)[:, None]
    return m


def block_mask_tables(block_mask):
    """Host-side: [nq, nk] bool -> (kt, counts, max_active) where
    kt[qi, j] is the j-th active k-block of q-row qi (padded with the
    row's last active block so padded steps re-DMA a resident block and
    the copy is elided)."""
    bm = np.asarray(block_mask, bool)
    nq, nk = bm.shape
    counts = bm.sum(1).astype(np.int32)
    max_active = int(counts.max()) if counts.size else 0
    if max_active == 0:
        raise ValueError("block mask has no active blocks")
    kt = np.zeros((nq, max_active), np.int32)
    for qi in range(nq):
        act = np.nonzero(bm[qi])[0]
        if len(act) == 0:
            act = np.array([0])
        kt[qi, :len(act)] = act
        kt[qi, len(act):] = act[-1]
    return kt, counts, max_active


def _fwd_kernel(kt_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, block_q, block_k,
                num_steps, seq_k):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < cnt_ref[qi])
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = (scale * _LOG2E) * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if seq_k % block_k:
            # ragged tail: zero-padded K tokens must not enter the
            # softmax denominator (phantom e^0 weights)
            col = kt_ref[qi, j] * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(col < seq_k, s, _NEG_INF)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_steps - 1)
    def _finalize():
        m = m_ref[:, 0:1]
        l = l_ref[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m * _LN2 + jnp.log(l_safe),
                                      lse_ref[0].shape)


def _bsa_fwd(q, k, v, kt, counts, scale, block_q, block_k, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qp = _pad_to(q, block_q, 2)
    kp = _pad_to(k, block_k, 2)
    vp = _pad_to(v, block_k, 2)
    bh = b * h
    qp = qp.reshape(bh, -1, d)
    kp = kp.reshape(bh, -1, d)
    vp = vp.reshape(bh, -1, d)
    nq = qp.shape[1] // block_q
    max_active = kt.shape[1]

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        num_steps=max_active, seq_k=sk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq, max_active),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda bhid, qi, j, kt_, cnt_: (bhid, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bhid, qi, j, kt_, cnt_:
                         (bhid, kt_[qi, j], 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bhid, qi, j, kt_, cnt_:
                         (bhid, kt_[qi, j], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda bhid, qi, j, kt_, cnt_: (bhid, qi, 0)),
            pl.BlockSpec((1, block_q, _LSE_LANES),
                         lambda bhid, qi, j, kt_, cnt_: (bhid, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    o, lse8 = pl.pallas_call(
        kernel,
        name="block_sparse_fwd",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, qp.shape[1], d), q.dtype),
            jax.ShapeDtypeStruct((bh, qp.shape[1], _LSE_LANES),
                                 jnp.float32),
        ],
        compiler_params=_compiler_params(("parallel", "parallel",
                                          "arbitrary")),
        interpret=interpret,
    )(kt, counts, qp, kp, vp)
    o = o.reshape(b, h, -1, d)[:, :, :sq, :]
    lse = lse8[:, :, 0].reshape(b, h, -1)[:, :, :sq]
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def block_sparse_flash_attention(q, k, v, block_mask_key, scale, block_q,
                                 block_k, interpret):
    """q/k/v: [batch, heads, seq_q, d] / [.., seq_k, d].

    block_mask_key: a _BlockMaskTables from prepare_block_mask() (hashable
    static carrier of the host-side tables). Returns [b, h, seq_q, d].
    """
    o, _ = _bsa_fwd(q, k, v, block_mask_key.kt_arr(),
                    block_mask_key.cnt_arr(), scale, block_q, block_k,
                    interpret)
    return o


class _BlockMaskTables:
    """Hashable static carrier for the block tables (custom_vjp nondiff
    args must be hashable)."""

    def __init__(self, block_mask, block_q, block_k):
        self.kt, self.counts, self.max_active = block_mask_tables(
            block_mask)
        bm = np.asarray(block_mask, bool)
        # transpose tables for dk/dv: active q-blocks per k-block
        self.qt, self.qcounts, self.qmax = block_mask_tables(bm.T)
        self._key = (bm.tobytes(), bm.shape, block_q, block_k)

    def kt_arr(self):
        return jnp.asarray(self.kt)

    def cnt_arr(self):
        return jnp.asarray(self.counts)

    def qt_arr(self):
        return jnp.asarray(self.qt)

    def qcnt_arr(self):
        return jnp.asarray(self.qcounts)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _BlockMaskTables) and \
            self._key == other._key


def prepare_block_mask(block_mask, block_q, block_k):
    return _BlockMaskTables(block_mask, block_q, block_k)


def _bsa_fwd_rule(q, k, v, tables, scale, block_q, block_k, interpret):
    o, lse = _bsa_fwd(q, k, v, tables.kt_arr(), tables.cnt_arr(), scale,
                      block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _bsa_bwd_rule(tables, scale, block_q, block_k, interpret, res, do):
    """Block-sparse backward by recompute: dq accumulates over each
    q-row's active k-blocks; dk/dv over each k-column's active q-blocks.
    Implemented with jnp gathers over the SAME tables (one fused XLA
    loop per direction) — the FLOP count is proportional to the active
    blocks, matching the forward's sparsity."""
    q, k, v, o, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    kt = tables.kt_arr()
    cnt = tables.cnt_arr()
    qt = tables.qt_arr()
    qcnt = tables.qcnt_arr()
    nq = kt.shape[0]
    nk = qt.shape[0]

    qb = _pad_to(q, block_q, 2).reshape(b * h, nq, block_q, d)
    kb = _pad_to(k, block_k, 2).reshape(b * h, nk, block_k, d)
    vb = _pad_to(v, block_k, 2).reshape(b * h, nk, block_k, d)
    dob = _pad_to(do, block_q, 2).reshape(b * h, nq, block_q, d)
    lseb = _pad_to(lse, block_q, 2).reshape(b * h, nq, block_q)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    deltab = _pad_to(delta, block_q, 2).reshape(b * h, nq, block_q)

    def p_block(qx, kx, ls, kj):
        s = jnp.einsum("bqd,bkd->bqk", qx.astype(jnp.float32),
                       kx.astype(jnp.float32)) * scale
        if sk % block_k:
            col = kj * block_k + jnp.arange(block_k)
            s = jnp.where(col[None, None, :] < sk, s, -jnp.inf)
        return jnp.exp(s - ls[..., None])

    # ---- dq: walk each q-row's active k-blocks ----
    def dq_row(qi, carry):
        dq = carry

        def step(j, acc):
            kj = kt[qi, j]
            kx = kb[:, kj]
            vx = vb[:, kj]
            p = p_block(qb[:, qi], kx, lseb[:, qi], kj)
            dp = jnp.einsum("bqd,bkd->bqk", dob[:, qi].astype(jnp.float32),
                            vx.astype(jnp.float32))
            ds = p * (dp - deltab[:, qi][..., None])
            upd = scale * jnp.einsum("bqk,bkd->bqd", ds,
                                     kx.astype(jnp.float32))
            return acc + jnp.where(j < cnt[qi], upd, 0.0)

        row = jax.lax.fori_loop(0, kt.shape[1], step,
                                jnp.zeros_like(dq[:, qi]))
        return dq.at[:, qi].set(row)

    dq = jax.lax.fori_loop(
        0, nq, dq_row, jnp.zeros_like(qb, jnp.float32))

    # ---- dk/dv: walk each k-column's active q-blocks ----
    def dkv_col(ki, carry):
        dk, dv = carry

        def step(j, accs):
            ak, av = accs
            qi = qt[ki, j]
            p = p_block(qb[:, qi], kb[:, ki], lseb[:, qi], ki)
            dvu = jnp.einsum("bqk,bqd->bkd", p,
                             dob[:, qi].astype(jnp.float32))
            dp = jnp.einsum("bqd,bkd->bqk", dob[:, qi].astype(jnp.float32),
                            vb[:, ki].astype(jnp.float32))
            ds = p * (dp - deltab[:, qi][..., None])
            dku = scale * jnp.einsum("bqk,bqd->bkd", ds,
                                     qb[:, qi].astype(jnp.float32))
            keep = j < qcnt[ki]
            return (ak + jnp.where(keep, dku, 0.0),
                    av + jnp.where(keep, dvu, 0.0))

        ck, cv = jax.lax.fori_loop(
            0, qt.shape[1], step,
            (jnp.zeros_like(dk[:, ki]), jnp.zeros_like(dv[:, ki])))
        return dk.at[:, ki].set(ck), dv.at[:, ki].set(cv)

    dk, dv = jax.lax.fori_loop(
        0, nk, dkv_col,
        (jnp.zeros_like(kb, jnp.float32), jnp.zeros_like(vb, jnp.float32)))

    dq = dq.reshape(b, h, -1, d)[:, :, :sq].astype(q.dtype)
    dk = dk.reshape(b, h, -1, d)[:, :, :sk].astype(k.dtype)
    dv = dv.reshape(b, h, -1, d)[:, :, :sk].astype(v.dtype)
    return dq, dk, dv


block_sparse_flash_attention.defvjp(_bsa_fwd_rule, _bsa_bwd_rule)


def block_sparse_attention(q, k, v, block_mask, block_q=512, block_k=512,
                           scale=None, interpret=None):
    """Public entry: q/k/v [batch, heads, seq, d]; block_mask [nq, nk]
    bool (host numpy) with nq = ceil(seq_q/block_q), nk =
    ceil(seq_k/block_k). Work and DMA are proportional to the ACTIVE
    block count."""
    if interpret is None:
        from paddle_tpu.ops.pallas import on_tpu
        interpret = not on_tpu()
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    nq = -(-q.shape[2] // block_q)
    nk = -(-k.shape[2] // block_k)
    bm = np.asarray(block_mask, bool)
    if bm.shape != (nq, nk):
        raise ValueError(
            f"block_mask shape {bm.shape} != (ceil(sq/bq), ceil(sk/bk)) "
            f"= {(nq, nk)}")
    tables = prepare_block_mask(bm, block_q, block_k)
    return block_sparse_flash_attention(q, k, v, tables, float(scale),
                                        block_q, block_k, bool(interpret))
