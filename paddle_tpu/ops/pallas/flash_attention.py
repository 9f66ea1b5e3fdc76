"""Flash attention for TPU (Pallas), forward + custom-VJP backward.

Reference parity: the reference exposes fused attention via
paddle.incubate.nn.functional.fused_attention / flash-attn CUDA kernels
(paddle/phi/kernels/gpu/flash_attn_kernel.cu in later branches). TPU-native
design: an online-softmax kernel tiled for the MXU with a 3-D grid
(batch*heads, q-blocks, k-blocks) — K/V stream through VMEM one
`block_k` slice at a time (so 16k+ sequences never pin the whole K/V in
the ~16MB VMEM), the running (acc, m, l) state lives in VMEM scratch that
persists across the innermost k-block grid dimension, and causal blocks
strictly above the diagonal skip their compute via `pl.when`.

Mosaic tiling: every block's trailing two dims are either (8,128)-aligned
or cover the full array dim. The log-sum-exp is carried as a
`[bh, seq, 8]` array (the scalar per row replicated across 8 lanes) —
a `(block_q, 8)` tile is legal where the naive `(1, block_q)` block that
round 2 shipped is not.

Layouts: public entry `flash_attention_bshd` takes paddle's [batch, seq,
heads, head_dim]; kernels run in [batch, heads, seq, head_dim].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Hard dependency: the 3-D-grid kernels carry their online-softmax state in
# VMEM scratch (pltpu.VMEM), which interpret mode also supports — a JAX
# build without pallas.tpu cannot run this module at all.
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
_NEG_INF = -1e30
_LSE_LANES = 8  # lse/delta replicated across this many lanes for tiling


def _vmem_spec(*args, **kwargs):
    kwargs["memory_space"] = _VMEM
    return pl.BlockSpec(*args, **kwargs)


def _compiler_params(dims):
    return pltpu.CompilerParams(dimension_semantics=dims)


def _round_up(n, m):
    return -(-n // m) * m


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453


def _mask_block(s, qi, ki, block_q, block_k, causal, seq_k, seq_q=None):
    """Apply causal/edge masking to one [bq, bk] score tile. The mask is
    skipped STATICALLY when no block can need it (dense attention on
    block-aligned sequences) — a traced per-block `lax.cond` measures
    slower than just masking, so the only branch here is at trace time."""
    ragged = (seq_k % block_k != 0) or (
        seq_q is not None and seq_q % block_q != 0)
    if not causal and not ragged:
        return s
    bq, bk = s.shape
    col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = col < seq_k
    row = None
    if causal or seq_q is not None:
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
    if causal:
        mask = jnp.logical_and(mask, col <= row)
    if seq_q is not None:
        mask = jnp.logical_and(mask, row < seq_q)
    return jnp.where(mask, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale, causal, block_q, block_k, seq_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: blocks strictly above the diagonal contribute nothing — skip
    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else ki >= 0

    @pl.when(run)
    def _compute():
        # dots run in the input dtype (bf16 hits the MXU at full rate) with
        # fp32 accumulation; softmax statistics stay fp32 throughout.
        q = q_ref[0]                                      # [bq, d]
        k = k_ref[0]                                      # [bk, d]
        v = v_ref[0]
        # base-2 softmax: fold scale*log2(e) into the score multiply so the
        # per-element exp is a bare exp2; m/l are tracked in the log2 domain
        s = (scale * _LOG2E) * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk] f32
        s = _mask_block(s, qi, ki, block_q, block_k, causal, seq_k)
        m_prev = m_ref[:, 0:1]                            # [bq, 1]
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

    @pl.when(ki == nk - 1)
    def _finalize():
        m = m_ref[:, 0:1]                                 # log2-domain max
        l = l_ref[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m * _LN2 + jnp.log(l_safe),
                                      lse_ref[0].shape)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k, seq_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else ki >= 0

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse2 = lse_ref[0][:, 0:1] * _LOG2E               # log2 domain
        delta = delta_ref[0][:, 0:1]
        k = k_ref[0]
        v = v_ref[0]
        s = (scale * _LOG2E) * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = _mask_block(s, qi, ki, block_q, block_k, causal, seq_k)
        p = jnp.exp2(s - lse2)                            # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[...] = dq_acc[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, seq_q, seq_k):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # causal: q blocks strictly before this k block see none of it — skip
    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else qi >= 0

    @pl.when(run)
    def _compute():
        k = k_ref[0]                                      # [bk, d]
        v = v_ref[0]
        q = q_ref[0]                                      # [bq, d]
        do = do_ref[0]
        lse2 = lse_ref[0][:, 0:1] * _LOG2E               # log2 domain
        delta = delta_ref[0][:, 0:1]
        s = (scale * _LOG2E) * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = _mask_block(s, qi, ki, block_q, block_k, causal, seq_k,
                        seq_q=seq_q)
        p = jnp.exp2(s - lse2)                            # [bq, bk]
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pick_blocks(sq, sk, block_q, block_k):
    """Clamp block sizes to the (16-aligned) sequence lengths so short
    sequences get a single full-array block (always Mosaic-legal)."""
    return (min(block_q, _round_up(sq, 16)), min(block_k, _round_up(sk, 16)))


def _flash_bhsd(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_block(q, k, v, causal, scale, block_q, block_k,
                        interpret)[0]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q, block_k = _pick_blocks(sq, sk, block_q, block_k)
    qp = _pad_to(q, block_q, 2)
    kp = _pad_to(k, block_k, 2)
    vp = _pad_to(v, block_k, 2)
    sqp, skp = qp.shape[2], kp.shape[2]
    qp = qp.reshape(b * h, sqp, d)
    kp = kp.reshape(b * h, skp, d)
    vp = vp.reshape(b * h, skp, d)

    grid = (b * h, sqp // block_q, skp // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_k=sk)
    # causal: clamp the k index map to the diagonal so the skipped
    # above-diagonal steps re-map to an already-resident block and Pallas
    # elides their K/V DMA entirely (pl.when alone skips compute, not the
    # prefetch)
    if causal:
        def kv_index(bh, qi, ki):
            return (bh, jnp.minimum(
                ki, (qi * block_q + block_q - 1) // block_k), 0)
    else:
        def kv_index(bh, qi, ki):
            return (bh, ki, 0)
    o, lse8 = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            _vmem_spec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            _vmem_spec((1, block_k, d), kv_index),
            _vmem_spec((1, block_k, d), kv_index),
        ],
        out_specs=[
            _vmem_spec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            _vmem_spec((1, block_q, _LSE_LANES),
                       lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sqp, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sqp, _LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q, d)),
            _scratch((block_q, 128)),
            _scratch((block_q, 128)),
        ],
        compiler_params=_compiler_params(("parallel", "parallel",
                                          "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    o = o.reshape(b, h, sqp, d)[:, :, :sq, :]
    lse = lse8[:, :, 0].reshape(b, h, sqp)[:, :, :sq]
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_block(q, k, v, causal, scale, block_q, block_k, interpret):
    """Flash attention returning (o, lse); differentiable in both (ring
    attention merges blocks by their lse)."""
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_block_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    # through _flash_block, not the raw _flash_fwd: under nested
    # differentiation (recompute's backward takes a vjp of a region whose
    # ops each took their own) the outer trace then meets a custom_vjp
    # call it can linearize, never a raw pallas_call it would have to JVP
    o, lse = _flash_block(q, k, v, causal, scale, block_q, block_k,
                          interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_block_bwd(causal, scale, block_q, block_k, interpret, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                    # [b,h,sq]
    # rows that never saw a key (lse == _NEG_INF sentinel, which is a
    # finite -1e30) have p == 0 everywhere — drop their lse cotangent
    delta = delta - jnp.where(lse > _NEG_INF / 2,
                              dlse.astype(jnp.float32), 0.0)
    return _flash_bwd_impl(q, k, v, do, lse, delta, causal, scale,
                           block_q, block_k, interpret)


def _rep_lanes(x, block, bh):
    """[b,h,sq] → [bh, sq_padded, _LSE_LANES] (value replicated per lane)."""
    xp = _pad_to(x, block, 2).reshape(bh, -1)
    return jnp.broadcast_to(xp[..., None], xp.shape + (_LSE_LANES,))


def _flash_bwd_impl(q, k, v, do, lse, delta, causal, scale, block_q, block_k,
                    interpret):
    """dq/dk/dv given precomputed delta (= sum(do*o) for the plain kernel;
    ring attention folds the lse cotangent in as delta - dlse)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q, block_k = _pick_blocks(sq, sk, block_q, block_k)

    bh = b * h
    qp = _pad_to(q, block_q, 2).reshape(bh, -1, d)
    dop = _pad_to(do, block_q, 2).reshape(bh, -1, d)
    lsep = _rep_lanes(lse, block_q, bh)
    deltap = _rep_lanes(delta, block_q, bh)
    kp = _pad_to(k, block_k, 2).reshape(bh, -1, d)
    vp = _pad_to(v, block_k, 2).reshape(bh, -1, d)
    sqp, skp = qp.shape[1], kp.shape[1]
    nq, nk = sqp // block_q, skp // block_k

    # causal DMA elision (see _flash_fwd): skipped blocks re-map to a
    # resident block index so their copies are elided
    if causal:
        def kv_index(bh, qi, ki):
            return (bh, jnp.minimum(
                ki, (qi * block_q + block_q - 1) // block_k), 0)

        def q_index(bh, ki, qi):
            return (bh, jnp.maximum(qi, (ki * block_k) // block_q), 0)
    else:
        def kv_index(bh, qi, ki):
            return (bh, ki, 0)

        def q_index(bh, ki, qi):
            return (bh, qi, 0)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_k=sk)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_dq",
        grid=(bh, nq, nk),
        in_specs=[
            _vmem_spec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            _vmem_spec((1, block_k, d), kv_index),
            _vmem_spec((1, block_k, d), kv_index),
            _vmem_spec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            _vmem_spec((1, block_q, _LSE_LANES),
                       lambda bh, qi, ki: (bh, qi, 0)),
            _vmem_spec((1, block_q, _LSE_LANES),
                       lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=_vmem_spec((1, block_q, d),
                             lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d))],
        compiler_params=_compiler_params(("parallel", "parallel",
                                          "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=sq, seq_k=sk)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_dkv",
        grid=(bh, nk, nq),
        in_specs=[
            _vmem_spec((1, block_q, d), q_index),
            _vmem_spec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            _vmem_spec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            _vmem_spec((1, block_q, d), q_index),
            _vmem_spec((1, block_q, _LSE_LANES), q_index),
            _vmem_spec((1, block_q, _LSE_LANES), q_index),
        ],
        out_specs=[
            _vmem_spec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            _vmem_spec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skp, d), k.dtype),
            jax.ShapeDtypeStruct((bh, skp, d), v.dtype),
        ],
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        compiler_params=_compiler_params(("parallel", "parallel",
                                          "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)

    dq = dq.reshape(b, h, sqp, d)[:, :, :sq, :]
    dk = dk.reshape(b, h, skp, d)[:, :, :sk, :]
    dv = dv.reshape(b, h, skp, d)[:, :, :sk, :]
    return dq, dk, dv


_flash_block.defvjp(_flash_block_fwd, _flash_block_bwd)


def _on_tpu():
    from paddle_tpu.ops.pallas import on_tpu
    return on_tpu()


def flash_attention_bshd(q, k, v, causal=False, scale=None, block_q=None,
                         block_k=None, interpret=None):
    """Flash attention on [batch, seq, heads, head_dim] inputs (paddle
    layout). Differentiable (custom VJP). Raises on CPU unless
    `interpret=True` — callers pick the XLA sdpa path there."""
    if interpret is None:
        interpret = False
        if not _on_tpu():
            raise NotImplementedError(
                "pallas flash attention requires TPU (or interpret=True)")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    scale = float(scale)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    o = _flash_bhsd(qt, kt, vt, bool(causal), scale,
                    block_q or DEFAULT_BLOCK_Q, block_k or DEFAULT_BLOCK_K,
                    bool(interpret))
    return jnp.transpose(o, (0, 2, 1, 3))


def flash_attention_bhsd(q, k, v, causal=False, scale=None, **kw):
    """Same kernel on [batch, heads, seq, head_dim] inputs."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    kw.setdefault("interpret", not _on_tpu())
    return _flash_bhsd(q, k, v, bool(causal), float(scale),
                       kw.get("block_q") or DEFAULT_BLOCK_Q,
                       kw.get("block_k") or DEFAULT_BLOCK_K,
                       bool(kw["interpret"]))
