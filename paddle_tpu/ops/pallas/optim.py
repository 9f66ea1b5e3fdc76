"""Fused single-pass optimizer update kernels.

The per-param Python loop in ``Optimizer.step`` emits ~30 small HLO ops
per parameter — the PR 8 roofline attributed ~35x param-bytes per step
to ``optimizer.step`` (the top row of the whole train program, 40% of
gpt_hybrid_train's bytes).  Algebra can't fix that: the cost model (and
the pre-fusion HLO) charges every elementwise intermediate.  A fused
kernel can: one ``pallas_call`` per parameter reads p, g, m, v exactly
once and writes p', m', v' exactly once (~7x param bytes; ~5x with
bf16 moments), with the update math in f32 registers.

CPU runs the same kernel in interpret mode (pure-JAX numerics, same
traced program — so tools/perfgate.py's deterministic budget measures
the real fused traffic).  Traced scalars (lr, bias corrections) ride in
one (1, 4) f32 operand so LR schedules never retrigger compilation.

Update math is kept EQN-FOR-EQN identical to the unfused
``Adam._update_param`` / ``AdamW._update_param`` path (same op order,
division by (1-beta^t) rather than multiply-by-reciprocal), so the
fused step is numerically interchangeable with the loop it replaces —
tests/test_bytesopt.py pins them allclose at 1e-6.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas.norm import _vmem_spec

__all__ = ["fused_adam_update", "supports_fused"]

# per-operand block-bytes ceiling: a grid step holds 7 row blocks
# (p, g, m, v in; p', m', v' out), each double-buffered by the pipeline,
# inside Mosaic's 16 MiB scoped-VMEM default — 14 x 512 KiB = 7 MiB
# leaves the rest for the f32 update temporaries
_BLOCK_BYTES = 1 << 19


def supports_fused(shape):
    """The fused kernel handles rank-2 parameters (the natural MXU
    layout every Linear/Embedding weight already has).  Rank-1 biases
    and norm scales stay on the unfused loop — they are <1% of the
    bytes and a reshape eqn per operand would cost more than it saves."""
    return len(tuple(shape)) == 2


def _pick_block_rows(rows, row_bytes):
    """Largest power-of-two row block that divides `rows` and keeps a
    block under _BLOCK_BYTES; falls back to the whole array (single
    block) for odd row counts."""
    br = 8
    while br * 2 <= rows and rows % (br * 2) == 0 \
            and (br * 2) * row_bytes <= _BLOCK_BYTES:
        br *= 2
    if rows % br != 0:
        return rows
    return br


def _adam_kernel(sc_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref,
                 vo_ref, *gq_ref, beta1, beta2, eps, weight_decay,
                 guard=False):
    """One fused AdamW step for one row block.

    sc = [lr, 1-beta1^t, 1-beta2^t, decay_on] — the traced scalars.
    Matches the unfused loop exactly: decoupled decay first (AdamW),
    then moment updates, bias correction by DIVISION, update, apply.

    ``guard=True`` (the training-sentinel probe) additionally reduces
    the block's gradient sum-of-squares in f32 — g is ALREADY in
    registers, so the probe adds zero extra HBM traffic — writes it to
    the per-block partials output, and GATES the block's commit on its
    finiteness: a block whose gradients are non-finite writes back the
    UNMODIFIED p/m/v (the zero-update skip), selected per step by data
    so the compiled program never changes."""
    lr = sc_ref[0, 0]
    c1 = sc_ref[0, 1]
    c2 = sc_ref[0, 2]
    decay_on = sc_ref[0, 3]
    p0 = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    p = p0
    if weight_decay:
        # decoupled (AdamW) decay; decay_on gates it per-param
        # (apply_decay_param_fun) without a second kernel variant
        p = p * (1.0 - decay_on * lr * weight_decay)
    new_m = beta1 * m + (1.0 - beta1) * g
    new_v = beta2 * v + (1.0 - beta2) * g * g
    mhat = new_m / c1
    vhat = new_v / c2
    upd = lr * mhat / (jnp.sqrt(vhat) + eps)
    new_p = p - upd
    if guard:
        gsq = jnp.sum(g * g)
        good = jnp.isfinite(gsq)
        # jnp.where, not multiply: NaN * 0 is NaN, select is clean
        new_p = jnp.where(good, new_p, p0)
        new_m = jnp.where(good, new_m, m)
        new_v = jnp.where(good, new_v, v)
        gq_ref[0][:] = jnp.full(gq_ref[0].shape, gsq, jnp.float32)
    po_ref[:] = new_p.astype(po_ref.dtype)
    mo_ref[:] = new_m.astype(mo_ref.dtype)
    vo_ref[:] = new_v.astype(vo_ref.dtype)


def fused_adam_update(p, g, m, v, lr, c1, c2, *, beta1, beta2, eps,
                      weight_decay=0.0, decay_on=True, guard=False,
                      interpret=None):
    """Single-pass Adam/AdamW update of one rank-2 parameter.

    Returns ``(p', m', v')``.  ``lr``/``c1``/``c2`` are traced scalars
    (learning rate and the 1-beta^t bias corrections); ``beta1/beta2/
    eps/weight_decay`` are static.  ``weight_decay`` non-zero applies
    DECOUPLED decay (AdamW) gated by ``decay_on``; plain Adam passes 0
    and handles coupled decay in the gradient as before.  Moments keep
    their storage dtype (bf16 moments read/write half the bytes; math
    stays f32 in-kernel).

    ``guard=True`` returns ``(p', m', v', partials)`` where
    ``partials[i, 0]`` is row-block ``i``'s gradient sum-of-squares
    (f32, reduced in-kernel — the sentinel probe's zero-extra-read
    path) and each block's commit is gated on its own finiteness (the
    zero-update skip; docs/resilience.md "Numerics sentinel" has the
    region-granularity contract).  The kernel writes each block scalar
    as one full (8, 128) f32 tile — the smallest block Mosaic accepts —
    of which one row per block is returned; the caller reads column 0.
    """
    if interpret is None:
        from paddle_tpu.ops.pallas import on_tpu
        interpret = not on_tpu()
    rows, cols = p.shape
    br = _pick_block_rows(rows, cols * 4)
    grid = (rows // br,)
    sc = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(c1, jnp.float32),
        jnp.asarray(c2, jnp.float32),
        jnp.asarray(1.0 if decay_on else 0.0, jnp.float32),
    ]).reshape(1, 4)
    kernel = functools.partial(_adam_kernel, beta1=float(beta1),
                               beta2=float(beta2), eps=float(eps),
                               weight_decay=float(weight_decay),
                               guard=bool(guard))
    blk = lambda i: (i, 0)          # noqa: E731 — row-block index map
    out_specs = [_vmem_spec((br, cols), blk) for _ in range(3)]
    out_shape = [
        jax.ShapeDtypeStruct(p.shape, p.dtype),
        jax.ShapeDtypeStruct(m.shape, m.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    if guard:
        out_specs.append(_vmem_spec((8, 128), blk))
        out_shape.append(
            jax.ShapeDtypeStruct((grid[0] * 8, 128), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        name="adamw_fused",
        grid=grid,
        in_specs=[_vmem_spec((1, 4), lambda i: (0, 0))]
        + [_vmem_spec((br, cols), blk) for _ in range(4)],
        out_specs=out_specs,
        out_shape=out_shape,
        # in-place param/moment updates: the donated input buffers ARE
        # the outputs on TPU (no extra HBM copies)
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
    )(sc, p, g, m, v)
    if guard:
        return (*outs[:3], outs[3][::8])
    return outs
