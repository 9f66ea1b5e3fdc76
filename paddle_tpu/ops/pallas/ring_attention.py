"""Ring flash attention: Pallas flash blocks + ppermute ring (context
parallel).

Reference parity: ring/P2P sequence-parallel attention in reference-derived
suites (NCCL send/recv of k/v chunks overlapping per-chunk CUDA flash
kernels). TPU-native design: the per-step block attention is the Pallas
flash kernel (returning per-row lse so steps merge online-softmax style);
k/v chunks rotate with `lax.ppermute` over ICI; `lax.scan` +
`jax.checkpoint` keep residual memory at O(local chunk). The block kernel
carries a custom VJP for BOTH outputs (o, lse) — the lse cotangent folds
into the flash backward's delta term (ds = p·(dp − (Δ − d_lse))) — so
reverse-mode AD through the scan yields the reverse ring for free.

Chunk-level causality is resolved with `lax.switch` on the (traced) chunk
relation: fully-future chunks contribute a zero block (lse = −inf), the
diagonal chunk runs the causal kernel (skipping above-diagonal tiles), past
chunks run the dense kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops.pallas.flash_attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    _flash_block,
    _NEG_INF,
)
from paddle_tpu.distributed import mesh as mesh_mod


def _merge(o1, lse1, o2, lse2):
    """Merge two normalized partial-attention results by their lse.

    Rows no block has touched carry the (finite) _NEG_INF sentinel; compare
    against _NEG_INF/2 — NOT isfinite — to keep them inert."""
    m = jnp.maximum(lse1, lse2)
    seen = m > _NEG_INF / 2
    m_safe = jnp.where(seen, m, 0.0)
    w1 = jnp.exp(lse1 - m_safe)
    w2 = jnp.exp(lse2 - m_safe)
    tot = w1 + w2
    tot_safe = jnp.where(tot == 0.0, 1.0, tot)
    # fp32 out: the running accumulator must not round to bf16 every step
    o = (o1.astype(jnp.float32) * w1[..., None] +
         o2.astype(jnp.float32) * w2[..., None]) / tot_safe[..., None]
    lse = jnp.where(seen, m_safe + jnp.log(tot_safe), m)
    return o, lse


def ring_flash_attention(q, k, v, axis_name="sp", causal=False, scale=None,
                         axis_size=None, block_q=None, block_k=None,
                         interpret=None):
    """Ring attention with Pallas flash blocks, inside a shard_map body.

    q/k/v: [batch, heads, s_local, head_dim]; sequence sharded contiguously
    over `axis_name` (chunk index == axis index). Exact (matches full
    attention), differentiable, O(s_local²/ring-step) work on the diagonal.
    """
    if interpret is None:
        from paddle_tpu.ops.pallas import on_tpu
        interpret = not on_tpu()
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    scale = float(scale)
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    n = mesh_mod.resolve_axis_size(axis_name, axis_size)

    def blk(qx, kx, vx, c):
        # positional-only: custom_vjp rejects keyword args at call time
        return _flash_block(qx, kx, vx, c, scale, block_q, block_k,
                            bool(interpret))

    if n == 1:
        o, _ = blk(q, k, v, causal)
        return o

    my_idx = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]

    def zero_block(qx, kx, vx):
        return (jnp.zeros((b, h, sq, d), qx.dtype),
                jnp.full((b, h, sq), _NEG_INF, jnp.float32))

    def causal_block(qx, kx, vx):
        return blk(qx, kx, vx, True)

    def dense_block(qx, kx, vx):
        return blk(qx, kx, vx, False)

    def accumulate(o, lse, kt, vt, t):
        if causal:
            kv_idx = (my_idx - t) % n
            branch = jnp.where(kv_idx > my_idx, 0,
                               jnp.where(kv_idx == my_idx, 1, 2))
            ob, lseb = lax.switch(branch,
                                  [zero_block, causal_block, dense_block],
                                  q, kt, vt)
        else:
            ob, lseb = dense_block(q, kt, vt)
        return _merge(o, lse, ob, lseb)

    def step(carry, t):
        # permute at loop entry — n-1 ring hops, not n (t=0 runs pre-scan)
        o, lse, kt, vt = carry
        kt = lax.ppermute(kt, axis_name, perm)
        vt = lax.ppermute(vt, axis_name, perm)
        o, lse = accumulate(o, lse, kt, vt, t)
        return (o, lse, kt, vt), None

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    lse0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    o, lse = accumulate(o0, lse0, k, v, 0)
    carry, _ = lax.scan(jax.checkpoint(step), (o, lse, k, v),
                        jnp.arange(1, n))
    return carry[0].astype(q.dtype)


def ring_flash_attention_bshd(q, k, v, causal=False, scale=None,
                              axis_name="sp", mesh=None, interpret=None):
    """Whole-array wrapper: [batch, seq, heads, head_dim], seq sharded over
    `axis_name` of the mesh; owns the shard_map."""
    from paddle_tpu.distributed.context_parallel import wrap_bshd
    mesh = mesh or mesh_mod.ensure_mesh()
    fn = functools.partial(ring_flash_attention, axis_name=axis_name,
                           causal=causal, scale=scale,
                           axis_size=mesh.shape[axis_name],
                           interpret=interpret)
    return wrap_bshd(fn, q, k, v, axis_name, mesh)
