"""EQuARX-style quantized AllReduce — Plane 2 of the quantization
subsystem.

Motivated by EQuARX (Efficient Quantized AllReduce in XLA,
arXiv:2506.17615, PAPERS.md): multichip training is gradient-sync-bound
over ICI, and the predecessor here
(`distributed/quantized_collective.py`) still put an int32 tensor on
the wire and leaned on the compiler to pack it.  This module moves the
ACTUAL payload to int8, in the EQuARX shape:

1. **block-scale + quantize** — the local f32 tensor is padded to an
   ``[axis_size, blocks, block]`` grid and every ``block``-element
   chunk gets its own scale (``absmax/qmax``, 4 bytes per block); codes
   are int8.  Per-block scales bound the error locally — one outlier
   coarsens 256 neighbours, not the whole gradient.
2. **all_to_all in narrow dtype** — shard ``r`` of every rank's codes
   (and scales) lands on rank ``r``: the reduce-scatter phase at int8
   wire width.
3. **dequant + local reduce** — each rank dequantizes its n shard
   copies adjacent to their scales and sums them in f32 (exact given
   the codes; numlint NL101/NL301-clean by construction).
4. **requantize + all_gather in narrow dtype + final dequant** — the
   reduced shard goes back on the wire as fresh int8 codes + scales;
   every rank reassembles and dequantizes the full tensor.

Two rounding stages, each bounded by half a grid step per value, so
``|err| <= (n_ranks + 1) * scale / (2 * qmax)``-ish per block — the
loss-trajectory contract in tests/test_quantized_kv.py pins what that
means for training.  Optional stochastic rounding (a step-varying
``key``) keeps the stage-1 error unbiased over a trajectory.

Selection is the policy's job (:mod:`quantization.policy`):
``distributed.collective.all_reduce`` routes mesh-axis float SUM/AVG
here when a :class:`~paddle_tpu.quantization.policy.CollectivePolicy`
is active, and keeps the plain psum otherwise or off-mesh.

The wire accounting (:func:`quantized_all_reduce_wire_bytes`,
:func:`collective_wire_bytes`) is what perfgate's ``allreduce_bytes``
budget and the bench ``--worker-quant`` lane gate: the analytic model
is device-count-independent (deterministic in CI), and the traced
walker proves the lowered program's collectives carry the bytes the
model claims.

Module-level imports are jax-only so the analysis CLIs stay light.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.quantization.kv_cache import encode_int_codes as _encode

__all__ = ["collective_wire_bytes", "quantized_all_reduce",
           "quantized_all_reduce_wire_bytes"]


def _axis_size(axis_name):
    """Static extent of a named mesh axis."""
    return int(lax.axis_size(axis_name))


def quantized_all_reduce(x, axis_name, bits=8, block=256, key=None,
                         mean=False):
    """All-reduce `x` over `axis_name` with int8 wire payloads.

    Call INSIDE shard_map over the reduce axis.  `x`: local float array
    (any shape); returns f32 (cast back to ``x.dtype`` by the policy
    hook).  ``key``: optional PRNG key enabling stochastic rounding of
    the stage-1 payload — pass a STEP-VARYING key; it is folded with
    the rank index here so ranks round independently.  ``mean=True``
    divides by the axis size (the dp gradient-sync op).
    """
    qmax = float(2 ** (int(bits) - 1) - 1)
    n = _axis_size(axis_name)
    orig_shape, size = x.shape, x.size
    flat = x.astype(jnp.float32).reshape(-1)
    grid = n * int(block)
    padded = -(-max(size, 1) // grid) * grid
    if padded != size:
        flat = jnp.pad(flat, (0, padded - size))
    g = flat.reshape(n, padded // (n * block), block)

    # stage 1: per-block scale, int8 codes
    s1 = jnp.max(jnp.abs(g), axis=-1) / qmax            # [n, nb]
    safe1 = jnp.where(s1 > 0, s1, 1.0)
    if key is not None:
        key = jax.random.fold_in(key, lax.axis_index(axis_name))
    q1 = _encode(g / safe1[..., None], qmax, key)       # [n, nb, block]

    # reduce-scatter phase at int8 width: shard r of every rank -> rank r
    qt = lax.all_to_all(q1, axis_name, split_axis=0, concat_axis=0,
                        tiled=True)                     # [n, nb, block]
    st = lax.all_to_all(s1, axis_name, split_axis=0, concat_axis=0,
                        tiled=True)                     # [n, nb]
    partial = jnp.sum(qt.astype(jnp.float32) * st[..., None],
                      axis=0)                           # [nb, block] f32

    # stage 2: requantize the reduced shard, gather at int8 width
    s2 = jnp.max(jnp.abs(partial), axis=-1) / qmax      # [nb]
    safe2 = jnp.where(s2 > 0, s2, 1.0)
    q2 = _encode(partial / safe2[..., None], qmax, None)
    allq = lax.all_gather(q2, axis_name)                # [n, nb, block]
    alls = lax.all_gather(s2, axis_name)                # [n, nb]
    out = (allq.astype(jnp.float32) * alls[..., None]).reshape(padded)
    out = out[:size].reshape(orig_shape)
    if mean:
        out = out / n
    return out


def quantized_all_reduce_wire_bytes(n_elems, axis_size, bits=8,
                                    block=256, wide_bytes=4):
    """Deterministic wire-byte model for one all-reduce of `n_elems`.

    Counts the payload bytes each rank PUTS ON THE WIRE, with the
    ``(n-1)/n`` locality factor applied to both sides so the ratio is
    fair: the plain path is the textbook ring all-reduce
    (reduce-scatter + all-gather = ``2 * (n-1)/n`` x payload at
    `wide_bytes`); the quantized path moves int8 codes + f32 per-block
    scales through the same two phases.  Returns the dict the perfgate
    ``quantization`` target and the bench lane report.
    """
    del bits                        # codes travel as int8 at any bits<=8
    n = int(axis_size)
    grid = n * int(block)
    padded = -(-max(int(n_elems), 1) // grid) * grid
    scale_bytes = (padded // int(block)) * 4
    locality = (n - 1) / n if n > 1 else 1.0
    quant = 2 * locality * (padded + scale_bytes)
    wide = 2 * locality * int(wide_bytes) * int(n_elems)
    return {
        "allreduce_bytes": int(round(quant)),
        "allreduce_bytes_wide": int(round(wide)),
        "allreduce_quant_vs_wide_ratio": round(quant / max(1.0, wide), 4),
    }


_COLLECTIVE_PRIMS = ("psum", "all_to_all", "all_gather", "ppermute",
                     "reduce_scatter", "all_reduce", "psum_scatter",
                     "collective_permute")


def _iter_jaxprs(v):
    if hasattr(v, "eqns"):
        yield v
    elif hasattr(v, "jaxpr"):
        yield v.jaxpr
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _iter_jaxprs(item)


def collective_wire_bytes(jaxpr):
    """Sum the operand bytes entering collective eqns of a traced
    program (sub-jaxprs included — shard_map/pjit bodies are where the
    collectives live).  The honest cross-check for the analytic model:
    the lowered quantized program must put int8, not f32, on the wire.
    Returns ``{"total": bytes, "by_prim": {prim: bytes}}``."""
    by_prim = {}

    def walk(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if any(name == p or name.startswith(p + "_")
                   for p in _COLLECTIVE_PRIMS):
                b = 0
                for v in eqn.invars:
                    aval = getattr(v, "aval", None)
                    if aval is None or not hasattr(aval, "dtype"):
                        continue
                    nelem = 1
                    for d in getattr(aval, "shape", ()) or ():
                        nelem *= int(d)
                    b += nelem * jnp.dtype(aval.dtype).itemsize
                by_prim[name] = by_prim.get(name, 0) + b
            for v in eqn.params.values():
                for sub in _iter_jaxprs(v):
                    walk(sub)

    walk(getattr(jaxpr, "jaxpr", jaxpr))
    return {"total": sum(by_prim.values()), "by_prim": by_prim}
