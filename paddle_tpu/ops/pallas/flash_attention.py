"""Flash attention for TPU (Pallas), forward + custom-VJP backward.

Reference parity: the reference exposes fused attention via
paddle.incubate.nn.functional.fused_attention / flash-attn CUDA kernels
(paddle/phi/kernels/gpu/flash_attn_kernel.cu in later branches). TPU-native
design: an online-softmax kernel tiled for the MXU.  The unit of compute is
one `[block_q, block_k]` score TILE; a grid step holds one query tile and
`span` key tiles (forward, dq) or one key tile and `span` query tiles (dkv)
and walks the tiles it holds in an in-kernel loop whose bounds come from
the diagonal:

- tiles wholly above the diagonal (or wholly in the padding) are not run;
- tiles the diagonal or a ragged key edge crosses take the MASKED body;
- every other tile takes the unmasked body (no iota, compare or select).

A causal forward may also be given a WINDOW `W` (query `i` sees key `j`
iff `i - W < j <= i`): key tiles wholly before the band's lower edge are
not run and their K/V DMA is elided as the diagonal's are, and the tiles
that edge crosses take the masked body.  The backward refuses a window
(no caller trains through one).  K/V with fewer heads than Q (grouped
queries: query head `i` reads K/V head `i // (H / H_kv)`) are read in
place through the index map, never repeated to `H` heads.

`_pick_blocks` chooses that schedule from what the kernel can see
(lengths, head size, dtype; causal moves no choice) and is the only place
the choice is made; its docstring says on which shape each choice was
measured, and `schedule_counts` what a schedule runs, masks and skips.  With
`span == 1` (every caller that names its blocks: ring attention, the
tests, the sweep; and every long sequence) a grid step is one tile, K/V
stream through VMEM one `block_k` slice at a time (so 16k+ sequences never
pin the whole K/V in the ~16 MB VMEM) and the running state lives in VMEM
scratch that persists across the innermost grid dimension.  A short
sequence's K/V (or Q/dO) of a head stay resident (`span` covers the
length), and a forward step whose one tile is the whole walk carries its
state in values, with no scratch, init or finalize pass.

The tiles are KEY-major, `k q^T` `[block_k, block_q]`: what is reduced
over keys (the softmax's max and sum) runs down the sublanes as whole-vreg
work, what is per query (m, l, lse, delta) is a lane-dense row
`[1, block_q]`, dkv's two accumulations are plain products, and the
`[head_dim, block_q]` accumulators of the forward and dq are turned once,
when a query tile is done.  lse and delta travel as `[bh, tiles, 1,
block_q]`.  Only the forward at a head size of 128 and more is query-major
(`q k^T`, column statistics, lse written as `[bh, seq, 8]`: the scalar per
row replicated across 8 lanes — a `(block_q, 8)` tile is legal where the
naive `(1, block_q)` block that round 2 shipped is not).

Mosaic tiling: every block's trailing two dims are either (8,128)-aligned
or cover the full array dim.

The functions that hold the three `pallas_call` sites (`_flash_fwd`,
`_flash_bwd_impl`) are `jax.jit`ted with everything but the arrays static:
a step program of n layers then traces each kernel body and lowers it to
Mosaic once a shape, not once a call site (jax caches an inner jit's trace
by function and avals and its lowering by jaxpr within a module), and XLA
inlines the calls, so the device program is the same with and without.

Layouts: public entry `flash_attention_bshd` takes paddle's [batch, seq,
heads, head_dim]; kernels run in [batch, heads, seq, head_dim].
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Hard dependency: the kernels carry their online-softmax state in VMEM
# scratch (pltpu.VMEM), which interpret mode also supports — a JAX
# build without pallas.tpu cannot run this module at all.
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

# what the serving AOT fingerprint names the kernel by where a serving
# program runs it (serving/kv_pool.py): bump on a change to what it computes
# or how it tiles
FLASH_ATTENTION_REVISION = "fa-w1"

# the tile a caller gets who names only one of its two blocks, and ring
# attention's; tuned at 16k / d128 causal, where it stays (`_pick_blocks`)
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
_NEG_INF = -1e30
_LSE_LANES = 8  # column statistics (query-major forward, block-sparse
                # attention) replicated across this many lanes for tiling


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

_NT = (((1,), (1,)), ((), ()))      # a b^T
_NN = (((1,), (0,)), ((), ()))      # a b
_TN = (((0,), (0,)), ((), ()))      # a^T b


class Schedule(NamedTuple):
    """What one grid step covers: one `[block_q, block_k]` score tile of the
    kept operand against `span` tiles of the streamed one — and which way
    the forward's tile lies (`key_major`: `k q^T`, keys down the sublanes;
    else `q k^T`)."""
    block_q: int
    block_k: int
    span: int = 1
    key_major: bool = True


# one operand's resident tiles may hold this much of VMEM (it is double
# buffered, and K and V, or Q and dO, are two such operands)
_RESIDENT_BYTES = 512 * 1024
# the tile of a sequence whose K/V stay resident
_RESIDENT_TILE = 512


def _pick_blocks(sq, sk, block_q, block_k, *, head_dim, dtype):
    """The tile schedule for a call, from what the kernel can see; the only
    place it is chosen.  Each line is a row of `tools/sweep_flash.py` on a
    TPU v5e (ms a call; docs/performance_guide.md, "Attention block sizes",
    has the table):

    - A caller that names a block is obeyed: its tile (the other side the
      default), one tile a grid step, clamped to the 16-aligned lengths so
      a short sequence is one full-array block (always Mosaic-legal).
    - The forward's tile is key-major under a head size of 128: the softmax
      reductions then run down the sublanes and its statistics are lane-
      dense rows ([64,2048,64] causal at the same 1024 x 1024 tile: 0.89 ->
      0.77).  At 128 the `v^T p^T` product it needs streams only 128 rows
      a latched tile and query-major is faster (16k: 2.23 against 2.48), so
      it stays.  dq and dkv are key-major at every head size (16k forward +
      backward: 8.31 -> 7.85).
    - Under a head size of 128, a sequence whose K/V (and Q/dO) of a head
      fit `_RESIDENT_BYTES` keeps them resident and walks 512 x 512 tiles
      in-kernel up to the diagonal ([64,2048,64] causal, forward +
      backward: 2.64 against 2.84 at 1024 x 1024 and 3.57 before;
      [32,4096,64]: 4.50 against 4.66 and 5.60).  Several rows a grid step
      measured nothing there (forward 0.77 -> 0.79) and are not offered.
    - A sequence that one default tile covers is that ONE tile a head, its
      state never leaving values ([576,512,64] dense: 2.91 against 4.62
      before).  [128,1024,64] causal too, where the walk would run 3 tiles
      of 512 for it: the forward is 0.363 as one tile against 0.461
      walked, forward + backward 1.769 against 1.724 — the walk's backward
      wins 0.14 and its forward loses 0.10, and a training step that
      recomputes runs the forward twice, so the single tile is taken.
    - Everything else streams 1024 x 1024 tiles, the 16k / d128 optimum: a
      query-major forward pays its column statistics per tile, so smaller
      tiles cost it more than the diagonal saves ([32,2048,128] forward:
      0.78 at 512 x 512 against 0.46).
    """
    key_major = head_dim < 128
    longest = max(sq, sk)
    span = 1
    if block_q is not None or block_k is not None:
        tile_q, tile_k = block_q or DEFAULT_BLOCK_Q, block_k or DEFAULT_BLOCK_K
    elif key_major and DEFAULT_BLOCK_Q < longest and (
            longest * head_dim * jnp.dtype(dtype).itemsize <= _RESIDENT_BYTES):
        tile_q = tile_k = _RESIDENT_TILE
        span = -(-longest // _RESIDENT_TILE)
    else:
        tile_q, tile_k = DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    return Schedule(min(tile_q, _round_up(sq, 16)),
                    min(tile_k, _round_up(sk, 16)), span, key_major)


def _walks(sched, sq, sk):
    """The schedule as its two walks hold it: (forward and dq, which keep a
    query tile and stream key tiles; dkv, which keeps a key tile and
    streams query tiles), `span` clamped to the tiles each side has."""
    return (sched._replace(span=min(sched.span, -(-sk // sched.block_k))),
            sched._replace(span=min(sched.span, -(-sq // sched.block_q))))


def schedule_counts(sq, sk, head_dim, causal, dtype, block_q=None,
                    block_k=None):
    """What the schedule of a call does, a batch*heads row: the blocks, the
    grids' two inner axes, and the score tiles run / masked / skipped (the
    masked are among the run; forward and dq walk them query tile by query
    tile, dkv key tile by key tile).  Static: a schedule never looks at
    data, so this is how often each of its branches engages."""
    s = _pick_blocks(sq, sk, block_q, block_k, head_dim=head_dim,
                     dtype=dtype)
    nqt, nkt = -(-sq // s.block_q), -(-sk // s.block_k)
    run = masked = 0
    for i in range(nqt):
        free, end = _key_tiles(i * s.block_q, 0, s._replace(span=nkt),
                               causal, sk)
        run += end
        masked += end - free
    keys, queries = _walks(s, sq, sk)
    return {
        "schedule": s,
        "grid_fwd_dq": (nqt, -(-nkt // keys.span)),
        "grid_dkv": (nkt, -(-nqt // queries.span)),
        "tiles_run": run, "tiles_masked": masked,
        "tiles_skipped": nqt * nkt - run,
        "scores_run": run * s.block_q * s.block_k,
        "scores_masked": masked * s.block_q * s.block_k,
    }


def _clip(x, hi):
    if isinstance(x, int):
        return max(0, min(x, hi))
    return jnp.clip(x, 0, hi)


def _least(a, b):
    if isinstance(a, int) and isinstance(b, int):
        return min(a, b)
    return jnp.minimum(a, b)


def _most(a, b):
    if isinstance(a, int) and isinstance(b, int):
        return max(a, b)
    return jnp.maximum(a, b)


def _key_tiles(row0, first, sched, causal, seq_k):
    """For the query tile that starts at `row0`, of the `span` key tiles a
    grid step holds from tile `first` on: local tiles [0, free) need no
    mask, [free, run) are crossed by the diagonal or the ragged key edge,
    the rest lie wholly above the diagonal or in the padding."""
    bq, bk, span, _ = sched
    run, free = -(-seq_k // bk), seq_k // bk
    if causal:
        run = _least(run, (row0 + bq + bk - 1) // bk)
        free = _least(free, (row0 + 1) // bk)
    return _clip(free - first, span), _clip(run - first, span)


def _window_tiles(row0, first, sched, window):
    """For the query tile that starts at `row0` under a window of `window`
    keys, of the `span` key tiles from tile `first` on: local tiles before
    `lo` lie wholly before the band's lower edge (not run), [lo, clear) are
    crossed by it (masked), from `clear` on it masks nothing."""
    bq, bk, span, _ = sched
    lo = (row0 - window + 1) // bk
    clear = (row0 + bq - window + bk - 1) // bk
    return _clip(lo - first, span), _clip(clear - first, span)


def _ranges(free, run, edge):
    """The `_walk` ranges of a query tile's key tiles: [0, free) unmasked,
    [free, run) masked — and with a window's `edge = (lo, clear)`, tiles
    before `lo` not run and [lo, clear) masked too."""
    if edge is None:
        return (0, free, False), (free, run, True)
    lo, clear = edge
    low = _least(clear, run)
    mid = _most(low, free)
    return (lo, low, True), (low, mid, False), (mid, run, True)


def _query_tiles(col0, first, sched, causal, seq_q):
    """For the key tile that starts at `col0`, of the `span` query tiles a
    grid step holds from tile `first` on: local tiles [lo, free) are
    crossed by the diagonal, [free, end) lie wholly under it (all of them
    when not causal), tiles before `lo` wholly above it and tiles from
    `end` on wholly in the padding."""
    bq, bk, span, _ = sched
    end = -(-seq_q // bq)
    lo = free = 0
    if causal:
        lo = col0 // bq
        free = (col0 + bk + bq - 2) // bq
    return (_clip(lo - first, span), _clip(_least(free, end) - first, span),
            _clip(end - first, span))


def _axis(i, n):
    """Grid position on axis `i`, static where the axis has one step (so
    that a one-step schedule traces no branch on it)."""
    return 0 if n == 1 else pl.program_id(i)


def _each_tile(lo, hi, span, body):
    """body(j) for the local tiles lo <= j < hi; bounds static or traced."""
    if isinstance(lo, int) and isinstance(hi, int):
        if hi - lo == 1:
            body(lo)
        elif hi > lo:
            jax.lax.fori_loop(lo, hi, lambda j, c: (body(j), c)[1], 0)
    elif span == 1:
        pl.when(hi > lo)(lambda: body(0))
    else:
        jax.lax.fori_loop(lo, hi, lambda j, c: (body(j), c)[1], 0)


def _tile_slice(j, size):
    if isinstance(j, int):
        return pl.ds(j * size, size)
    return pl.ds(pl.multiple_of(j * size, size), size)


def _keep(shape, key_axis, row0, col0, causal, seq_k, window=None):
    """bool `shape`: the scores that a tile whose first query is `row0` and
    first key `col0` keeps — a key at or before its query (causal), less
    than `window` keys before it (a window), and before the ragged edge
    `seq_k` (None: the length is block-aligned, the compare is not
    traced).  None where there is nothing to mask."""
    key = jax.lax.broadcasted_iota(jnp.int32, shape, key_axis)
    keep = None
    if causal:
        query = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - key_axis)
        keep = key - query <= row0 - col0
    if window is not None:
        keep = jnp.logical_and(keep, key - query > row0 - col0 - window)
    if seq_k is not None:
        edge = key < seq_k - col0
        keep = edge if keep is None else jnp.logical_and(keep, edge)
    return keep


def _walk(pos, steps, span, init, step, done, *ranges):
    """One grid step of a kernel's walk: `init` on the first step of the
    streamed axis (`pos` of `steps`), `step(tile, masked)` over each
    `(lo, hi, masked)` range of the local tiles, `done` on the last.  With
    one step on the axis the state is the kept tile's alone and nothing
    branches."""
    init() if steps == 1 else pl.when(pos == 0)(init)
    for lo, hi, masked in ranges:
        _each_tile(lo, hi, span, lambda j, masked=masked: step(j, masked))
    done() if steps == 1 else pl.when(pos == steps - 1)(done)


def _scores(q, k, scale, keep):
    """One tile's scores in the log2 domain: scale*log2(e) folded into the
    multiply so the per-element exp is a bare exp2.  Dots run in the input
    dtype (bf16 hits the MXU at full rate) with fp32 accumulation."""
    s = (scale * _LOG2E) * jax.lax.dot_general(
        q, k, _NT, preferred_element_type=jnp.float32)
    return s if keep is None else jnp.where(keep, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, scale, causal,
                sched, seq_k, grid, window=None):
    """Key-major, the tile is `k q^T` `[bk, bq]`: the softmax's max and sum
    run down the sublanes (whole-vreg work), m, l and lse are lane-dense
    rows `[1, bq]`, and the accumulator is `v^T p^T` `[d, bq]`, turned
    once at the end of a query tile.  Query-major it is `q k^T` `[bq, bk]`
    with column statistics and `p v` `[bq, d]`."""
    bq, bk, span, key_major = sched
    _, nq, nk = grid
    qi, ki = _axis(1, nq), _axis(2, nk)
    row0 = qi * bq
    free, run = _key_tiles(row0, ki * span, sched, causal, seq_k)
    band = (None if window is None
            else _window_tiles(row0, ki * span, sched, window))
    edge = seq_k if seq_k % bk else None      # the ragged key edge, if any
    key_axis = 0 if key_major else 1

    def tile(j, masked, m_prev, l_prev, acc_prev):
        keys = _tile_slice(j, bk)
        q, k, v = q_ref[0], k_ref[0, keys, :], v_ref[0, keys, :]
        keep = _keep((bk, bq) if key_major else (bq, bk), key_axis, row0,
                     (ki * span + j) * bk, causal, edge,
                     window) if masked else None
        s = (_scores(k, q, scale, keep) if key_major
             else _scores(q, k, scale, keep))                 # f32
        # softmax statistics stay fp32, in the log2 domain
        m_new = jnp.max(s, axis=key_axis, keepdims=True)
        if m_prev is not None:
            m_new = jnp.maximum(m_prev, m_new)
        p = jnp.exp2(s - m_new)
        l_new = jnp.sum(p, axis=key_axis, keepdims=True)
        p = p.astype(v.dtype)
        acc = jax.lax.dot_general(*((v, p, _TN) if key_major
                                    else (p, v, _NN)),
                                  preferred_element_type=jnp.float32)
        if m_prev is not None:
            corr = jnp.exp2(m_prev - m_new)
            l_new = l_prev * corr + l_new
            acc = acc_prev * corr + acc
        return m_new, l_new, acc

    def finalize(m, l, acc):
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o = acc / l_safe
        o_ref[0] = (o.T if key_major else o).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m * _LN2 + jnp.log(l_safe),
                                      lse_ref.shape[1:])

    if not scratch:
        # one tile is the whole row of tiles and which body it takes is
        # known at trace time: the state never leaves values
        finalize(*tile(0, run > free or band is not None, None, None, None))
        return
    acc_ref, m_ref, l_ref = scratch

    def stat(ref):
        # query-major statistics are columns kept a full lane tile wide
        return ref[...] if key_major else ref[:, 0:1]

    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(j, masked):
        m, l, acc_ref[...] = tile(j, masked, stat(m_ref), stat(l_ref),
                                  acc_ref[...])
        m_ref[...] = jnp.broadcast_to(m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l, l_ref.shape)

    def done():
        finalize(stat(m_ref), stat(l_ref), acc_ref[...])

    _walk(ki, nk, span, init, step, done, *_ranges(free, run, band))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, sched, seq_k, grid):
    """Key-major: lse and delta are rows, the accumulator is `k^T ds^T`
    `[d, bq]`, turned once at the end of a query tile."""
    bq, bk, span, _ = sched
    _, nq, nk = grid
    qi, ki = _axis(1, nq), _axis(2, nk)
    row0 = qi * bq
    free, run = _key_tiles(row0, ki * span, sched, causal, seq_k)
    edge = seq_k if seq_k % bk else None      # the ragged key edge, if any

    def init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(j, masked):
        keys = _tile_slice(j, bk)
        k, v = k_ref[0, keys, :], v_ref[0, keys, :]
        # the edge too: a padded key row is zero, but its p need not be
        # finite
        keep = _keep((bk, bq), 0, row0, (ki * span + j) * bk, causal,
                     edge) if masked else None
        st = _scores(k, q_ref[0], scale, keep)                # [bk, bq]
        pt = jnp.exp2(st - lse_ref[0, 0] * _LOG2E)
        dpt = jax.lax.dot_general(v, do_ref[0], _NT,
                                  preferred_element_type=jnp.float32)
        # ds without its factor `scale`: applied once, to the sum
        dst = pt * (dpt - delta_ref[0, 0])
        dq_acc[...] += jax.lax.dot_general(
            k, dst.astype(k.dtype), _TN,
            preferred_element_type=jnp.float32)               # [d, bq]

    def done():
        dq_ref[0] = (dq_acc[...] * scale).T.astype(dq_ref.dtype)

    _walk(ki, nk, span, init, step, done, (0, free, False), (free, run, True))


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    sched, seq_q, grid):
    """Key-major: the tile is `k q^T` `[bk, bq]`, lse and delta are rows,
    and both accumulations are plain products.  No ragged-edge mask: a
    padded query row is zero in q, dO, lse and delta, so it adds nothing,
    and a padded key row only reaches its own dk / dv row, which the
    caller slices away."""
    bq, bk, span, _ = sched
    _, nk, nq = grid
    ki, qi = _axis(1, nk), _axis(2, nq)
    col0 = ki * bk
    lo, free, end = _query_tiles(col0, qi * span, sched, causal, seq_q)

    def init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(i, masked):
        rows = _tile_slice(i, bq)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        k, v = k_ref[0], v_ref[0]
        keep = _keep((bk, bq), 0, (qi * span + i) * bq, col0, causal,
                     None) if masked else None
        st = _scores(k, q, scale, keep)                       # [bk, bq]
        pt = jnp.exp2(st - lse_ref[0, i] * _LOG2E)
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN,
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0, i])
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN,
            preferred_element_type=jnp.float32)

    def done():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    _walk(qi, nq, span, init, step, done, (lo, free, True), (free, end, False))


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _schedule_of(q, k, block_q, block_k):
    sq, sk = q.shape[2], k.shape[2]
    sched = _pick_blocks(sq, sk, block_q, block_k, head_dim=q.shape[3],
                         dtype=q.dtype)
    return _walks(sched, sq, sk)


def _flash_bhsd(q, k, v, causal, scale, block_q, block_k, interpret,
                window=None):
    return _flash_block(q, k, v, causal, scale, block_q, block_k,
                        interpret, window)[0]


# a function that holds `pallas_call` sites is traced and lowered once for
# each (shapes, everything but the arrays), not once a call site
_once_a_shape = functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "window"))
def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               window=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    groups = h // k.shape[1]        # query heads a K/V head serves
    sched, _ = _schedule_of(q, k, block_q, block_k)
    bq, bk, span, key_major = sched
    bh = b * h
    qp = _pad_to(q, bq, 2).reshape(bh, -1, d)
    kp = _pad_to(k, span * bk, 2).reshape(bh // groups, -1, d)
    vp = _pad_to(v, span * bk, 2).reshape(bh // groups, -1, d)
    sqp, skp = qp.shape[1], kp.shape[1]

    grid = (bh, sqp // bq, skp // (span * bk))
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, sched=sched, seq_k=sk,
        grid=grid, window=window)
    # causal: clamp the k index map to the diagonal so the skipped
    # above-diagonal steps re-map to an already-resident block and Pallas
    # elides their K/V DMA entirely (a step that runs no tile skips
    # compute, not the prefetch); a window clamps it from below to the
    # band's first block the same way
    # grouped queries: a (batch, query head) row reads its K/V head's row
    def kv_row(g):
        return g if groups == 1 else g // groups

    if window is not None:
        def kv_index(g, qi, ki):
            lo = jnp.maximum(qi * bq - window + 1, 0) // (span * bk)
            hi = (qi * bq + bq - 1) // (span * bk)
            return (kv_row(g), jnp.clip(ki, lo, hi), 0)
    elif causal:
        def kv_index(g, qi, ki):
            return (kv_row(g),
                    jnp.minimum(ki, (qi * bq + bq - 1) // (span * bk)), 0)
    else:
        def kv_index(g, qi, ki):
            return (kv_row(g), ki, 0)
    if key_major:
        # lse as rows, a query tile each
        lse_shape, lse_block = (bh, sqp // bq, 1, bq), (1, 1, 1, bq)
        lse_index = lambda g, qi, ki: (g, qi, 0, 0)       # noqa: E731
        state = [_scratch((d, bq)), _scratch((1, bq)), _scratch((1, bq))]
    else:
        # lse as columns, replicated across 8 lanes: a `(block_q, 8)` tile
        # is legal where the naive `(1, block_q)` block is not
        lse_shape, lse_block = (bh, sqp, _LSE_LANES), (1, bq, _LSE_LANES)
        lse_index = lambda g, qi, ki: (g, qi, 0)          # noqa: E731
        state = [_scratch((bq, d)), _scratch((bq, 128)), _scratch((bq, 128))]
    # one tile is a query tile's whole walk, and its body known at trace
    # time: no state to keep
    lone = grid[2] * span == 1 and (not causal or grid[1] == 1)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            _vmem_spec((1, bq, d), lambda g, qi, ki: (g, qi, 0)),
            _vmem_spec((1, span * bk, d), kv_index),
            _vmem_spec((1, span * bk, d), kv_index),
        ],
        out_specs=[
            _vmem_spec((1, bq, d), lambda g, qi, ki: (g, qi, 0)),
            _vmem_spec(lse_block, lse_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sqp, d), q.dtype),
            jax.ShapeDtypeStruct(lse_shape, jnp.float32),
        ],
        scratch_shapes=[] if lone else state,
        compiler_params=_compiler_params(("parallel", "parallel",
                                          "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    o = o.reshape(b, h, sqp, d)[:, :, :sq, :]
    lse = lse if key_major else lse[:, :, 0]
    return o, lse.reshape(b, h, sqp)[:, :, :sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_block(q, k, v, causal, scale, block_q, block_k, interpret,
                 window=None):
    """Flash attention returning (o, lse); differentiable in both (ring
    attention merges blocks by their lse) — without a window and with as
    many K/V heads as query heads."""
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                      window=window)


def _flash_block_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                     window=None):
    # through _flash_block, not the raw _flash_fwd: under nested
    # differentiation (recompute's backward takes a vjp of a region whose
    # ops each took their own) the outer trace then meets a custom_vjp
    # call it can linearize, never a raw pallas_call it would have to JVP
    o, lse = _flash_block(q, k, v, causal, scale, block_q, block_k,
                          interpret, window)
    return (o, lse), (q, k, v, o, lse)


def _flash_block_bwd(causal, scale, block_q, block_k, interpret, window, res,
                     cts):
    q, k, v, o, lse = res
    if window is not None or k.shape[1] != q.shape[1]:
        raise NotImplementedError(
            "flash attention backward with a window or grouped K/V heads: "
            "no caller trains through either (docs/serving.md)")
    do, dlse = cts
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                    # [b,h,sq]
    # rows that never saw a key (lse == _NEG_INF sentinel, which is a
    # finite -1e30) have p == 0 everywhere — drop their lse cotangent
    delta = delta - jnp.where(lse > _NEG_INF / 2,
                              dlse.astype(jnp.float32), 0.0)
    return _flash_bwd_impl(q, k, v, do, lse, delta, causal, scale,
                           block_q, block_k, interpret)


@_once_a_shape
def _flash_bwd_impl(q, k, v, do, lse, delta, causal, scale, block_q, block_k,
                    interpret):
    """dq/dk/dv given precomputed delta (= sum(do*o) for the plain kernel;
    ring attention folds the lse cotangent in as delta - dlse)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    dq_sched, dkv_sched = _schedule_of(q, k, block_q, block_k)
    bq, bk, span_k, _ = dq_sched
    span_q = dkv_sched.span
    bh = b * h

    # each side is padded to whole grid steps of the walk that streams it
    def rows(x, block):
        return _pad_to(x, block, 2).reshape(bh, -1, *x.shape[3:])

    qp, dop = rows(q, span_q * bq), rows(do, span_q * bq)
    kp, vp = rows(k, span_k * bk), rows(v, span_k * bk)
    sqp, skp = qp.shape[1], kp.shape[1]
    # lse and delta as rows, a query tile each: [bh, tiles, 1, bq]
    lsep = rows(lse, span_q * bq).reshape(bh, -1, 1, bq)
    deltap = rows(delta, span_q * bq).reshape(bh, -1, 1, bq)

    # causal DMA elision (see _flash_fwd): skipped blocks re-map to a
    # resident block index so their copies are elided
    if causal:
        def kv_index(g, qi, ki):
            return (g, jnp.minimum(ki,
                                   (qi * bq + bq - 1) // (span_k * bk)), 0)

        def q_block(ki, qi):
            return jnp.maximum(qi, (ki * bk) // (span_q * bq))
    else:
        def kv_index(g, qi, ki):
            return (g, ki, 0)

        def q_block(ki, qi):
            return qi

    def q_index(g, ki, qi):
        return (g, q_block(ki, qi), 0)

    def q_row_index(g, ki, qi):
        return (g, q_block(ki, qi), 0, 0)

    dq_grid = (bh, sqp // bq, skp // (span_k * bk))
    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, sched=dq_sched, seq_k=sk,
        grid=dq_grid)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_dq",
        grid=dq_grid,
        in_specs=[
            _vmem_spec((1, bq, d), lambda g, qi, ki: (g, qi, 0)),
            _vmem_spec((1, span_k * bk, d), kv_index),
            _vmem_spec((1, span_k * bk, d), kv_index),
            _vmem_spec((1, bq, d), lambda g, qi, ki: (g, qi, 0)),
            _vmem_spec((1, 1, 1, bq), lambda g, qi, ki: (g, qi, 0, 0)),
            _vmem_spec((1, 1, 1, bq), lambda g, qi, ki: (g, qi, 0, 0)),
        ],
        out_specs=_vmem_spec((1, bq, d), lambda g, qi, ki: (g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, d), q.dtype),
        scratch_shapes=[_scratch((d, bq))],
        compiler_params=_compiler_params(("parallel", "parallel",
                                          "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)

    dkv_grid = (bh, skp // bk, sqp // (span_q * bq))
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, sched=dkv_sched,
        seq_q=sq, grid=dkv_grid)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_dkv",
        grid=dkv_grid,
        in_specs=[
            _vmem_spec((1, span_q * bq, d), q_index),
            _vmem_spec((1, bk, d), lambda g, ki, qi: (g, ki, 0)),
            _vmem_spec((1, bk, d), lambda g, ki, qi: (g, ki, 0)),
            _vmem_spec((1, span_q * bq, d), q_index),
            _vmem_spec((1, span_q, 1, bq), q_row_index),
            _vmem_spec((1, span_q, 1, bq), q_row_index),
        ],
        out_specs=[
            _vmem_spec((1, bk, d), lambda g, ki, qi: (g, ki, 0)),
            _vmem_spec((1, bk, d), lambda g, ki, qi: (g, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skp, d), k.dtype),
            jax.ShapeDtypeStruct((bh, skp, d), v.dtype),
        ],
        scratch_shapes=[_scratch((bk, d)), _scratch((bk, d))],
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
                         block_k=None, interpret=None, window=None):
    """Flash attention on [batch, seq, heads, head_dim] inputs (paddle
    layout). Differentiable (custom VJP) without a window and with as many
    K/V heads as query heads.  `window` (causal only): query `i` sees keys
    `i - window < j <= i`.  K/V may have fewer heads than Q, a divisor of
    them (grouped queries).  Raises on CPU unless `interpret=True` —
    callers pick the XLA sdpa path there."""
    if window is not None and not causal:
        raise ValueError("a window is causal: pass causal=True")
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
    o = _flash_bhsd(qt, kt, vt, bool(causal), scale, block_q, block_k,
                    bool(interpret), None if window is None else int(window))
    return jnp.transpose(o, (0, 2, 1, 3))


def flash_attention_bhsd(q, k, v, causal=False, scale=None, **kw):
    """Same kernel on [batch, heads, seq, head_dim] inputs."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    kw.setdefault("interpret", not _on_tpu())
    return _flash_bhsd(q, k, v, bool(causal), float(scale),
                       kw.get("block_q"), kw.get("block_k"),
                       bool(kw["interpret"]))
