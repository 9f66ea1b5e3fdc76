"""One contract over the serving cache's kinds (serving/kv_pool.py): what a
pool writes in prefill and in decode it reads back as dense attention over
the tokens written, at the kind's tolerance, and its pages survive the
hand-off — export, the fleet's wire format, import into fresh pools on
other page ids — with the next decode read bit-identical."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import serving
from paddle_tpu.incubate.nn.paged_attention import PageAllocator
from paddle_tpu.ops.pallas.paged_attention import PAGED_DECODE_REVISION
from paddle_tpu.quantization.kv_cache import resolve_kv_cache_dtype
from paddle_tpu.serving.fleet import wire
from paddle_tpu.serving.kv_pool import LatentPool, PlainKV, QuantizedKV

LAYERS, HEADS, DIM, PAGE = 2, 4, 32, 8      # a row of 128: kernel-sized
ROW, RANK = 40, 32                          # latent: [c 32 | k_r 8]
SLOTS, TABLE, PAGES = 3, 3, 12
PROMPTS = (11, 5, 0)                        # slot 2 stays empty

# kind -> (attention_path, decode_kernel, tolerance of the decode read
# against the dense float32 reference, relative to its largest value)
KINDS = {
    "head_major": ("xla", False, 1e-5),
    "row_pages": (f"paged_decode/{PAGED_DECODE_REVISION}", True, 2e-5),
    # docs/quantization.md: int8 pools track f32 pools within 8%
    "int8": ("xla", False, 0.08),
    "latent": ("latent/xla", False, 1e-5),
}


def _pool(kind):
    cfg = serving.EngineConfig(
        max_num_seqs=SLOTS, page_size=PAGE, max_model_len=TABLE * PAGE,
        num_pages=PAGES, kv_cache_dtype="int8" if kind == "int8" else None)
    if kind == "latent":
        return LatentPool(cfg, {"kind": "latent", "row_width": ROW,
                                "value_width": RANK, "num_layers": LAYERS})
    if kind == "int8":
        return QuantizedKV(cfg, LAYERS, HEADS, DIM, None,
                           resolve_kv_cache_dtype("int8"))
    # row pages as on a TPU; off one the kernel runs in interpret mode
    return PlainKV(cfg, LAYERS, HEADS, DIM, rows=kind == "row_pages")


def _softmax(s):
    e = np.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


class _KV:
    """Drives a K/V kind; the reference is attention over the K and V
    written so far."""

    def draw(self, rng, s):
        return [rng.standard_normal((SLOTS, s, HEADS, DIM)).astype(
            np.float32) for _ in "qkv"]

    def prefill(self, pool, pools, li, ins, tables, lens):
        _, pools[0][li], pools[1][li] = pool.prefill(
            *ins, pools[0][li], pools[1][li], tables, lens)
        return ins[1:]

    def decode(self, pool, pools, li, ins, tables, lens):
        out, pools[0][li], pools[1][li] = pool.decode(
            *ins, pools[0][li], pools[1][li], tables, lens)
        return out, ins[1:]

    def reference(self, q, hist):
        k, v = hist                                     # [t, h, d]
        p = _softmax(np.einsum("hd,thd->ht", q[0], k) / np.sqrt(DIM))
        return np.einsum("ht,thd->hd", p, v)[None]


class _Latent:
    """Drives the latent kind: absorbed attention over the rows written
    so far, scores over the whole row, values its first RANK columns."""

    scale = 1.0 / np.sqrt(ROW)

    def draw(self, rng, s):
        mk = lambda *shape: rng.standard_normal(shape).astype(np.float32)
        if s > 1:       # prefill: expanded q, k, v and the rows cached
            return [mk(SLOTS, s, HEADS, 24), mk(SLOTS, s, HEADS, 24),
                    mk(SLOTS, s, HEADS, 16), mk(SLOTS, s, ROW)]
        return [mk(SLOTS, 1, HEADS, ROW), mk(SLOTS, 1, ROW)]

    def prefill(self, pool, pools, li, ins, tables, lens):
        _, pools[0][li] = pool.prefill(*ins, pools[0][li], tables, lens)
        return ins[3:]

    def decode(self, pool, pools, li, ins, tables, lens):
        out, pools[0][li] = pool.decode(*ins, RANK, self.scale,
                                        pools[0][li], tables, lens)
        return out, ins[1:]

    def reference(self, q, hist):
        (rows,) = hist                                  # [t, w]
        p = _softmax(np.einsum("hw,tw->ht", q[0], rows) * self.scale)
        return np.einsum("ht,tr->hr", p, rows[:, :RANK])[None]


class _Cache:
    """The host's half, as LLMEngine keeps it: allocator, tables, lens."""

    def __init__(self, order=range(SLOTS)):
        self.alloc = PageAllocator(PAGES, SLOTS, TABLE)
        self.tables = np.zeros((SLOTS, TABLE), np.int32)
        self.lens = np.zeros((SLOTS,), np.int32)
        self.order = list(order)

    def grow(self, new_lens):
        for b in self.order:
            for pos, page in self.alloc.allocate(
                    b, self.alloc.pages_needed(new_lens[b], PAGE)):
                self.tables[b, pos] = page


@pytest.mark.parametrize("kind", list(KINDS))
def test_pool_kind_contract(kind):
    path, kernel, tol = KINDS[kind]
    pool = _pool(kind)
    drive = _Latent() if kind == "latent" else _KV()
    assert (pool.attention_path, pool.decode_kernel) == (path, kernel)
    pools = tuple(list(half) for half in pool.allocate())
    assert pool.nbytes == sum(int(x.nbytes)
                              for x in jax.tree_util.tree_leaves(pools))
    assert len(pools[0]) == LAYERS

    rng = np.random.default_rng(0)
    host = _Cache()
    live = [b for b in range(SLOTS) if PROMPTS[b]]
    hist = [[None] * SLOTS for _ in range(LAYERS)]

    def remember(li, written, upto=None):
        for b in live:
            new = [np.asarray(w)[b, :upto[b] if upto else None]
                   for w in written]
            hist[li][b] = new if hist[li][b] is None else [
                np.concatenate(pair) for pair in zip(hist[li][b], new)]

    host.grow(PROMPTS)
    for li in range(LAYERS):
        ins = drive.draw(rng, max(PROMPTS))
        remember(li, drive.prefill(
            pool, pools, li, [jnp.asarray(x) for x in ins],
            jnp.asarray(host.tables), jnp.asarray(PROMPTS, jnp.int32)),
            upto=PROMPTS)
    host.lens[:] = PROMPTS

    def decode_step(host, pools, ins_by_layer, check):
        host.grow([n + 1 if b in live else 0
                   for b, n in enumerate(host.lens)])
        outs = []
        for li, ins in enumerate(ins_by_layer):
            out, written = drive.decode(
                pool, pools, li, [jnp.asarray(x) for x in ins],
                jnp.asarray(host.tables), jnp.asarray(host.lens))
            outs.append(np.asarray(out))
            if check:
                remember(li, written)
                for b in live:
                    want = drive.reference(ins[0][b], hist[li][b])
                    err = np.abs(outs[-1][b] - want).max()
                    assert err <= tol * np.abs(want).max(), (li, b, err)
        for b in live:
            host.lens[b] += 1
        return outs

    for _ in range(3):
        decode_step(host, pools, [drive.draw(rng, 1)
                                  for _ in range(LAYERS)], check=True)

    # the hand-off: every live slot's pages, through the wire format,
    # into fresh pools whose allocator hands out OTHER page ids
    there = _Cache(order=reversed(range(SLOTS)))
    there.grow(host.lens)
    there.lens[:] = host.lens
    assert (there.tables[live] != host.tables[live]).any()
    moved = pool.allocate()
    for b in live:
        state = wire.unpack_state(wire.pack_state({
            "geometry": dict(pool.geometry),
            "layers": pool.export(pools, host.alloc.owned_pages(b))}))
        assert state["geometry"] == pool.geometry
        if kind in ("head_major", "row_pages"):
            # head-major on the wire, whatever the local layout
            assert state["layers"][0]["k"].shape == (
                len(host.alloc.owned_pages(b)), HEADS, PAGE, DIM)
        moved = pool.import_(moved, np.asarray(there.alloc.owned_pages(b)),
                             state["layers"])
    moved = tuple(list(half) for half in moved)
    nxt = [drive.draw(rng, 1) for _ in range(LAYERS)]
    here_out = decode_step(host, pools, nxt, check=True)
    there_out = decode_step(there, moved, nxt, check=False)
    for a, b in zip(here_out, there_out):
        np.testing.assert_array_equal(a[live], b[live])


# ------------------------------------------------------------ window rings
WIN, W_HEADS, W_QUERY = 8, 2, 4          # a ring of 8 rows, 2 query heads
#                                          a K/V head


def _window_pool():
    from paddle_tpu.serving.kv_pool import LayeredPool
    cfg = serving.EngineConfig(max_num_seqs=SLOTS, page_size=PAGE,
                               max_model_len=64)
    return LayeredPool(cfg, [{"kind": "window", "num_heads": W_HEADS,
                              "head_dim": DIM, "query_heads": W_QUERY,
                              "window": WIN}])


def _windowed(q, k, v):
    """Dense attention of a query at the last of ``k``/``v``'s positions
    [t, H_kv, d] over the window's last WIN of them."""
    k, v = k[-WIN:], v[-WIN:]
    g = W_QUERY // W_HEADS
    qg = q.reshape(W_HEADS, g, DIM)
    p = _softmax(np.einsum("hgd,thd->hgt", qg, k) / np.sqrt(DIM))
    return np.einsum("hgt,thd->hgd", p, v).reshape(W_QUERY, DIM)


class _Ring:
    """Drives one window layer slot by slot as the engine does; each slot's
    history is what its CURRENT request wrote."""

    def __init__(self):
        self.pool = _window_pool()
        self.rings = list(self.pool.allocate())
        self.rng = np.random.default_rng(7)
        self.hist = {}
        self.lens = np.zeros((SLOTS,), np.int32)

    def draw(self, b, s, heads):
        return self.rng.standard_normal((b, s, heads, DIM)).astype(
            np.float32)

    def prefill(self, slot, n, bucket=32):
        q, k, v = (self.draw(1, bucket, h)
                   for h in (W_QUERY, W_HEADS, W_HEADS))
        step = self.pool.layer_step(0, "prefill",
                                    jnp.asarray([slot], jnp.int32))
        out, self.rings[0][0], self.rings[1][0] = step(
            *(jnp.asarray(x) for x in (q, k, v)), self.rings[0][0],
            self.rings[1][0], None, jnp.asarray([n], jnp.int32))
        self.hist[slot] = (k[0, :n], v[0, :n])
        self.lens[slot] = n
        for i in range(n):                 # the prompt's own window
            want = _windowed(q[0, i], k[0, :i + 1], v[0, :i + 1])
            np.testing.assert_allclose(np.asarray(out)[0, i], want,
                                       rtol=1e-5, atol=1e-5)

    def decode(self, check=True):
        """One pass over every slot at the stored lengths; unchecked, the
        lengths do not advance (a pass launched and discarded)."""
        q, k, v = (self.draw(SLOTS, 1, h)
                   for h in (W_QUERY, W_HEADS, W_HEADS))
        out, self.rings[0][0], self.rings[1][0] = self.pool.layer_step(
            0, "decode")(*(jnp.asarray(x) for x in (q, k, v)),
                         self.rings[0][0], self.rings[1][0], None,
                         jnp.asarray(self.lens))
        if check:
            for b, (hk, hv) in self.hist.items():
                hk, hv = (np.concatenate([h, x[b]]) for h, x in
                          ((hk, k), (hv, v)))
                self.hist[b] = (hk, hv)
                np.testing.assert_allclose(
                    np.asarray(out)[b, 0], _windowed(q[b, 0], hk, hv),
                    rtol=1e-5, atol=1e-5)
                self.lens[b] += 1


@pytest.mark.parametrize("case", ["longer_than_window", "slot_reused",
                                  "discarded_pass"])
def test_window_ring_reads_the_window(case):
    """A window layer's ring against dense attention over the last WIN
    positions a request wrote: a prompt longer than the window, decode
    across the ring's wrap, a slot reused by a shorter request (the rows
    its earlier request left are never read), and a run-ahead pass that
    is launched and discarded (it overwrites only the row of a position
    that no later query's window holds)."""
    ring = _Ring()
    assert ring.pool.window_layers == 1 and ring.pool.state_layers == 0
    ring.prefill(0, 19)                    # 19 > WIN: the last 8 kept
    ring.prefill(1, 3)
    for _ in range(12):                    # slot 1 wraps, slot 0 wraps on
        ring.decode()
    if case == "slot_reused":
        ring.prefill(0, 2)                 # slot 0's new, shorter request
        for _ in range(10):
            ring.decode()
    if case == "discarded_pass":
        # the pass run ahead of the last one (whose queries have read
        # their windows) is discarded; the next pass computes its
        # positions again
        ring.decode(check=False)
        for _ in range(10):
            ring.decode()


def test_window_ring_geometry_and_refusals():
    """Bytes, the fingerprint's term, the prefill's attributes — and a
    hand-off of a ring, which is not built, refused by name."""
    pool = _window_pool()
    ring = pool.window.struct
    assert ring.shape == (SLOTS, WIN, W_HEADS, DIM)
    assert pool.nbytes == pool.window_nbytes == 2 * SLOTS * WIN * W_HEADS \
        * DIM * 4
    assert pool.attention_path == f"window/{WIN}:xla/ring+kinds:w"
    assert pool.geometry["window"] == WIN
    assert pool.prefill_attrs(19, 32) == {"window": WIN, "window_layers": 1,
                                          "window_rows": WIN}
    assert pool.live_rows([3, 19]) == 4 + WIN
    pools = pool.allocate()
    with pytest.raises(ValueError, match="'window' layer"):
        pool.export(pools, [1], slot=0)
    with pytest.raises(ValueError, match="'window' layer"):
        pool.import_(pools, np.asarray([1]), [{}], slot=0)
