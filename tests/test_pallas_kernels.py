"""Pallas kernels vs XLA references (interpret mode on the CPU test mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional.transformer import _sdpa_ref
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
from paddle_tpu.ops.pallas.norm import fused_layer_norm, fused_rms_norm


def _qkv(b, s, h, d, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv(2, 256, 4, 64)
        out = flash_attention_bshd(q, k, v, causal=causal, interpret=True)
        ref = _sdpa_ref(q, k, v, None, 0.0, causal, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_unaligned_seq_and_head_dim(self):
        q, k, v = _qkv(1, 200, 2, 80)
        out = flash_attention_bshd(q, k, v, causal=True, interpret=True)
        ref = _sdpa_ref(q, k, v, None, 0.0, True, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match(self, causal):
        q, k, v = _qkv(1, 128, 2, 64)

        def f(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        ours = jax.grad(f(lambda q, k, v: flash_attention_bshd(
            q, k, v, causal=causal, interpret=True)), argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(f(lambda q, k, v: _sdpa_ref(
            q, k, v, None, 0.0, causal, None)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(ours, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_bf16(self):
        q, k, v = [t.astype(jnp.bfloat16) for t in _qkv(1, 128, 2, 64)]
        out = flash_attention_bshd(q, k, v, causal=True, interpret=True)
        ref = _sdpa_ref(q, k, v, None, 0.0, True, None)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=0.05, atol=0.05)


def _bhsd(b, h, sq, sk, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda s: jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    return mk(sq), mk(sk), mk(sk)


def _ref_bhsd(q, k, v, causal):
    """Plain attention on [b, h, s, d] in f32: (o, lse)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * q.shape[-1] ** -0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                   v.astype(jnp.float32))
    return o, jax.nn.logsumexp(s, axis=-1)


def _flash_and_grads(q, k, v, causal, blocks=(None, None)):
    """(o, lse) and the gradients of a loss that reads both."""
    def run(fn):
        def loss(q, k, v):
            o, lse = fn(q, k, v)
            return (o.astype(jnp.float32) ** 2).sum() + lse.sum()
        return fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = run(lambda q, k, v: fa._flash_block(
        q, k, v, causal, q.shape[-1] ** -0.5, *blocks, True))
    want = run(lambda q, k, v: _ref_bhsd(q, k, v, causal))
    return got, want


def _assert_close(got, want, tol):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * (np.abs(b).max() + 1e-6)


# (rows of batch*heads as (b, h)), seq_q, seq_k, head size, causal, explicit
# blocks: every schedule `_pick_blocks` can return — the resident walk (the
# GPT cell's shape, 4096, dense, a length that ends inside a tile), the lone
# tile (the BERT cell's shape, 1024 causal, ragged), seq_q != seq_k with and
# without named blocks (as ring attention calls), the query-major forward
# at a head size of 128, streamed tiles under a resident walk's size
SCHEDULE_CASES = {
    "gpt_s2048_causal": ((1, 1), 2048, 2048, 64, True, (None, None)),
    "walk_s2048_dense": ((1, 1), 2048, 2048, 64, False, (None, None)),
    "walk_s4096_causal": ((1, 1), 4096, 4096, 64, True, (None, None)),
    "walk_s4096_dense": ((1, 1), 4096, 4096, 64, False, (None, None)),
    "walk_ragged_1300_causal": ((1, 1), 1300, 1300, 64, True, (None, None)),
    "walk_sq512_sk2048": ((1, 1), 512, 2048, 64, False, (None, None)),
    "walk_sq2048_sk1100_causal": ((1, 1), 2048, 1100, 64, True,
                                  (None, None)),
    "bert_s512_dense": ((1, 2), 512, 512, 64, False, (None, None)),
    "lone_s1024_causal": ((1, 1), 1024, 1024, 64, True, (None, None)),
    "ragged_300_causal": ((1, 2), 300, 300, 64, True, (None, None)),
    "ragged_1000_dense": ((1, 1), 1000, 1000, 64, False, (None, None)),
    "ragged_1000_causal": ((1, 1), 1000, 1000, 64, True, (None, None)),
    "lone_sq256_sk700": ((1, 2), 256, 700, 64, False, (None, None)),
    "ring_sq256_sk512": ((1, 2), 256, 512, 64, False, (128, 128)),
    "ring_sq512_sk256_causal": ((1, 2), 512, 256, 64, True, (128, 256)),
    "named_ragged_600_causal": ((1, 1), 600, 600, 64, True, (256, 128)),
    "d128_s2048_causal": ((1, 1), 2048, 2048, 128, True, (None, None)),
    "d128_ragged_700_dense": ((1, 1), 700, 700, 128, False, (None, None)),
    "d32_s1536_causal": ((1, 1), 1536, 1536, 32, True, (None, None)),
}


@pytest.fixture
def force(monkeypatch):
    """force(name, value): replace a module-level piece of the schedule for
    one test.  The kernels' functions are traced once a shape (`jax.jit`),
    so what they traced before is dropped at each replacement, and again
    when the test ends."""
    def clear():
        fa._flash_fwd.clear_cache()
        fa._flash_bwd_impl.clear_cache()

    def force(name, value):
        monkeypatch.setattr(fa, name, value)
        clear()

    yield force
    monkeypatch.undo()
    clear()


class TestFlashSchedule:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("case", sorted(SCHEDULE_CASES))
    def test_forward_and_gradients_match(self, case, dtype):
        (b, h), sq, sk, d, causal, blocks = SCHEDULE_CASES[case]
        q, k, v = _bhsd(b, h, sq, sk, d, dtype)
        got, want = _flash_and_grads(q, k, v, causal, blocks)
        _assert_close(got, want, 3e-2 if dtype == jnp.bfloat16 else 2e-5)

    @pytest.mark.parametrize("causal,sq,sk,sched", [
        (True, 512, 512, (128, 128, 4, True)),
        (True, 512, 512, (128, 128, 1, True)),
        (True, 512, 512, (128, 256, 2, False)),
        (True, 384, 512, (256, 128, 2, True)),
        (False, 300, 300, (128, 128, 3, True)),
    ])
    def test_unmasked_body_equals_masked_body(self, force, causal, sq, sk,
                                              sched):
        """A tile wholly under the diagonal (or wholly inside the length)
        gives the same numbers through the unmasked body as through the
        masked one: with every tile that runs sent through the masked
        body, all five results are what the schedule gives (to an ulp:
        the interpreter fuses the two bodies differently)."""
        force("_pick_blocks", lambda *a, **kw: fa.Schedule(*sched))
        q, k, v = _bhsd(1, 2, sq, sk, 64, jnp.float32)
        scheduled, _ = _flash_and_grads(q, k, v, causal)
        key_tiles, query_tiles = fa._key_tiles, fa._query_tiles

        traced = []

        def all_masked_keys(*a):
            free, run = key_tiles(*a)
            traced.append("keys")
            return free * 0, run

        def all_masked_queries(*a):
            lo, free, end = query_tiles(*a)
            traced.append("queries")
            return lo, end, end

        force("_key_tiles", all_masked_keys)
        force("_query_tiles", all_masked_queries)
        masked, _ = _flash_and_grads(q, k, v, causal)
        # the kernels were traced anew, through the all-masked bounds
        assert {"keys", "queries"} <= set(traced)
        for a, b in zip(jax.tree_util.tree_leaves(scheduled),
                        jax.tree_util.tree_leaves(masked)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("sched", [
        (128, 128, 4, True), (128, 128, 1, True), (256, 128, 2, True),
        (128, 256, 2, True), (512, 512, 1, True), (128, 128, 4, False),
        (128, 128, 1, False), (512, 512, 1, False)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_any_schedule_gives_the_same_attention(self, force, sched,
                                                   causal):
        force("_pick_blocks", lambda *a, **kw: fa.Schedule(*sched))
        q, k, v = _bhsd(1, 2, 512, 512, 64, jnp.float32)
        _assert_close(*_flash_and_grads(q, k, v, causal), 2e-5)

    @pytest.mark.parametrize("shape,want", [
        # gpt355m_train: K/V of a head resident, 512 x 512 tiles walked
        # in-kernel up to the diagonal
        ((2048, 2048, 64, True), dict(
            schedule=(512, 512, 4, True), grid_fwd_dq=(4, 1),
            grid_dkv=(4, 1), tiles_run=10, tiles_masked=4, tiles_skipped=6,
            scores_run=2621440, scores_masked=1048576)),
        # bert_base_train: one tile is the whole head
        ((512, 512, 64, False), dict(
            schedule=(512, 512, 1, True), grid_fwd_dq=(1, 1),
            grid_dkv=(1, 1), tiles_run=1, tiles_masked=0, tiles_skipped=0,
            scores_run=262144, scores_masked=0)),
        # 16k / d128: streams 1024 x 1024 tiles, query-major forward
        ((16384, 16384, 128, True), dict(
            schedule=(1024, 1024, 1, False), grid_fwd_dq=(16, 16),
            grid_dkv=(16, 16), tiles_run=136, tiles_masked=16,
            tiles_skipped=120, scores_run=136 * 1024 * 1024,
            scores_masked=16 * 1024 * 1024)),
        # a ragged dense length under 1024: one tile, masked at its edge
        ((1000, 1000, 64, False), dict(
            schedule=(1008, 1008, 1, True), grid_fwd_dq=(1, 1),
            grid_dkv=(1, 1), tiles_run=1, tiles_masked=1, tiles_skipped=0,
            scores_run=1008 * 1008, scores_masked=1008 * 1008)),
        # s1024 / d64 causal: the single tile, not a walk of three (the
        # forward is 0.36 ms against 0.46, `_pick_blocks`)
        ((1024, 1024, 64, True), dict(
            schedule=(1024, 1024, 1, True), grid_fwd_dq=(1, 1),
            grid_dkv=(1, 1), tiles_run=1, tiles_masked=1, tiles_skipped=0,
            scores_run=1024 * 1024, scores_masked=1024 * 1024)),
        # s4096 / d64 causal: 512 KiB of K (and of V) a head, the largest
        # that stays resident
        ((4096, 4096, 64, True), dict(
            schedule=(512, 512, 8, True), grid_fwd_dq=(8, 1),
            grid_dkv=(8, 1), tiles_run=36, tiles_masked=8, tiles_skipped=28,
            scores_run=36 * 512 * 512, scores_masked=8 * 512 * 512)),
        # s8192 / d64: too long to stay resident, streamed key-major
        ((8192, 8192, 64, True), dict(
            schedule=(1024, 1024, 1, True), grid_fwd_dq=(8, 8),
            grid_dkv=(8, 8), tiles_run=36, tiles_masked=8, tiles_skipped=28,
            scores_run=36 * 1024 * 1024, scores_masked=8 * 1024 * 1024)),
        # a resident walk whose last tile the length ends in
        ((1300, 1300, 64, False), dict(
            schedule=(512, 512, 3, True), grid_fwd_dq=(3, 1),
            grid_dkv=(3, 1), tiles_run=9, tiles_masked=3, tiles_skipped=0,
            scores_run=9 * 512 * 512, scores_masked=3 * 512 * 512)),
    ], ids=["gpt355m_train", "bert_base_train", "16k_d128", "ragged_1000",
            "s1024_d64", "s4096_d64", "s8192_d64", "ragged_1300"])
    def test_schedule_counts_pinned(self, shape, want):
        sq, sk, d, causal = shape
        assert fa.schedule_counts(sq, sk, d, causal, jnp.bfloat16) == want

    def test_named_blocks_are_obeyed(self):
        """A caller that names a block gets its tile, one a grid step."""
        kw = dict(head_dim=64, dtype=jnp.bfloat16)
        assert fa._pick_blocks(2048, 2048, 512, 256, **kw) == (
            512, 256, 1, True)
        assert fa._pick_blocks(2048, 2048, 256, None, **kw) == (
            256, fa.DEFAULT_BLOCK_K, 1, True)
        assert fa._pick_blocks(200, 333, 1024, 1024, **kw) == (
            208, 336, 1, True)


class TestFusedNorms:
    def test_layer_norm(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((37, 256)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(256), jnp.float32)
        b = jnp.asarray(rng.standard_normal(256), jnp.float32)

        def ref(x, w, b):
            mu = x.mean(-1, keepdims=True)
            return (x - mu) / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5) * w + b

        y = fused_layer_norm(x, w, b, 1e-5, None, True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, w, b)),
                                   rtol=1e-5, atol=1e-5)
        g = jax.grad(lambda *a: (fused_layer_norm(*a, 1e-5, None, True) ** 2
                                 ).sum(), argnums=(0, 1, 2))(x, w, b)
        gr = jax.grad(lambda *a: (ref(*a) ** 2).sum(),
                      argnums=(0, 1, 2))(x, w, b)
        for a, b_ in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    def test_rms_norm(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((16, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(128), jnp.float32)

        def ref(x, w):
            return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * w

        y = fused_rms_norm(x, w, 1e-6, None, True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, w)),
                                   rtol=1e-5, atol=1e-5)
        g = jax.grad(lambda *a: (fused_rms_norm(*a, 1e-6, None, True) ** 2
                                 ).sum(), argnums=(0, 1))(x, w)
        gr = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1))(x, w)
        for a, b_ in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)


class TestKernelsUnderRecompute:
    """recompute()'s backward takes a vjp of a region whose ops each took
    their own vjp on the tape.  That outer differentiation must meet the
    kernels' custom_vjp calls, never a raw pallas_call (which has no usable
    JVP): the first GPT train step on a chip died exactly there, and the
    CPU gate never saw it because attention runs the XLA path off-TPU."""

    def test_recompute_grads_match_plain(self):
        import paddle_tpu as P
        import paddle_tpu.nn.functional as F
        from paddle_tpu.core.dispatch import apply
        from paddle_tpu.distributed.recompute import recompute

        class Block(P.nn.Layer):
            def __init__(self):
                super().__init__()
                self.ln1 = P.nn.LayerNorm(32)
                self.ln2 = P.nn.LayerNorm(32)
                self.qkv = P.nn.Linear(32, 96)

            def forward(self, x):
                b, s, h = x.shape
                n = F.layer_norm(x, 32, self.ln1.weight, self.ln1.bias,
                                 fused=True)
                q, k, v = (t.reshape([b, s, 2, 16])
                           for t in self.qkv(n).split(3, axis=-1))
                a = apply(lambda q, k, v: flash_attention_bshd(
                    q, k, v, causal=True, interpret=True), q, k, v)
                x, y = F.fused_ln_residual(
                    a.reshape([b, s, h]), x, self.ln2.weight,
                    self.ln2.bias, fused=True)
                return x + y

        def grads(use_recompute):
            P.seed(0)
            blk = Block()
            x = P.to_tensor(np.random.default_rng(0).standard_normal(
                (2, 16, 32)).astype(np.float32))
            x.stop_gradient = False
            out = recompute(blk, x) if use_recompute else blk(x)
            (out ** 2).mean().backward()
            return [x.grad.numpy()] + [p.grad.numpy()
                                       for p in blk.parameters()]

        for got, want in zip(grads(True), grads(False)):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def _windowed_dense(q, k, v, window):
    """Dense causal attention under a window, K/V heads repeated to the
    query heads' (query head i reads K/V head i // groups)."""
    groups = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, groups, axis=2) for x in (k, v))
    s = q.shape[1]
    gap = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (gap >= 0) & (gap < window)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


class TestFlashWindow:
    """The forward with a window (key tiles before the band's lower edge
    not run, those it crosses masked) and grouped K/V read through the
    index map, against the dense windowed composition (interpret mode,
    float32: the kernel's online softmax differs from one softmax by
    rounding alone, 1e-5)."""

    @pytest.mark.parametrize("s,heads,kv_heads,d,window,block", [
        (256, 4, 2, 64, 16, 64),      # window < block: one tile, masked
        (256, 2, 1, 128, 100, 64),    # the band spans tiles: some skipped
        (200, 4, 4, 64, 70, 64),      # a ragged tail under the window
        (130, 4, 2, 64, 40, None),    # the kernel's own schedule
        (300, 4, 4, 64, 1000, 64),    # a window past the sequence: causal
    ])
    def test_matches_dense_windowed(self, s, heads, kv_heads, d, window,
                                    block):
        rng = np.random.default_rng(s + window)
        q, k, v = (jnp.asarray(rng.standard_normal((1, s, h, d)),
                               jnp.float32)
                   for h in (heads, kv_heads, kv_heads))
        out = flash_attention_bshd(q, k, v, causal=True, window=window,
                                   block_q=block, block_k=block,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(
            _windowed_dense(q, k, v, window)), rtol=1e-5, atol=1e-5)

    def test_skipped_tiles_cost_nothing_in_the_answer(self):
        """Keys wholly before every query's window do not move the output:
        scrambling them changes nothing."""
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.standard_normal((1, 256, 2, 64)),
                               jnp.float32) for _ in range(3))
        kw = dict(causal=True, window=32, block_q=64, block_k=64,
                  interpret=True)
        out = flash_attention_bshd(q, k, v, **kw)
        k2 = k.at[:, :160].set(1e3)
        out2 = flash_attention_bshd(q, k2, v, **kw)
        np.testing.assert_array_equal(np.asarray(out[:, 192:]),
                                      np.asarray(out2[:, 192:]))

    def test_no_window_leaves_the_jaxpr_unchanged(self):
        """``window=None`` traces exactly what a call without it traces
        (forward and backward), and a window traces something else."""
        q, k, v = _qkv(1, 256, 2, 64)

        def forward(**kw):
            return lambda q, k, v: flash_attention_bshd(
                q, k, v, causal=True, interpret=True, **kw)

        def jaxprs(**kw):
            f = forward(**kw)
            g = jax.grad(lambda q, k, v: jnp.sum(f(q, k, v)))
            return str(jax.make_jaxpr(f)(q, k, v)), str(
                jax.make_jaxpr(g)(q, k, v))

        assert jaxprs(window=None) == jaxprs()
        assert str(jax.make_jaxpr(forward(window=64))(q, k, v)) != \
            jaxprs()[0]

    def test_backward_and_non_causal_window_refuse(self):
        q, k, v = _qkv(1, 128, 2, 64)
        with pytest.raises(ValueError, match="causal"):
            flash_attention_bshd(q, k, v, window=16, interpret=True)
        with pytest.raises(NotImplementedError, match="window"):
            jax.grad(lambda q: jnp.sum(flash_attention_bshd(
                q, k, v, causal=True, window=16, interpret=True)))(q)
