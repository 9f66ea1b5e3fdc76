"""Bytes/step optimization stack (PR 10) — contracts and regressions.

Covers the three HBM-roofline fronts and their satellites:

- **fused single-pass optimizer** (ops/pallas/optim.py): fused AdamW
  trajectory + final weights match the unfused per-op loop at 1e-5;
  bf16-moments mode stays within its documented tolerance; accumulator
  sharding inheritance (PR 4) survives the fused path.
- **Pallas fused LN/residual** (ops/pallas/norm.py): forward and all
  four gradients match the pure-JAX composition (incl. the gelu
  variant); the pure fallback and the fused path are interchangeable.
- **bf16 activation residency** (amp/policy.py + to_static): the
  20-step gpt-tiny loss trajectory stays within the documented
  tolerance of the f32 run; the policy is trace-scoped (never leaks to
  eager); remat="bf16" saved-boundary narrowing keeps training close;
  shardlint reports ZERO SL303 findings on the optimized program.
- **profiler fused-kernel costing** (observability/profile.py): a
  pallas_call is costed by its operand/result bytes at the call
  boundary, inside the caller's named scope — the flagged/clean pair
  pins both the bytes and the attribution (nothing falls into
  ``<unattributed>``).
- **perfgate**: ratchet semantics (an improvement without
  --write-baseline still PASSES and prints the ratchet prompt) and the
  ``--diff`` table; the remat bench lane's honest keys.
- **bench.py worker limits**: a worker past its time limit is killed
  with its whole process GROUP (stub sleeper with a child — both die)
  and reported as a failed lane.
- **serving token identity**: fused-LN serving produces tokens
  identical to the unfused engine, request for request.
"""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn.functional as F
from paddle_tpu import amp, nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


@pytest.fixture(autouse=True)
def _clean_mesh():
    # earlier test modules (launcher/distributed) can leave a global
    # mesh installed; engine/train-step compiles here must be
    # single-device like the standalone runs (repo-wide pattern)
    from paddle_tpu.distributed.mesh import set_mesh
    set_mesh(None)
    yield
    set_mesh(None)


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "ptpu_bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------- fused optimizer
def _train_linear(fused, moment_dtype=None, steps=6, cls="AdamW"):
    P.seed(0)
    m = nn.Linear(16, 24)
    kw = dict(learning_rate=0.01, parameters=m.parameters(), fused=fused)
    if moment_dtype:
        kw["moment_dtype"] = moment_dtype
    opt = getattr(P.optimizer, cls)(**kw)
    xs = P.to_tensor(np.random.default_rng(0)
                     .standard_normal((4, 16)).astype(np.float32))
    losses = []
    for _ in range(steps):
        opt.clear_grad()
        y = m(xs)
        loss = (y * y).mean()
        loss.backward()
        opt.step()
        losses.append(float(loss.numpy()))
    return losses, {k: np.asarray(v.numpy()) for k, v in
                    m.state_dict().items()}


class TestFusedOptimizer:
    @pytest.mark.parametrize("cls", ["Adam", "AdamW"])
    def test_fused_matches_unfused(self, cls):
        l0, s0 = _train_linear(False, cls=cls)
        l1, s1 = _train_linear(True, cls=cls)
        np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-6)
        for k in s0:
            np.testing.assert_allclose(s0[k], s1[k], rtol=1e-5,
                                       atol=1e-6)

    def test_bf16_moments_tolerance(self):
        """The documented bf16-moments contract: same trajectory within
        1e-2 relative over the short run (moment STORAGE narrows, the
        update math stays f32 in-kernel)."""
        l0, _ = _train_linear(True)
        l1, _ = _train_linear(True, moment_dtype="bfloat16")
        np.testing.assert_allclose(l0, l1, rtol=1e-2, atol=1e-2)

    def test_fused_kernel_exact_vs_loop_math(self):
        """Kernel-level: one fused update == the unfused eqn sequence."""
        from paddle_tpu.ops.pallas.optim import fused_adam_update
        rng = np.random.default_rng(3)
        p = rng.standard_normal((32, 48)).astype(np.float32)
        g = rng.standard_normal((32, 48)).astype(np.float32)
        m = rng.standard_normal((32, 48)).astype(np.float32)
        v = np.abs(rng.standard_normal((32, 48))).astype(np.float32)
        lr, b1, b2, eps, wd = 0.01, 0.9, 0.999, 1e-8, 0.05
        c1, c2 = 1 - b1 ** 3, 1 - b2 ** 3
        np_, nm, nv = fused_adam_update(
            p, g, m, v, lr, c1, c2, beta1=b1, beta2=b2, eps=eps,
            weight_decay=wd, decay_on=True, interpret=True)
        pp = p * (1.0 - lr * wd)
        rm = b1 * m + (1 - b1) * g
        rv = b2 * v + (1 - b2) * g * g
        ref = pp - lr * (rm / c1) / (np.sqrt(rv / c2) + eps)
        np.testing.assert_allclose(np.asarray(np_), ref, rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(nm), rm, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(nv), rv, rtol=1e-6)

    def test_fused_accumulators_inherit_sharding(self):
        """PR 4's SL102 fix must survive the fused path: moments of a
        dist_spec-annotated param keep the param's PartitionSpec."""
        from paddle_tpu.distributed.mesh import get_dist_spec, shard_tensor
        P.seed(0)
        m = nn.Linear(16, 24)
        shard_tensor(m.weight, None, "tp")
        opt = P.optimizer.AdamW(learning_rate=0.01,
                                parameters=m.parameters(), fused=True)
        y = m(P.to_tensor(np.ones((2, 16), np.float32)))
        (y * y).mean().backward()
        opt.step()
        acc = opt._acc("moment1", m.weight)
        assert get_dist_spec(acc) == get_dist_spec(m.weight)

    def test_rank1_params_fall_back_to_loop(self):
        """Biases (rank-1) keep the unfused loop; the step still runs
        and updates them."""
        P.seed(0)
        m = nn.Linear(8, 8)
        opt = P.optimizer.AdamW(learning_rate=0.1,
                                parameters=m.parameters(), fused=True)
        before = np.asarray(m.bias.numpy()).copy()
        y = m(P.to_tensor(np.ones((2, 8), np.float32)))
        (y * y).mean().backward()
        opt.step()
        assert not opt._will_fuse(m.bias)
        assert opt._will_fuse(m.weight)
        assert np.abs(np.asarray(m.bias.numpy()) - before).max() > 0


# ---------------------------------------------- fused LN / residual
def _ln_res_ref(x, r, w, b, eps=1e-5, act=None):
    import jax
    import jax.numpy as jnp
    h = x + r
    hf = h.astype(jnp.float32)
    mean = hf.mean(-1, keepdims=True)
    var = ((hf - mean) ** 2).mean(-1, keepdims=True)
    y = (hf - mean) / jnp.sqrt(var + eps) * w + b
    if act == "gelu":
        y = jax.nn.gelu(y, approximate=True)
    return h, y.astype(h.dtype)


class TestFusedLNResidual:
    @pytest.mark.parametrize("act", [None, "gelu"])
    def test_forward_and_grads_match_reference(self, act):
        import jax
        from paddle_tpu.ops.pallas.norm import fused_ln_residual
        rng = np.random.default_rng(0)
        x = np.asarray(rng.standard_normal((4, 9, 64)), np.float32)
        r = np.asarray(rng.standard_normal((4, 9, 64)), np.float32)
        w = np.asarray(rng.standard_normal(64), np.float32)
        b = np.asarray(rng.standard_normal(64), np.float32)
        h1, y1 = fused_ln_residual(x, r, w, b, 1e-5, act, None, True)
        h2, y2 = _ln_res_ref(x, r, w, b, act=act)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   atol=1e-5)

        def f(fn):
            return lambda *a: (
                (fn(*a)[1].astype(np.float32) ** 2).sum()
                + (fn(*a)[0].astype(np.float32) * 0.3).sum())
        g1 = jax.grad(f(lambda *a: fused_ln_residual(
            *a, 1e-5, act, None, True)), argnums=(0, 1, 2, 3))(x, r, w, b)
        g2 = jax.grad(f(lambda *a: _ln_res_ref(*a, act=act)),
                      argnums=(0, 1, 2, 3))(x, r, w, b)
        for got, want in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)

    def test_plain_fused_layer_norm_pallas_backward(self):
        import jax
        from paddle_tpu.ops.pallas.norm import fused_layer_norm
        rng = np.random.default_rng(1)
        x = np.asarray(rng.standard_normal((6, 64)), np.float32)
        w = np.asarray(rng.standard_normal(64), np.float32)
        b = np.asarray(rng.standard_normal(64), np.float32)

        def ref(x, w, b):
            import jax.numpy as jnp
            m = x.mean(-1, keepdims=True)
            v = ((x - m) ** 2).mean(-1, keepdims=True)
            return (x - m) / jnp.sqrt(v + 1e-5) * w + b
        g1 = jax.grad(lambda *a: (fused_layer_norm(
            *a, 1e-5, None, True) ** 2).sum(), argnums=(0, 1, 2))(x, w, b)
        g2 = jax.grad(lambda *a: (ref(*a) ** 2).sum(),
                      argnums=(0, 1, 2))(x, w, b)
        for got, want in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)

    def test_functional_fused_vs_fallback(self):
        """F.fused_ln_residual: the Pallas path (fused=True, interpret
        on CPU) and the pure-JAX fallback (fused=False) are numerically
        interchangeable — the flag is a performance knob, not a
        semantics knob."""
        rng = np.random.default_rng(2)
        x = P.to_tensor(np.asarray(
            rng.standard_normal((2, 8, 64)), np.float32))
        r = P.to_tensor(np.asarray(
            rng.standard_normal((2, 8, 64)), np.float32))
        ln = nn.LayerNorm(64)
        h1, y1 = F.fused_ln_residual(x, r, ln.weight, ln.bias, 1e-5,
                                     fused=True)
        h2, y2 = F.fused_ln_residual(x, r, ln.weight, ln.bias, 1e-5,
                                     fused=False)
        np.testing.assert_allclose(np.asarray(h1.numpy()),
                                   np.asarray(h2.numpy()), atol=1e-6)
        np.testing.assert_allclose(np.asarray(y1.numpy()),
                                   np.asarray(y2.numpy()), atol=1e-5)

    def test_transformer_encoder_layer_fused_ln_equivalent(self):
        """nn.TransformerEncoderLayer(fused_ln=True): each post-LN
        residual join collapses into the fused kernel; outputs and
        trained grads match the plain composition."""
        def run(fused):
            P.seed(0)
            layer = nn.TransformerEncoderLayer(
                d_model=64, nhead=4, dim_feedforward=128, dropout=0.0,
                fused_ln=fused)
            x = P.to_tensor(np.random.default_rng(0)
                            .standard_normal((2, 6, 64))
                            .astype(np.float32))
            out = layer(x)
            (out ** 2).mean().backward()
            g = np.asarray(layer.norm1.weight.grad.numpy())
            return np.asarray(out.numpy()), g

        o0, g0 = run(False)
        o1, g1 = run(True)
        np.testing.assert_allclose(o0, o1, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(g0, g1, rtol=1e-3, atol=1e-4)

    def test_set_fused_norm_flag_roundtrip(self):
        prev = F.set_fused_norm(True)
        try:
            assert F.fused_norm_enabled()
        finally:
            F.set_fused_norm(prev)
        assert F.fused_norm_enabled() == prev


# ------------------------------------------- bf16 residency policy
def _gpt_losses(optimized, steps, lr=1e-3, remat=None):
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
    P.seed(0)
    cfg = gpt3_tiny(fused_ln=bool(optimized))
    model = GPTForCausalLM(cfg)
    opt = P.optimizer.AdamW(learning_rate=lr,
                            parameters=model.parameters(),
                            fused=bool(optimized))

    @P.jit.to_static(amp_policy="bf16" if optimized else None,
                     remat=remat)
    def train_step(ids, labels):
        opt.clear_grad()
        logits = model(ids)
        loss = F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1]))
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)
    ids = P.to_tensor(rng.integers(0, cfg.vocab_size, (2, 32)),
                      dtype="int64")
    labels = P.to_tensor(rng.integers(0, cfg.vocab_size, (2, 32)),
                         dtype="int64")
    return [float(train_step(ids, labels).numpy()) for _ in range(steps)]


class TestBf16ActivationPolicy:
    def test_policy_is_trace_scoped(self):
        import jax.numpy as jnp
        assert amp.current_policy() is None
        with amp.activation_residency("bf16"):
            assert amp.current_policy() is not None
            assert jnp.dtype(amp.residency_dtype()) == jnp.bfloat16
        assert amp.current_policy() is None
        assert amp.remat_active() is False

    def test_20_step_loss_trajectory_within_tolerance(self):
        """THE numerics contract (docs/performance_guide.md): 20 gpt
        train steps under bf16 activation residency + fused optimizer +
        fused LN track the f32 run within |Δloss| <= 0.05 at every
        step (measured headroom ~100x: observed max |Δ| ≈ 6e-4)."""
        f32 = _gpt_losses(False, 20)
        opt = _gpt_losses(True, 20)
        assert f32[-1] < f32[0], "f32 run failed to learn"
        diffs = [abs(a - b) for a, b in zip(f32, opt)]
        assert max(diffs) <= 0.05, (max(diffs), f32, opt)

    def test_remat_bf16_saved_boundaries_close_to_plain(self):
        """remat="bf16" narrows only the SAVED block boundaries; the
        trajectory stays near the no-remat run (bf16 round-trip of the
        boundary bounds the drift)."""
        plain = _gpt_losses(False, 6)
        remat = _gpt_losses(False, 6, remat="bf16")
        diffs = [abs(a - b) for a, b in zip(plain, remat)]
        assert max(diffs) <= 0.05, (plain, remat)

    @staticmethod
    def _enable_recompute_losses(mode, autocast=False):
        P.seed(0)
        m = nn.Sequential(nn.Linear(16, 32), nn.GELU(),
                          nn.Linear(32, 8))
        if mode is not None:
            m[0].enable_recompute(mode)
        opt = P.optimizer.AdamW(learning_rate=0.01,
                                parameters=m.parameters())
        xs = P.to_tensor(np.random.default_rng(0)
                         .standard_normal((4, 16)).astype(np.float32))
        losses = []
        for _ in range(4):
            opt.clear_grad()
            with P.amp.auto_cast(enable=autocast, level="O1"):
                loss = (m(xs).astype("float32") ** 2).mean()
            loss.backward()
            opt.step()
            losses.append(float(loss.numpy()))
        return losses

    def test_per_layer_enable_recompute(self):
        """Per-Layer remat selection: a layer wrapped via
        enable_recompute(True) trains to the same losses as the plain
        layer (the recompute region is numerics-neutral in f32), and
        "auto" mode only engages under an ambient remat policy."""
        run = self._enable_recompute_losses
        plain = run(None)
        remat = run(True)
        np.testing.assert_allclose(plain, remat, rtol=1e-5, atol=1e-6)
        auto_off = run("auto")      # no ambient policy: behaves plain
        np.testing.assert_allclose(plain, auto_off, rtol=1e-5, atol=1e-6)

    def test_per_layer_enable_recompute_under_autocast(self):
        """The property users rely on: under auto_cast(O1) the region
        re-runs in the forward's bf16 (backward() is called after the
        block has exited), so the recomputed layer trains to the plain
        layer's losses at bf16's level — and does learn."""
        plain = self._enable_recompute_losses(None, autocast=True)
        remat = self._enable_recompute_losses(True, autocast=True)
        assert remat[-1] < remat[0]
        np.testing.assert_allclose(plain, remat, rtol=2 ** -7)

    @pytest.mark.shardlint
    def test_optimized_program_has_zero_sl303(self):
        """bf16 residency must not create f32-stored/bf16-consumed
        inputs: params keep a non-convert consumer (the f32 optimizer
        math), activations are bf16-stored outright.  SL303 count on
        the optimized gpt target: exactly 0."""
        import perfgate
        from paddle_tpu import analysis
        train_step, ids, labels = perfgate.build_gpt_train_step()
        jaxpr, infos = train_step.traced_program(ids, labels)
        findings, _ = analysis.audit_jaxpr(
            jaxpr, where="<optimized>", inputs=infos,
            config=analysis.AuditConfig(f32_param_min_bytes=1 << 10))
        assert not [f for f in findings if f.code == "SL303"], findings


# ------------------------------------- profiler fused-kernel costing
@pytest.mark.profile
class TestPallasBoundaryCosting:
    # a bare 2-grid-step elementwise kernel: boundary bytes and body
    # flops are exactly computable by hand
    ROWS, COLS, GRID = 16, 64, 2

    def _trace(self, tagging):
        import jax
        from jax.experimental import pallas as pl
        from paddle_tpu.observability import profile

        def kern(x_ref, o_ref):
            o_ref[:] = x_ref[:] * 2.0

        rows, cols, grid = self.ROWS, self.COLS, self.GRID
        br = rows // grid

        def f(x):
            with profile.scope("blk"):
                return pl.pallas_call(
                    kern, grid=(grid,),
                    in_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((br, cols), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct((rows, cols),
                                                   np.float32),
                    interpret=True)(x)
        prev = profile.set_scope_tagging(tagging)
        try:
            jaxpr = jax.make_jaxpr(f)(np.ones((rows, cols), np.float32))
        finally:
            profile.set_scope_tagging(prev)
        return profile.profile_traced(jaxpr, where="<t>")

    def test_pallas_call_costed_at_call_boundary_in_caller_scope(self):
        """The flagged/clean pair's CLEAN half: with scope tagging on,
        the fused kernel's bytes land in the caller's scope at exactly
        operands+results (x in, y out — NOT the kernel body's per-block
        VMEM traffic), flops = body flops x grid steps, and nothing is
        unattributed."""
        rep = self._trace(True)
        row = {r.name: r for r in rep.rows()}
        assert "blk" in row, list(row)
        blk = row["blk"]
        boundary = self.ROWS * self.COLS * 4 * 2       # x + y
        assert blk.bytes == boundary, (blk.bytes, boundary)
        # one mul per element, body counted once per grid step
        assert blk.flops == self.ROWS * self.COLS, blk.flops
        assert rep.unattributed.bytes == 0
        assert rep.frac_attributed_bytes == 1.0

    def test_pallas_call_without_tagging_is_unattributed_not_zero(self):
        """FLAGGED half: tagging off, the kernel's cost must still be
        nonzero — it lands in <unattributed> instead of vanishing."""
        rep = self._trace(False)
        assert not rep.layers
        boundary = self.ROWS * self.COLS * 4 * 2
        assert rep.unattributed.bytes >= boundary

    def test_fused_ln_cheaper_than_unfused_composition_in_model(self):
        """End-to-end: the fused LN call boundary costs fewer
        cost-model bytes than the pure-jnp composition of the same norm
        — the reduction the perfgate ratchet locked in — and stays
        attributed to its layer scope."""
        import jax
        from paddle_tpu.observability import profile
        from paddle_tpu.ops.pallas.norm import fused_layer_norm

        x = np.ones((8, 64), np.float32)
        w = np.ones((64,), np.float32)
        b = np.zeros((64,), np.float32)

        def fused(x, w, b):
            with profile.scope("blk"):
                return fused_layer_norm(x, w, b, 1e-5, None, True).sum()

        def unfused(x, w, b):
            import jax.numpy as jnp
            with profile.scope("blk"):
                m = x.mean(-1, keepdims=True)
                v = ((x - m) ** 2).mean(-1, keepdims=True)
                return ((x - m) / jnp.sqrt(v + 1e-5) * w + b).sum()

        rep_f = profile.profile_traced(jax.make_jaxpr(fused)(x, w, b))
        rep_u = profile.profile_traced(jax.make_jaxpr(unfused)(x, w, b))
        blk_f = {r.name: r for r in rep_f.rows()}["blk"]
        blk_u = {r.name: r for r in rep_u.rows()}["blk"]
        assert blk_f.bytes < blk_u.bytes, (blk_f.bytes, blk_u.bytes)
        assert rep_f.unattributed.bytes == 0


# -------------------------------------------------- perfgate gates
@pytest.mark.profile
class TestPerfgateRatchetAndDiff:
    @pytest.fixture()
    def stub_gate(self, monkeypatch, tmp_path):
        import perfgate
        monkeypatch.setitem(perfgate.TARGETS, "stub",
                            lambda: {"bytes_per_step": 800})
        for k in [k for k in perfgate.TARGETS if k != "stub"]:
            monkeypatch.delitem(perfgate.TARGETS, k)
        base = tmp_path / "base.json"
        return perfgate, base

    def test_improvement_without_write_baseline_passes_with_prompt(
            self, stub_gate, capsys):
        """The lint_all perfgate gate's ratchet semantics: a big
        improvement is NOT a failure — exit 0 — but the operator is
        prompted to ratchet via --write-baseline."""
        perfgate, base = stub_gate
        base.write_text(json.dumps({
            "tool": "perfgate", "version": 1, "tolerance": 0.05,
            "targets": {"stub": {"bytes_per_step": 1000}}}))
        rc = perfgate.main(["--check", "--baseline", str(base)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "improved" in out and "--write-baseline" in out
        assert "ratchet" in out

    def test_regression_still_fails(self, stub_gate, capsys):
        perfgate, base = stub_gate
        base.write_text(json.dumps({
            "tool": "perfgate", "version": 1, "tolerance": 0.05,
            "targets": {"stub": {"bytes_per_step": 500}}}))
        rc = perfgate.main(["--check", "--baseline", str(base)])
        assert rc == 1
        assert "REGRESSION" in capsys.readouterr().out

    def test_diff_renders_per_metric_table(self, stub_gate, capsys):
        perfgate, base = stub_gate
        base.write_text(json.dumps({
            "tool": "perfgate", "version": 1,
            "targets": {"stub": {"bytes_per_step": 1000,
                                 "gone_metric": 7}}}))
        rc = perfgate.main(["--diff", "--baseline", str(base)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "-20.0%" in out           # 1000 -> 800
        assert "gone" in out             # metric vanished
        assert "baseline" in out and "current" in out

    def test_remat_report_keys_are_honest(self):
        """The bench remat lane: on/off bytes plus signed saved-pct —
        remat RAISES cost-model bytes (recompute is not free), and the
        lane must say so rather than echo a feel-good bool."""
        import perfgate
        rep = perfgate.remat_report()
        for k in ("remat_bytes_per_step_off", "remat_bytes_per_step_on",
                  "remat_bytes_saved_pct", "remat_peak_hbm_saved_pct"):
            assert k in rep
        assert rep["remat_bytes_per_step_on"] > \
            rep["remat_bytes_per_step_off"]
        assert rep["remat_bytes_saved_pct"] < 0


# ---------------------------------------------- optimized gpt target
@pytest.mark.profile
class TestOptimizedTargetContracts:
    def test_bytes_per_step_reduced_at_least_25pct_vs_plain(self):
        """The tentpole acceptance, measured live: the optimized build
        (bf16 residency + fused optimizer + fused LN) cuts cost-model
        bytes/step >= 25% vs the plain f32 per-op build of the SAME
        model/step."""
        import perfgate
        rep_plain, _ = perfgate.gpt_roofline_report(optimized=False)
        rep_opt, _ = perfgate.gpt_roofline_report(optimized=True)
        drop = 1.0 - rep_opt.total_bytes / rep_plain.total_bytes
        assert drop >= 0.25, (rep_plain.total_bytes, rep_opt.total_bytes)

    def test_attribution_holds_through_fused_paths(self):
        """>= 90% of bytes AND flops attribute to named layers with the
        Pallas/bf16 paths enabled (the custom-VJP backward included)."""
        import perfgate
        from paddle_tpu.observability import profile
        train_step, ids, labels = perfgate.build_gpt_train_step()
        jaxpr, _ = train_step.traced_program(ids, labels)
        rep = profile.profile_traced(jaxpr, where="<opt>")
        assert rep.frac_attributed_bytes >= 0.90, rep.to_dict()
        assert rep.frac_attributed_flops >= 0.90, rep.to_dict()
        names = {l.name for l in rep.layers}
        assert "optimizer.step" in names
        assert any(n.endswith("/ln2") for n in names), names


# ------------------------------------------------- bench worker limit
class TestBenchWorkerLimit:
    def test_limit_kills_worker_process_group(self, tmp_path):
        """Stub sleeper: a parent that spawns a child then sleeps —
        past the limit, _finish must take down BOTH (the worker runs in
        its own session, so its children die with it) and report the
        lane as failed."""
        bench = _load_bench()
        pidfile = tmp_path / "child.pid"
        code = ("import subprocess,sys,time\n"
                "c=subprocess.Popen([sys.executable,'-c',"
                "'import time;time.sleep(120)'])\n"
                f"open({str(pidfile)!r},'w').write(str(c.pid))\n"
                "print('{\"partial\": 1}', flush=True)\n"
                "time.sleep(120)\n")
        proc = subprocess.Popen([sys.executable, "-c", code],
                                stdout=subprocess.PIPE, text=True,
                                start_new_session=True)
        try:
            # wait for the child pid to appear so the group is complete
            for _ in range(100):
                if pidfile.exists() and pidfile.read_text().strip():
                    break
                time.sleep(0.1)
            child_pid = int(pidfile.read_text().strip())
            rc, result = bench._finish(proc, 1.0)
            # a killed worker's partial output is not a result
            assert (rc, result) == (124, None)
            assert proc.poll() is not None
            # the CHILD must be gone too (process-group kill, not a
            # parent-only kill that orphans it)
            for _ in range(50):
                try:
                    os.kill(child_pid, 0)
                except ProcessLookupError:
                    break
                try:  # reap a zombie child if init hasn't yet
                    os.waitpid(child_pid, os.WNOHANG)
                except ChildProcessError:
                    pass
                time.sleep(0.1)
            else:
                pytest.fail(f"child {child_pid} survived the group kill")
        finally:
            try:
                os.killpg(proc.pid, 9)
            except (OSError, ProcessLookupError):
                pass

    def test_worker_exit_code_and_result_propagate(self):
        bench = _load_bench()

        def run(code):
            return bench._finish(subprocess.Popen(
                [sys.executable, "-c", code], stdout=subprocess.PIPE,
                text=True, start_new_session=True), 30.0)

        assert run("print('noise'); print('{\"k\": 2}')") == (0, {"k": 2})
        assert run("import sys; print('{\"k\": 2}'); sys.exit(3)") == \
            (3, None)
        assert run("print('no json here')") == (1, None)


# ------------------------------------------- serving token identity
@pytest.mark.serving
class TestServingFusedLNIdentity:
    def test_fused_ln_engine_token_identical(self):
        """The deterministic-sampler replay contract, reused: the SAME
        prompts/seeds through a fused-LN engine and a plain engine
        produce identical tokens — the serving path is unaffected by
        the training-side byte work."""
        from paddle_tpu import serving
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny

        def gen(fused_ln):
            P.seed(0)
            model = GPTForCausalLM(gpt3_tiny(fused_ln=fused_ln))
            eng = serving.LLMEngine(model, serving.EngineConfig(
                max_num_seqs=4, page_size=4, max_model_len=48,
                prefill_buckets=(8, 32)))
            rng = np.random.default_rng(7)
            prompts = [list(rng.integers(1, 256, n))
                       for n in (3, 7, 12, 5)]
            sps = [serving.SamplingParams(
                max_new_tokens=6, temperature=0.7 if i % 2 else 0.0,
                top_k=20 if i % 3 else 0, seed=i)
                for i in range(len(prompts))]
            try:
                return [r.output_token_ids
                        for r in eng.generate(prompts, sps)]
            finally:
                eng.shutdown()

        assert gen(True) == gen(False)
