"""paddle_tpu.resilience.sentinel — in-trace anomaly probes, the
skip/rollback policy machine, replay-bisection localization, the
cross-rank SDC digest vote, and the serving guard.

The `chaos`-marked tests are the PR 15 acceptance proofs (also run by
the tools/lint_all.py chaos gate): an injected bitflip/NaN training
run detects within ONE step, skips (zero-update commit) or rolls back,
and the rolled-back-and-resumed loss trajectory + final weights match
the fault-free run EXACTLY; a guarded serving run with injected NaN
logits evicts-and-requeues only the offender token-identically.  The
3-process digest-vote proof lives in
tests/test_distributed_multiprocess.py.
"""
import math
import threading

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as P
import paddle_tpu.nn as nn
from paddle_tpu import resilience as R
from paddle_tpu.resilience import faultinject, fleet, sentinel
from paddle_tpu.observability.recompile import recompile_log

pytestmark = pytest.mark.sentinel


def _batch(step, din=6, dout=3, n=8):
    rng = np.random.default_rng(1000 + step)
    X = rng.standard_normal((n, din)).astype(np.float32)
    y = rng.standard_normal((n, dout)).astype(np.float32)
    return P.to_tensor(X), P.to_tensor(y)


def _build(guard=True, fused=False, lr=0.05, cls=None):
    P.seed(0)
    model = nn.Linear(6, 3)
    cls = cls or P.optimizer.AdamW
    opt = cls(learning_rate=lr, parameters=model.parameters(),
              guard=guard, **({"fused": fused}
                              if cls is not P.optimizer.SGD else {}))
    return model, opt


def _eager_step(model, opt, step):
    X, y = _batch(step)
    opt.clear_grad()
    loss = ((model(X) - y) ** 2).mean()
    loss.backward()
    opt.step()
    return float(loss.numpy())


# ------------------------------------------------------------ summary
class TestGuardSummary:
    @pytest.mark.smoke
    def test_parse_and_fields(self):
        s = sentinel.GuardSummary.from_array(
            np.asarray([1.0, 4.0, 0.0, 7.0], np.float32))
        assert s.good and s.grad_sumsq == 4.0 and s.regions == 7
        assert s.grad_norm == 2.0
        bad = sentinel.GuardSummary.from_array(
            np.asarray([0.0, np.nan, 3.0, 7.0], np.float32))
        assert not bad.good and bad.bad_regions == 3
        assert math.isnan(bad.grad_norm)
        assert bad.to_dict()["regions"] == 7
        with pytest.raises(ValueError):
            sentinel.GuardSummary.from_array(np.zeros(2))

    @pytest.mark.smoke
    def test_anomaly_event_machine_readable(self):
        evt = sentinel.AnomalyDetected(12, "nan_grad", "train",
                                       bad_regions=2)
        d = evt.to_dict()
        assert d == {"step": 12, "kind": "nan_grad", "site": "train",
                     "bad_regions": 2}
        assert isinstance(evt, RuntimeError)   # raisable where opted in


# ----------------------------------------------------- optimizer guard
class TestOptimizerGuard:
    @pytest.mark.smoke
    def test_clean_guarded_step_identical_to_unguarded(self):
        m1, o1 = _build(guard=False)
        m2, o2 = _build(guard=True)
        _eager_step(m1, o1, 1)
        _eager_step(m2, o2, 1)
        np.testing.assert_array_equal(np.asarray(m1.weight._value),
                                      np.asarray(m2.weight._value))
        s = o2.guard_summary()
        assert s.good and s.bad_regions == 0 and s.regions == 2

    def test_nan_grad_commits_zero_update_for_that_param(self):
        model, opt = _build(guard=True)
        X, y = _batch(1)
        loss = ((model(X) - y) ** 2).mean()
        loss.backward()
        w0 = np.asarray(model.weight._value).copy()
        b0 = np.asarray(model.bias._value).copy()
        model.weight.grad._set_value(
            model.weight.grad._value.at[0, 0].set(jnp.nan))
        opt.step()
        # poisoned param holds (zero-update commit), clean param moves
        np.testing.assert_array_equal(np.asarray(model.weight._value),
                                      w0)
        assert not np.array_equal(np.asarray(model.bias._value), b0)
        assert np.isfinite(np.asarray(model.bias._value)).all()
        s = opt.guard_summary()
        assert not s.good and s.bad_regions == 1 and s.regions == 2
        # moments of the poisoned param hold at their fresh init (0)
        m = opt._acc("moment1", model.weight)
        np.testing.assert_array_equal(np.asarray(m._value),
                                      np.zeros_like(w0))

    def test_beta_pow_holds_on_skipped_param(self):
        model, opt = _build(guard=True)
        # one clean step so the powers exist and have advanced
        _eager_step(model, opt, 1)
        b1p = opt._acc("beta1_pow", model.weight)
        before = float(b1p._value)
        X, y = _batch(2)
        opt.clear_grad()
        loss = ((model(X) - y) ** 2).mean()
        loss.backward()
        model.weight.grad._set_value(
            jnp.full_like(model.weight.grad._value, jnp.nan))
        opt.step()
        assert float(b1p._value) == before          # held
        bias_b1p = opt._acc("beta1_pow", model.bias)
        assert float(bias_b1p._value) == pytest.approx(before * 0.9)

    def test_fused_guard_clean_identical_and_nan_gated(self):
        # rank-2 params route through the fused kernel's in-kernel gate
        from paddle_tpu.ops.pallas.optim import fused_adam_update
        rng = np.random.default_rng(0)
        p = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
        g = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
        m = jnp.zeros_like(p)
        v = jnp.zeros_like(p)
        kw = dict(beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01,
                  decay_on=True)
        p1, m1, v1 = fused_adam_update(p, g, m, v, 0.1, 0.1, 0.001, **kw)
        p2, m2, v2, parts = fused_adam_update(p, g, m, v, 0.1, 0.1,
                                              0.001, guard=True, **kw)
        for a, b in ((p1, p2), (m1, m2), (v1, v2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(parts)[0, 0] == pytest.approx(
            float(jnp.sum(g * g)), rel=1e-6)
        p3, m3, v3, parts3 = fused_adam_update(
            p, g.at[0, 0].set(jnp.nan), m, v, 0.1, 0.1, 0.001,
            guard=True, **kw)
        assert not np.isfinite(np.asarray(parts3)[:, 0]).all()
        np.testing.assert_array_equal(np.asarray(p3), np.asarray(p))
        np.testing.assert_array_equal(np.asarray(m3), np.asarray(m))

    def test_generic_guard_covers_sgd(self):
        model, opt = _build(guard=True, cls=P.optimizer.SGD)
        X, y = _batch(1)
        loss = ((model(X) - y) ** 2).mean()
        loss.backward()
        w0 = np.asarray(model.weight._value).copy()
        model.weight.grad._set_value(
            jnp.full_like(model.weight.grad._value, jnp.inf))
        opt.step()
        np.testing.assert_array_equal(np.asarray(model.weight._value),
                                      w0)
        assert not opt.guard_summary().good

    @pytest.mark.smoke
    def test_corrupt_array_deterministic(self):
        spec = faultinject.FaultSpec("optimizer.grads", "bitflip", at=3)
        a = np.linspace(1.0, 2.0, 16, dtype=np.float32)
        c1 = faultinject.corrupt_array(spec, a, seed=5)
        c2 = faultinject.corrupt_array(spec, a, seed=5)
        np.testing.assert_array_equal(c1.view(np.uint32),
                                      c2.view(np.uint32))
        assert (c1 != a).sum() == 1       # exactly one element corrupted
        # a LOW-bit flip is the strictly-silent variant: values change,
        # nothing goes non-finite (only a digest vote can see it)
        silent = faultinject.FaultSpec("optimizer.grads", "bitflip",
                                       at=0, payload={"bit": 20})
        cs = faultinject.corrupt_array(silent, a, seed=5)
        assert np.isfinite(cs).all() and not np.array_equal(cs, a)
        c3 = faultinject.corrupt_array(
            faultinject.FaultSpec("optimizer.grads", "nan_grad", at=0,
                                  payload={"index": 4}), a)
        assert np.isnan(c3[4]) and np.isfinite(np.delete(c3, 4)).all()
        # float64 inputs stay float64 and ONLY the target element
        # changes (bit-exact elsewhere — the digest-vote soundness
        # requirement); default high bit scales to the 64-bit word
        a64 = np.linspace(1.0, 2.0, 8, dtype=np.float64)
        c64 = faultinject.corrupt_array(
            faultinject.FaultSpec("optimizer.grads", "bitflip", at=0,
                                  payload={"index": 2, "bit": 18}), a64)
        assert c64.dtype == np.float64
        assert (c64 != a64).sum() == 1 and c64[2] != a64[2]
        np.testing.assert_array_equal(np.delete(c64, 2),
                                      np.delete(a64, 2))
        assert np.isfinite(c64).all()   # low bit: the silent variant
        with pytest.raises(ValueError):
            faultinject.corrupt_array(
                faultinject.FaultSpec("optimizer.grads", "exception"), a)


# ----------------------------------------------------- to_static guard
class TestToStaticGuard:
    def _train_fn(self, guard):
        model, opt = _build(guard=guard, fused=True)

        @P.jit.to_static(guard=guard)
        def train_step(X, y):
            opt.clear_grad()
            loss = ((model(X) - y) ** 2).mean()
            loss.backward()
            opt.step()
            return loss

        return model, opt, train_step

    def test_zero_extra_lifetime_compiles(self):
        # THE recompile-log proof: arming the guard adds no compile
        # events over a multi-step run — detection rides the one
        # compiled program
        counts = {}
        for guard in (False, True):
            _m, _o, step_fn = self._train_fn(guard)
            X, y = _batch(1)
            n0 = len(recompile_log().events())
            for _ in range(4):
                step_fn(X, y)
            counts[guard] = len(recompile_log().events()) - n0
        assert counts[True] == counts[False] == 1

    @pytest.mark.smoke
    def test_last_guard_probe(self):
        _m, opt, step_fn = self._train_fn(True)
        X, y = _batch(1)
        loss = step_fn(X, y)
        lg = step_fn.last_guard
        assert lg["loss"] == pytest.approx(float(loss.numpy()))
        assert lg["loss_finite"] is True
        assert opt.guard_summary().good

    def test_nan_input_flags_loss_probe(self):
        _m, opt, step_fn = self._train_fn(True)
        X, y = _batch(1)
        Xn = P.to_tensor(np.full((8, 6), np.nan, np.float32))
        step_fn(Xn, y)
        assert step_fn.last_guard["loss_finite"] is False
        assert not opt.guard_summary().good
        # same signature — the NaN batch costs no recompile either
        n0 = len(recompile_log().events())
        step_fn(X, y)
        assert len(recompile_log().events()) == n0

    def test_ambient_sentinel_receives_probe(self):
        sent = sentinel.install(sentinel.TrainingSentinel())
        try:
            _m, _o, step_fn = self._train_fn(True)
            X, y = _batch(1)
            step_fn(X, y)
            assert sent.last_probe is not None
            assert sent.last_probe["fn"] == "train_step"
        finally:
            sentinel.uninstall(sent)
        assert sentinel.current() is None


# ------------------------------------------------------ policy machine
class TestPolicyMachine:
    @pytest.mark.smoke
    def test_nan_loss_flagged_clean_pair(self):
        sent = sentinel.TrainingSentinel(auto_rollback=False)
        assert sent.observe(1, loss=0.5) is sentinel.SentinelAction.OK
        act = sent.observe(2, loss=float("nan"))
        assert act is sentinel.SentinelAction.SKIP
        assert sent.anomalies[-1].kind == "nan_loss"
        assert sent.anomalies[-1].step == 2

    @pytest.mark.smoke
    def test_nan_grad_summary_flagged_clean_pair(self):
        sent = sentinel.TrainingSentinel(auto_rollback=False)
        good = np.asarray([1.0, 2.0, 0.0, 4.0], np.float32)
        bad = np.asarray([0.0, np.nan, 1.0, 4.0], np.float32)
        assert sent.observe(1, loss=0.5, summary=good) is \
            sentinel.SentinelAction.OK
        assert sent.observe(2, loss=0.5, summary=bad) is \
            sentinel.SentinelAction.SKIP
        assert sent.anomalies[-1].kind == "nan_grad"
        assert sent.anomalies[-1].ctx["bad_regions"] == 1

    @pytest.mark.smoke
    def test_grad_norm_limit_flagged_clean_pair(self):
        sent = sentinel.TrainingSentinel(auto_rollback=False,
                                         grad_norm_limit=10.0)
        ok = np.asarray([1.0, 25.0, 0.0, 4.0], np.float32)    # norm 5
        hot = np.asarray([1.0, 40000.0, 0.0, 4.0], np.float32)  # 200
        assert sent.observe(1, summary=ok) is sentinel.SentinelAction.OK
        assert sent.observe(2, summary=hot) is \
            sentinel.SentinelAction.SKIP
        assert sent.anomalies[-1].kind == "grad_norm"

    @pytest.mark.smoke
    def test_loss_spike_flagged_clean_pair(self):
        sent = sentinel.TrainingSentinel(auto_rollback=False,
                                         spike_factor=3.0,
                                         spike_window=4)
        for i, v in enumerate((1.0, 0.9, 1.1, 0.95)):
            assert sent.observe(i, loss=v) is sentinel.SentinelAction.OK
        # gentle drift stays clean; a 10x excursion is a spike
        assert sent.observe(5, loss=1.3) is sentinel.SentinelAction.OK
        act = sent.observe(6, loss=10.0)
        assert act is sentinel.SentinelAction.SKIP
        assert sent.anomalies[-1].kind == "loss_spike"

    def test_streak_resets_on_clean_step(self):
        sent = sentinel.TrainingSentinel(auto_rollback=False,
                                         skip_limit=3)
        sent.observe(1, loss=float("nan"))
        sent.observe(2, loss=float("nan"))
        assert sent.skip_streak == 2
        sent.observe(3, loss=0.5)
        assert sent.skip_streak == 0

    def test_rollback_restores_and_cools_lr(self, tmp_path):
        model, opt = _build(guard=True)
        ck = R.Checkpointer(str(tmp_path), keep=2)
        sent = sentinel.TrainingSentinel(
            checkpointer=ck, model=model, optimizer=opt, skip_limit=2,
            lr_cooldown=0.5)
        _eager_step(model, opt, 1)
        ck.save_train_state(1, model, opt)
        sent.note_checkpoint(1)
        assert sent.last_good_step == 1
        w_ckpt = np.asarray(model.weight._value).copy()
        _eager_step(model, opt, 2)        # diverge from the checkpoint
        lr0 = opt.get_lr()
        bad = np.asarray([0.0, np.nan, 1.0, 2.0], np.float32)
        assert sent.observe(3, summary=bad) is \
            sentinel.SentinelAction.SKIP
        act = sent.observe(4, summary=bad)
        assert act is sentinel.SentinelAction.ROLLBACK
        assert sent.rollbacks == 1 and sent.resume_step == 2
        np.testing.assert_array_equal(np.asarray(model.weight._value),
                                      w_ckpt)
        assert opt.get_lr() == pytest.approx(lr0 * 0.5)
        assert sent.skip_streak == 0

    def test_rollback_anchors_last_good_not_newest(self, tmp_path):
        # the quickstart saves unconditionally every loop, so the
        # NEWEST entry can capture post-anomaly state (post-commit
        # kinds — loss_spike/grad_norm — commit before detection);
        # the rollback must restore the last_good_step anchor instead
        model, opt = _build(guard=True)
        ck = R.Checkpointer(str(tmp_path), keep=4)
        sent = sentinel.TrainingSentinel(
            checkpointer=ck, model=model, optimizer=opt, skip_limit=2)
        _eager_step(model, opt, 1)
        ck.save_train_state(1, model, opt)
        sent.note_checkpoint(1)
        w_good = np.asarray(model.weight._value).copy()
        bad = np.asarray([0.0, np.nan, 1.0, 2.0], np.float32)
        assert sent.observe(2, summary=bad) is \
            sentinel.SentinelAction.SKIP
        # per-loop save lands DURING the streak: newest entry now
        # holds diverged state (note_checkpoint mid-streak is ignored)
        _eager_step(model, opt, 2)
        ck.save_train_state(2, model, opt)
        sent.note_checkpoint(2)
        assert sent.last_good_step == 1
        act = sent.observe(3, summary=bad)
        assert act is sentinel.SentinelAction.ROLLBACK
        assert sent.resume_step == 2      # anchor step 1, resume at 2
        np.testing.assert_array_equal(
            np.asarray(model.weight._value), w_good)

    def test_no_restorable_checkpoint_stays_skip(self, tmp_path):
        # anomalies before any checkpoint ever landed: the sentinel
        # must not claim a rollback it could not perform (a ROLLBACK
        # with resume_step=None would crash the documented
        # `step = sent.resume_step` caller pattern)
        model, opt = _build(guard=True)
        ck = R.Checkpointer(str(tmp_path), keep=2)
        sent = sentinel.TrainingSentinel(
            checkpointer=ck, model=model, optimizer=opt, skip_limit=2)
        assert sent.observe(1, loss=float("nan")) is \
            sentinel.SentinelAction.SKIP
        assert sent.observe(2, loss=float("nan")) is \
            sentinel.SentinelAction.SKIP
        assert sent.rollbacks == 0 and sent.resume_step is None

    def test_anomalous_checkpoint_not_anchored(self):
        sent = sentinel.TrainingSentinel(auto_rollback=False)
        sent.observe(1, loss=float("nan"))
        sent.note_checkpoint(1)           # mid-streak: not trusted
        assert sent.last_good_step is None
        sent.observe(2, loss=0.5)
        sent.note_checkpoint(2)
        assert sent.last_good_step == 2

    def test_on_anomaly_callback_outside_lock(self):
        # a callback that re-enters observe() must not deadlock (the
        # PR 7 health-monitor lesson, applied here)
        sent = sentinel.TrainingSentinel(auto_rollback=False)
        seen = []

        def cb(evt):
            seen.append(evt.kind)
            sent.observe(99, loss=0.1)    # reentrant clean observe

        sent.on_anomaly = cb
        t = threading.Thread(
            target=lambda: sent.observe(1, loss=float("nan")))
        t.start()
        t.join(timeout=5)
        assert not t.is_alive(), "observe() deadlocked in on_anomaly"
        assert seen == ["nan_loss"]


# -------------------------------------------------------- localization
class TestLocalization:
    @pytest.mark.smoke
    def test_replay_bisect_unit(self):
        calls = []

        def pred(k):
            calls.append(k)
            return k >= 7

        assert sentinel.replay_bisect(pred, 1, 12) == 7
        assert len(calls) <= 1 + math.ceil(math.log2(12))
        assert sentinel.replay_bisect(lambda k: False, 1, 12) is None
        assert sentinel.replay_bisect(lambda k: True, 3, 3) == 3
        with pytest.raises(ValueError):
            sentinel.replay_bisect(pred, 5, 4)

    @pytest.mark.smoke
    def test_lineage_ring(self):
        lin = sentinel.BatchLineage(capacity=3)
        for s in range(5):
            lin.record(s, seed=s * 10, batch=f"b{s}")
        assert lin.steps() == [2, 3, 4]
        assert lin.get(3)["seed"] == 30
        assert lin.get(0) is None and len(lin) == 3
        with pytest.raises(ValueError):
            sentinel.BatchLineage(capacity=0)

    def test_poison_batch_localized_by_replay(self, tmp_path):
        POISON, LAST_GOOD, TOTAL = 7, 4, 10
        lineage = sentinel.BatchLineage()

        def batch(step):
            X, y = _batch(step)
            if step == POISON:
                Xv = np.asarray(X._value).copy()
                Xv[0, 0] = np.nan          # the poisoned microbatch
                X = P.to_tensor(Xv)
            return X, y

        model, opt = _build(guard=True)
        ck = R.Checkpointer(str(tmp_path), keep=2)
        flagged_at = None
        for step in range(1, TOTAL + 1):
            X, y = batch(step)
            lineage.record(step, seed=step, batch=(X, y))
            opt.clear_grad()
            loss = ((model(X) - y) ** 2).mean()
            loss.backward()
            opt.step()
            if not opt.guard_summary().good and flagged_at is None:
                flagged_at = step
            if step == LAST_GOOD:
                ck.save_train_state(step, model, opt)
        assert flagged_at == POISON    # detection itself is 1-step here

        replays = []

        def replay(upto):
            replays.append(upto)
            got = ck.load()
            assert got is not None and got[0] == LAST_GOOD
            model.set_state_dict(got[1]["model"])
            opt.set_state_dict(got[1]["optimizer"])
            tripped = False
            for s in range(LAST_GOOD + 1, upto + 1):
                X, y = lineage.get(s)["batch"]
                opt.clear_grad()
                loss = ((model(X) - y) ** 2).mean()
                loss.backward()
                opt.step()
                tripped = tripped or not opt.guard_summary().good
            return tripped

        found = sentinel.localize_poison(replay, LAST_GOOD, TOTAL)
        assert found == POISON
        assert len(replays) <= 1 + math.ceil(math.log2(TOTAL - LAST_GOOD))


# --------------------------------------------------------- digest vote
class TestDigestVote:
    @pytest.mark.smoke
    def test_tree_digest_deterministic_and_sensitive(self):
        t1 = {"w": np.arange(6.0).reshape(2, 3), "b": np.zeros(3)}
        t2 = {"b": np.zeros(3), "w": np.arange(6.0).reshape(2, 3)}
        assert sentinel.tree_digest(t1) == sentinel.tree_digest(t2)
        t3 = {"w": np.arange(6.0).reshape(2, 3), "b": np.ones(3)}
        assert sentinel.tree_digest(t1) != sentinel.tree_digest(t3)
        # dtype and shape are part of the identity
        assert sentinel.tree_digest(np.zeros(4, np.float32)) != \
            sentinel.tree_digest(np.zeros(4, np.float64))
        assert sentinel.tree_digest(np.zeros((2, 2))) != \
            sentinel.tree_digest(np.zeros(4))

    def _vote_world(self, values, monitor_rank=0):
        sentinel._reset_for_tests()
        kv = fleet.LocalKVClient()
        worlds = {r: fleet.WorldView([0, 1, 2], r) for r in range(3)}
        cfg = fleet.FleetConfig(collective_timeout_s=10.0,
                                kv_slice_s=0.05)
        mon = fleet.FleetMonitor(client=kv, config=cfg,
                                 world_fn=lambda: worlds[monitor_rank])
        results = {}

        def vote(r):
            results[r] = sentinel.digest_vote(
                values[r], step=1, site="params", client=kv,
                world_view=worlds[r], timeout_s=10.0,
                monitor=mon if r == monitor_rank else None)

        ts = [threading.Thread(target=vote, args=(r,))
              for r in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert len(results) == 3, "a voter hung"
        return results, mon

    def test_vote_names_dissenting_rank(self):
        w = np.arange(12.0).reshape(3, 4)
        bad = w.copy()
        bad[1, 1] += 1e-4                 # silent corruption: tiny, finite
        results, mon = self._vote_world({0: w, 1: bad, 2: w})
        for r, res in results.items():
            assert res.suspects == (1,), (r, res.to_dict())
            assert res.majority == sentinel.tree_digest(w)
        assert results[1].self_suspect and not results[0].self_suspect
        # the monitor-fed voter quarantined the suspect
        assert mon.quarantined_ranks() == [1]
        assert mon.states()[1] is fleet.RankState.SUSPECT

    def test_vote_unanimous(self):
        w = np.arange(8.0)
        results, mon = self._vote_world({r: w for r in range(3)})
        for res in results.values():
            assert res.agree and res.suspects == ()
        assert mon.quarantined_ranks() == []

    def test_single_rank_vote_trivially_agrees(self):
        wv = fleet.WorldView([0], 0)
        res = sentinel.digest_vote(np.zeros(3), step=5, world_view=wv)
        assert res.agree and res.majority == res.mine

    def test_two_member_tie_is_inconclusive_never_a_coin_flip(self):
        # a 1-1 split has no strict majority: naming a "suspect" would
        # quarantine whichever rank's digest sorts larger — refuse
        sentinel._reset_for_tests()
        kv = fleet.LocalKVClient()
        wv0, wv1 = (fleet.WorldView([0, 1], r) for r in (0, 1))
        mon = fleet.FleetMonitor(client=kv, world_fn=lambda: wv0)
        vals = {0: np.zeros(4), 1: np.ones(4)}
        out = {}

        def vote(r, view):
            out[r] = sentinel.digest_vote(
                vals[r], step=1, site="tie", client=kv,
                world_view=view, timeout_s=10.0,
                monitor=mon if r == 0 else None)

        ts = [threading.Thread(target=vote, args=(r, v))
              for r, v in ((0, wv0), (1, wv1))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert len(out) == 2
        for res in out.values():
            assert not res.conclusive
            assert res.majority is None and res.suspects == ()
            assert not res.agree and not res.self_suspect
        assert mon.quarantined_ranks() == []   # nobody quarantined

    def test_quarantine_sticky_until_cleared(self):
        # fresh heartbeats must NOT clear an externally quarantined
        # rank (its host is alive; its math is not trusted)
        kv = fleet.LocalKVClient()
        cfg = fleet.FleetConfig(collective_timeout_s=5.0,
                                kv_slice_s=0.05,
                                heartbeat_interval_s=0.05,
                                suspect_after_s=10.0,
                                dead_after_s=20.0)
        wv = fleet.WorldView([0, 1], 0)
        pubs = {r: fleet.HeartbeatPublisher(
            client=kv, rank=r, interval_s=0.05).start()
            for r in range(2)}
        mon = fleet.FleetMonitor(client=kv, config=cfg,
                                 world_fn=lambda: wv)
        try:
            states = mon.poll()
            assert states[1] is fleet.RankState.HEALTHY
            mon.mark_suspect(1, reason="digest vote params@3")
            import time as _t
            _t.sleep(0.12)                 # fresh beats arrive
            assert mon.poll()[1] is fleet.RankState.SUSPECT
            assert mon.suspect_ranks() == [1]
            mon.clear_suspect(1)
            assert mon.poll()[1] is fleet.RankState.HEALTHY
        finally:
            for p in pubs.values():
                p.stop()
            mon.stop()

    def test_vote_round_keys_reaped(self):
        # votes are lockstep collectives: round r's start proves every
        # round before r_prev consumed — each rank deletes its own old
        # keys, bounding coordinator growth to two live rounds
        sentinel._reset_for_tests()
        kv = fleet.LocalKVClient()
        wv0, wv1 = (fleet.WorldView([0, 1], r) for r in (0, 1))
        w = np.zeros(4)

        def round_(step):
            out = {}

            def vote(r, view):
                out[r] = sentinel.digest_vote(
                    w, step=step, site="g", client=kv, world_view=view,
                    timeout_s=10.0)

            ts = [threading.Thread(target=vote, args=(r, v))
                  for r, v in ((0, wv0), (1, wv1))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            assert len(out) == 2

        for step in (1, 2, 3, 4):
            round_(step)
        live = [k for k, _v in kv.key_value_dir_get_bytes(
            f"{wv0.namespace}/sentinel/vote/g/")]
        rounds = {k.rsplit("/", 2)[-2] for k in live}
        assert rounds == {"s3", "s4"}, sorted(live)


# -------------------------------------------------------- serving guard
class TestServingGuard:
    def _engine(self, guard, kv=None, limit=None, requeue=2):
        from paddle_tpu import serving
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        P.seed(0)
        mcfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=64, dropout=0.0,
                         attention_dropout=0.0)
        model = GPTForCausalLM(mcfg)
        return serving.LLMEngine(model, serving.EngineConfig(
            max_num_seqs=4, page_size=8, max_model_len=32,
            prefill_buckets=(8, 16), guard=guard, kv_cache_dtype=kv,
            guard_scale_limit=limit, guard_requeue_limit=requeue))

    def _serve(self, eng, plan=None):
        from paddle_tpu import serving
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
        sp = serving.SamplingParams(max_new_tokens=6, seed=7)
        try:
            if plan is not None:
                with R.FaultInjector(plan):
                    outs = eng.generate(prompts, sp)
            else:
                outs = eng.generate(prompts, sp)
            return ([o.output_token_ids for o in outs],
                    [o.finish_reason for o in outs],
                    eng.metrics.snapshot())
        finally:
            eng.shutdown()

    @pytest.mark.smoke
    def test_clean_guarded_serving_token_identical(self):
        toks0, _f, m0 = self._serve(self._engine(False))
        toks1, _f, m1 = self._serve(self._engine(True))
        assert toks0 == toks1
        assert m1["guard_anomalies"] == 0
        # still ONE decode program: the guard rides the same bound
        assert m1["compiles"]["count"] <= m1["compiles"]["bound"]

    @pytest.mark.chaos
    def test_injected_nan_logits_evicts_offender_token_identical(self):
        toks0, _f, _m = self._serve(self._engine(False))
        plan = R.FaultPlan([R.FaultSpec("serving.logits", "nan_grad",
                                        at=2)], name="logit-nan")
        toks1, fins, m = self._serve(self._engine(True), plan)
        # detection + evict-and-requeue recovered token-identically;
        # only the offender paid an eviction
        assert toks1 == toks0
        assert m["guard_anomalies"] == 1
        assert m["requests"]["evicted"] == 1
        assert fins == ["length", "length", "length"]
        assert m["compiles"]["count"] <= m["compiles"]["bound"]

    def test_injected_inf_bitflip_also_detected(self):
        plan = R.FaultPlan([R.FaultSpec("serving.logits", "bitflip",
                                        at=1)], name="logit-inf")
        toks, _fins, m = self._serve(self._engine(True), plan)
        assert m["guard_anomalies"] == 1

    def test_scale_overflow_flagged_vs_clean(self):
        # clean pair: int8 pools under the default (finite-only) check
        _t, fins, m = self._serve(self._engine(True, kv="int8"))
        assert m["guard_anomalies"] == 0 and set(fins) == {"length"}
        # flagged pair: an absurd limit makes every real page scale an
        # overflow — persistent, so requests finish with "anomaly"
        _t, fins, m = self._serve(
            self._engine(True, kv="int8", limit=1e-6))
        assert m["guard_anomalies"] > 0
        assert set(fins) == {"anomaly"}

    def test_requeue_limit_bounds_deterministic_poison(self):
        # a poison that replays identically must finish, not spin:
        # fault every decode step for one request
        plan = R.FaultPlan(
            [R.FaultSpec("serving.logits", "nan_grad", at=0, times=999,
                         payload={"request_id": "req-0"})],
            name="sticky-poison")
        toks, fins, m = self._serve(
            self._engine(True, requeue=1), plan)
        assert fins[0] == "anomaly"
        # the other requests finish normally
        assert fins[1] == "length" and fins[2] == "length"

    def test_guard_in_aot_fingerprint(self):
        from paddle_tpu.serving.aot_cache import engine_fingerprint
        from paddle_tpu import serving
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        P.seed(0)
        mcfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=64, dropout=0.0,
                         attention_dropout=0.0)
        model = GPTForCausalLM(mcfg)
        params = {k: t._value for k, t in model.state_dict().items()}
        fps = set()
        for guard in (False, True):
            cfg = serving.EngineConfig(max_num_seqs=4, page_size=8,
                                       max_model_len=32,
                                       prefill_buckets=(8,),
                                       guard=guard)
            fps.add(engine_fingerprint(mcfg, cfg, params,
                                       attention="xla"))
        assert len(fps) == 2   # guarded programs are their own family


# --------------------------------------------------- chaos acceptance
@pytest.mark.chaos
class TestChaosAcceptance:
    """THE PR 15 training proofs: an injected fault is detected within
    ONE step, the step skips (zero-update commit) or the policy rolls
    back, and — because fault-plan occurrence counters are spent during
    the faulted window — the rolled-back-and-resumed trajectory matches
    the fault-free run EXACTLY (weights and losses)."""

    CKPT_STEP, FAULT_STEP, TOTAL, SKIPS = 4, 7, 10, 2

    def _run(self, ckpt_dir, plan, grad_norm_limit=None):
        model, opt = _build(guard=True)
        ck = R.Checkpointer(str(ckpt_dir), keep=2)
        sent = sentinel.TrainingSentinel(
            checkpointer=ck, model=model, optimizer=opt,
            skip_limit=self.SKIPS, lr_cooldown=1.0,
            grad_norm_limit=grad_norm_limit)
        inj = R.FaultInjector(plan) if plan is not None else None
        if inj is not None:
            faultinject.install(inj)
        losses = {}
        try:
            step = 1
            while step <= self.TOTAL:
                loss = _eager_step(model, opt, step)
                act = sent.observe(step, loss=loss,
                                   summary=opt.guard_summary())
                if act is sentinel.SentinelAction.ROLLBACK:
                    step = sent.resume_step
                    continue
                if act is sentinel.SentinelAction.OK:
                    losses[step] = loss
                    if step == self.CKPT_STEP:
                        ck.save_train_state(step, model, opt)
                        sent.note_checkpoint(step)
                step += 1
        finally:
            if inj is not None:
                faultinject.uninstall(inj)
        return losses, np.asarray(model.weight._value).copy(), sent

    @pytest.mark.parametrize("kind,limit", [("nan_grad", None),
                                            ("bitflip", 1e3)])
    def test_detect_skip_rollback_matches_fault_free(self, tmp_path,
                                                     kind, limit):
        clean_losses, clean_w, _ = self._run(tmp_path / "a", None,
                                             grad_norm_limit=limit)
        plan = R.FaultPlan(
            [R.FaultSpec("optimizer.grads", kind,
                         at=self.FAULT_STEP - 1, times=self.SKIPS,
                         payload={"bit": 30})],
            seed=3, name=f"chaos-{kind}")
        fault_losses, fault_w, sent = self._run(tmp_path / "b", plan,
                                                grad_norm_limit=limit)
        # detection within ONE step of injection; a bit-30 flip lands
        # on either channel depending on the victim's exponent (huge-
        # finite -> grad_norm, exponent-saturated -> nan_grad) — both
        # are the same real hardware flip, both must detect
        assert sent.anomalies
        assert sent.anomalies[0].step == self.FAULT_STEP
        allowed = (("nan_grad",) if kind == "nan_grad"
                   else ("nan_grad", "grad_norm"))
        assert sent.anomalies[0].kind in allowed
        assert sent.skips_total == self.SKIPS
        assert sent.rollbacks == 1
        # the acceptance identity: resumed trajectory == fault-free
        assert fault_losses == clean_losses
        np.testing.assert_array_equal(fault_w, clean_w)
        # and nothing non-finite ever reached the weights
        assert np.isfinite(fault_w).all()

    def test_skip_only_transient_nan_stays_finite(self, tmp_path):
        # a single transient NaN below skip_limit: the in-trace gate
        # zero-commits it and training continues — no rollback at all
        plan = R.FaultPlan([R.FaultSpec("optimizer.grads", "nan_grad",
                                        at=2)], seed=1, name="one-nan")
        losses, w, sent = self._run(tmp_path, plan)
        assert sent.skips_total == 1 and sent.rollbacks == 0
        assert np.isfinite(w).all()
        assert all(np.isfinite(v) for v in losses.values())


# ----------------------------------------------------- gates & hygiene
class TestGates:
    def test_guard_overhead_under_two_percent(self):
        # the perfgate-pinned detection-cost contract, asserted from
        # tier-1 too (the gpt flagship trace pair, deterministic)
        import os
        import sys
        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        sys.path.insert(0, tools)
        try:
            import perfgate
            out = perfgate.target_sentinel()
        finally:
            sys.path.remove(tools)
        assert out["guard_bytes_overhead_pct"] < 2.0
        assert out["guard_bytes_per_step"] > 0

    def test_guard_summary_path_numlint_clean(self):
        # the probe's reductions are f32 (NL101-clean): arming the
        # guard on a bf16-residency step adds ZERO numlint findings
        from paddle_tpu import analysis
        import paddle_tpu.nn.functional as F

        def build(guard):
            P.seed(0)
            model = nn.Linear(8, 4)
            opt = P.optimizer.AdamW(learning_rate=0.01,
                                    parameters=model.parameters(),
                                    guard=guard)

            @P.jit.to_static(amp_policy="bf16", guard=guard)
            def step_fn(X, y):
                opt.clear_grad()
                loss = F.mse_loss(model(X), y)
                loss.backward()
                opt.step()
                return loss

            return step_fn

        counts = {}
        for guard in (False, True):
            fn = build(guard)
            X, y = _batch(1, din=8, dout=4)
            jaxpr, infos = fn.traced_program(X, y)
            findings = analysis.check_numerics(jaxpr, where="<guard>",
                                               inputs=infos)
            counts[guard] = len(findings)
        assert counts[True] == counts[False]
