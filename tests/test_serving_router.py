"""paddle_tpu.serving.router — multi-replica routing, the AOT program
cache, failover semantics, and the tp-sharding groundwork.

Acceptance contracts pinned here (ISSUE 11):

- a 3-replica router run over mixed prefill/decode traffic is
  token-identical to the sequential single-engine run, INCLUDING across
  a forced DRAINING-replica failover;
- a second engine boot from the AOT program cache registers ZERO new
  compile events in the observability recompile log;
- a mid-decode replica crash evicts-and-requeues through the router
  with no data loss (and still token-identical output);
- ``EngineConfig(mesh=...)`` shards weights and the paged KV pools
  along the head axis over the virtual CPU mesh, audited by shardlint
  through ``audit_programs()``.
"""
import os
import shutil
import tempfile
import time

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu import observability as obs
from paddle_tpu import resilience as R
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.serving.router import (AOTProgramCache, ReplicaState,
                                       Router, RouterConfig,
                                       engine_fingerprint)

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def tiny_model():
    P.seed(0)
    return GPTForCausalLM(gpt3_tiny())


@pytest.fixture(scope="module")
def cache_dir():
    d = tempfile.mkdtemp(prefix="ptpu_aot_cache_")
    yield d
    shutil.rmtree(d, ignore_errors=True)


def _cfg(**kw):
    d = dict(max_num_seqs=4, page_size=4, max_model_len=48,
             prefill_buckets=(8, 16, 32))
    d.update(kw)
    return serving.EngineConfig(**d)


def _rcfg(**kw):
    d = dict(sleep=lambda s: None)   # in-process: stepping IS the wait
    d.update(kw)
    return RouterConfig(**d)


def _traffic(n=9, seed=42):
    """Mixed prefill/decode trace: varied prompt lengths, mixed greedy
    and stochastic sampling, one seed per request."""
    rng = np.random.default_rng(seed)
    lens = [3, 7, 12, 5, 17, 2, 9, 4, 11, 6, 14, 8][:n]
    prompts = [list(rng.integers(1, 256, ln)) for ln in lens]
    sps = [serving.SamplingParams(
        max_new_tokens=6, temperature=0.7 if i % 2 else 0.0,
        top_k=20 if i % 3 else 0, seed=i) for i in range(n)]
    return prompts, sps


def _sequential_reference(model, ecfg, prompts, sps, cache=None):
    eng = serving.LLMEngine(model, ecfg, program_cache=cache)
    out = []
    for p, sp in zip(prompts, sps):
        (one,) = eng.generate([p], [sp])
        out.append(one.output_token_ids)
    eng.shutdown()
    return out


# ---------------------------------------------------- AOT program cache
class TestAOTProgramCache:
    def test_warm_boot_registers_zero_compile_events(self, tiny_model,
                                                     cache_dir):
        """Acceptance: boot #1 compiles + persists; boot #2 loads every
        program from the cache and the recompile log records NOTHING —
        with token-identical generations from both engines."""
        cache = AOTProgramCache(cache_dir)
        e1 = serving.LLMEngine(tiny_model, _cfg(), program_cache=cache)
        w1 = e1.warmup()
        assert w1["programs"] == e1.config.compile_bound
        prompts, sps = _traffic(4)
        r1 = e1.generate(prompts, sps)
        e1.shutdown()

        events_before = obs.recompile_log().count
        t0 = time.perf_counter()
        e2 = serving.LLMEngine(tiny_model, _cfg(), program_cache=cache)
        w2 = e2.warmup()
        warm_ms = (time.perf_counter() - t0) * 1e3
        assert obs.recompile_log().count == events_before, \
            "warm boot must register ZERO new compile events"
        assert w2["compiled"] == 0
        assert w2["cache_loads"] == e2.config.compile_bound
        assert e2.metrics.compile_count == 0
        r2 = e2.generate(prompts, sps)
        assert [r.output_token_ids for r in r2] == \
            [r.output_token_ids for r in r1]
        # generating from cached programs still compiles nothing
        assert obs.recompile_log().count == events_before
        e2.shutdown()
        # the speedup is the point; cold pays len(buckets)+3 XLA
        # compiles, warm pays deserialization only
        assert warm_ms < w1["boot_ms"], \
            f"warm boot {warm_ms:.0f}ms not faster than cold " \
            f"{w1['boot_ms']:.0f}ms"

    def test_fingerprint_invalidation_on_config_change(self, tiny_model,
                                                       cache_dir):
        """The cache key covers engine geometry: a different page_size
        fingerprints differently, so stale programs are structurally
        unreachable (never loaded, only orphaned)."""
        e1 = serving.LLMEngine(tiny_model, _cfg(),
                               program_cache=cache_dir)
        e2 = serving.LLMEngine(tiny_model, _cfg(page_size=8),
                               program_cache=cache_dir)
        assert e1.program_fingerprint != e2.program_fingerprint
        fp1 = engine_fingerprint(tiny_model.config, _cfg(),
                                 e1._params, None,
                                 attention=e1.attention_path)
        assert fp1 == e1.program_fingerprint
        e1.shutdown()
        e2.shutdown()

    def test_sampler_revision_is_part_of_the_fingerprint(
            self, tiny_model, monkeypatch):
        """Two trees that differ only in what the sampler computes never
        share a stored `sample/<width>`: the second engine compiles its
        own and leaves the first one's entry where it was."""
        from paddle_tpu.serving import sampler
        d = tempfile.mkdtemp(prefix="ptpu_aot_sampler_")
        try:
            cache = AOTProgramCache(d)
            e1 = serving.LLMEngine(tiny_model, _cfg(), program_cache=cache)
            e1._get_sampler(1)
            e1._get_sampler(4)
            monkeypatch.setattr(sampler, "SAMPLER_REVISION",
                                sampler.SAMPLER_REVISION + 1)
            e2 = serving.LLMEngine(tiny_model, _cfg(), program_cache=cache)
            assert e2.program_fingerprint != e1.program_fingerprint
            assert not cache.entries(e2.program_fingerprint)
            e2._get_sampler(1)
            e2._get_sampler(4)
            assert e2.metrics.aot_cache_loads == 0
            assert e2.metrics.compile_count == 2
            for fp in (e1.program_fingerprint, e2.program_fingerprint):
                assert sorted(cache.entries(fp)) == ["sample_1", "sample_4"]
            e1.shutdown()
            e2.shutdown()
        finally:
            shutil.rmtree(d, ignore_errors=True)

    def test_grouped_products_are_part_of_an_expert_engines_fingerprint(
            self, tiny_model, monkeypatch, tmp_path):
        """An engine whose model has expert layers names what its grouped
        products are built from (``ragged_dot`` here; the kernel at its
        revision where a kernel may run): two trees that differ in the
        kernel's revision, or in the path, never share an executable.  A
        model without experts adds no term: its fingerprint is what it
        was."""
        from paddle_tpu import ops
        from paddle_tpu.distributed import moe
        from paddle_tpu.ops.pallas import grouped_matmul as gm
        from tests.test_sdar_moe_model import build, tiny_weights
        cfg = _cfg()
        cache = AOTProgramCache(str(tmp_path))
        sdar = serving.LLMEngine(build(tiny_weights()), cfg,
                                 program_cache=cache)
        gpt = serving.LLMEngine(tiny_model, cfg, program_cache=cache)
        assert sdar.experts_path == "ragged_dot" and gpt.experts_path is None

        def fp(engine, experts):
            return engine_fingerprint(
                engine._model.config, cfg, engine._params, None,
                attention=engine.attention_path, experts=experts)

        assert fp(gpt, None) == gpt.program_fingerprint == engine_fingerprint(
            tiny_model.config, cfg, gpt._params, None,
            attention="xla+next_token/1")
        assert fp(sdar, "ragged_dot") == sdar.program_fingerprint
        assert fp(sdar, None) != sdar.program_fingerprint
        monkeypatch.setattr(ops.pallas, "kernel_default", lambda: True)
        kernel = moe.experts_path()
        assert kernel == f"grouped_matmul/{gm.GROUPED_MATMUL_REVISION}"
        monkeypatch.setattr(gm, "GROUPED_MATMUL_REVISION",
                            gm.GROUPED_MATMUL_REVISION + 1)
        bumped = moe.experts_path()
        assert len({fp(sdar, "ragged_dot"), fp(sdar, kernel),
                    fp(sdar, bumped)}) == 3
        sdar.shutdown()
        gpt.shutdown()

    def test_corrupt_entry_degrades_to_compile(self, tiny_model):
        """A torn cache entry is a miss, not a crash: the engine
        recompiles and REPLACES the bad file."""
        d = tempfile.mkdtemp(prefix="ptpu_aot_corrupt_")
        try:
            cache = AOTProgramCache(d)
            e1 = serving.LLMEngine(tiny_model, _cfg(),
                                   program_cache=cache)
            e1._get_decode()
            e1.shutdown()
            fp = e1.program_fingerprint
            (entry,) = [p for p in cache.entries(fp) if p == "decode"]
            path = cache._entry_path(fp, entry)
            with open(path, "wb") as fh:
                fh.write(b"torn")
            e2 = serving.LLMEngine(tiny_model, _cfg(),
                                   program_cache=cache)
            e2._get_decode()                 # recompile, not a crash
            assert e2.metrics.compile_count == 1
            assert cache.error_count >= 1
            # the replacement entry is loadable again
            e3 = serving.LLMEngine(tiny_model, _cfg(),
                                   program_cache=cache)
            e3._get_decode()
            assert e3.metrics.compile_count == 0
            assert e3.metrics.aot_cache_loads == 1
            e2.shutdown()
            e3.shutdown()
        finally:
            shutil.rmtree(d, ignore_errors=True)

    def test_evict_stale_keeps_current_fingerprint(self, tiny_model):
        d = tempfile.mkdtemp(prefix="ptpu_aot_evict_")
        try:
            cache = AOTProgramCache(d)
            e1 = serving.LLMEngine(tiny_model, _cfg(),
                                   program_cache=cache)
            e2 = serving.LLMEngine(tiny_model, _cfg(page_size=8),
                                   program_cache=cache)
            e1._get_decode()
            e2._get_decode()
            evicted = cache.evict_stale(e1.program_fingerprint)
            assert evicted == [e2.program_fingerprint]
            assert cache.entries(e1.program_fingerprint)
            assert not cache.entries(e2.program_fingerprint)
            e1.shutdown()
            e2.shutdown()
        finally:
            shutil.rmtree(d, ignore_errors=True)


# ------------------------------------------------------------- routing
class TestRouter:
    def test_three_replica_token_identity_with_forced_drain(
            self, tiny_model, cache_dir):
        """Acceptance: 3 replicas under the mixed trace — with a forced
        mid-run drain (migrating queued work) and an elastic respawn —
        produce tokens identical to the sequential single-engine run."""
        prompts, sps = _traffic(9)
        ref = _sequential_reference(tiny_model, _cfg(), prompts, sps,
                                    cache=AOTProgramCache(cache_dir))

        router = Router(tiny_model, _cfg(), num_replicas=3,
                        config=_rcfg(), program_cache=cache_dir)
        # with the cache warmed by earlier boots, every replica boots
        # warm: zero compiles anywhere in the fleet
        assert all(h.boot_info["warm"] for h in router.replicas)
        rids = [router.add_request(p, sp)
                for p, sp in zip(prompts[:6], sps[:6])]
        for _ in range(2):
            router.step()
        drained = router.drain(0)            # forced DRAINING failover
        assert drained.state is ReplicaState.DRAINING
        rids += [router.add_request(p, sp)
                 for p, sp in zip(prompts[6:], sps[6:])]
        rounds = 0
        while router.has_unfinished():
            router.step()
            rounds += 1
            assert rounds < 500, "router failed to converge"
        outs = [router.finished_results[r].output_token_ids
                for r in rids]
        assert outs == ref, "routed run diverged from single-engine run"
        snap = router.snapshot()
        assert snap["drains"] == 1
        assert snap["respawns"] >= 1         # elastic: drained → respawned
        assert snap["requests"]["finished"] == len(prompts)
        # admissions actually spread over the fleet
        replicas_used = {router.finished_results[r].replica
                        for r in rids}
        assert len(replicas_used) >= 2
        router.shutdown()

    def test_draining_replica_spills_to_healthy_replica(
            self, tiny_model, cache_dir):
        """Satellite: a replica whose ENGINE health machine is DRAINING
        answers admissions with AdmissionRejected; the router routes /
        spills to a healthy replica and output stays token-identical to
        the single-engine run."""
        prompts, sps = _traffic(4)
        ref = _sequential_reference(tiny_model, _cfg(), prompts, sps,
                                    cache=AOTProgramCache(cache_dir))
        from paddle_tpu.serving.engine import LLMEngine

        def factory(index):
            if index == 0:
                # hair-trigger health over a small pool: one request's
                # pages (1/12 ≈ 8%) already exceed drain_at → DRAINING
                cfg = _cfg(num_pages=13,
                           health_degraded_at=0.02,
                           health_drain_at=0.05,
                           health_recover_at=0.01)
            else:
                cfg = _cfg()
            return LLMEngine(tiny_model, cfg,
                             program_cache=AOTProgramCache(cache_dir))

        router = Router(engine_factory=factory, num_replicas=2,
                        config=_rcfg())
        # request 0 lands on replica 0 (empty fleet, index tie-break);
        # one step in, replica 0's occupancy trips its health machine
        r0 = router.add_request(prompts[0], sps[0])
        router.step()
        eng0 = router.replicas[0].engine
        assert not eng0.health.admitting          # engine-level DRAINING
        with pytest.raises(serving.AdmissionRejected):
            eng0.add_request(prompts[1], sps[1])  # the rejection itself
        # the router spills the same admission to the healthy replica
        rids = [r0] + [router.add_request(p, sp)
                       for p, sp in zip(prompts[1:], sps[1:])]
        while router.has_unfinished():
            router.step()
        outs = [router.finished_results[r].output_token_ids
                for r in rids]
        assert outs == ref
        for r in rids[1:]:
            assert router.finished_results[r].replica == 1
        router.shutdown()

    def test_mid_decode_crash_evicts_and_requeues_without_data_loss(
            self, tiny_model, cache_dir):
        """Satellite: a fatal mid-decode fault (crash_safe_decode off)
        kills a replica; the router adopts every in-flight request onto
        the survivor — generated tokens intact, continuation replayed
        token-identically — and respawns the dead replica warm."""
        prompts, sps = _traffic(6)
        ecfg = _cfg(crash_safe_decode=False)
        ref = _sequential_reference(tiny_model, ecfg, prompts, sps,
                                    cache=AOTProgramCache(cache_dir))
        router = Router(tiny_model, ecfg, num_replicas=2,
                        config=_rcfg(), program_cache=cache_dir)
        plan = R.FaultPlan(
            [R.FaultSpec("serving.decode", "exception", at=2)],
            name="router-crash")
        with R.FaultInjector(plan):
            res = router.generate(prompts, sps)
        assert [r.output_token_ids for r in res] == ref, \
            "tokens diverged across the crash"
        assert router.metrics.failovers == 1
        assert router.metrics.adoptions >= 1      # migrated, not dropped
        assert router.metrics.respawns == 1
        assert any(r.migrations > 0 for r in res)
        assert all(r.finish_reason in ("length", "stop") for r in res)
        router.shutdown()

    def test_queue_full_spillover_and_fleet_backpressure(
            self, tiny_model, cache_dir):
        """Engine AdmissionRejected(queue_full) spills to the next
        replica; when the WHOLE fleet refuses, generate() retries under
        the RetryPolicy (stepping between attempts) instead of losing
        the request."""
        prompts, sps = _traffic(8)
        ecfg = _cfg(max_num_seqs=1, max_queue_depth=1)
        ref = _sequential_reference(tiny_model, ecfg, prompts, sps,
                                    cache=AOTProgramCache(cache_dir))
        router = Router(tiny_model, ecfg, num_replicas=2,
                        config=_rcfg(), program_cache=cache_dir)
        res = router.generate(prompts, sps)
        assert [r.output_token_ids for r in res] == ref
        assert router.metrics.spillovers >= 1
        router.shutdown()

    def test_background_loop_serves_admissions(self, tiny_model,
                                               cache_dir):
        """The daemon step loop drives the fleet: admissions from the
        caller thread finish without the caller ever stepping."""
        prompts, sps = _traffic(4)
        router = Router(tiny_model, _cfg(), num_replicas=2,
                        config=_rcfg(), program_cache=cache_dir)
        got = []
        router.start(interval_s=0.001)
        try:
            rids = [router.add_request(
                p, sp, stream=lambda rid, t, fin: got.append(
                    (rid, t, fin)))
                for p, sp in zip(prompts, sps)]
            deadline = time.time() + 60.0
            while time.time() < deadline:
                with router._lock:
                    if all(r in router.finished_results for r in rids):
                        break
                time.sleep(0.01)
            else:
                pytest.fail("background loop did not finish the traffic")
        finally:
            router.stop()
        assert all(len(router.finished_results[r].output_token_ids) == 6
                   for r in rids)
        assert any(fin for _, _, fin in got)
        router.shutdown()

    def test_generate_batch_larger_than_retention(self, tiny_model,
                                                  cache_dir):
        """A generate() batch bigger than finished_retention must
        return EVERY result: the retention sweep may not evict results
        the in-flight call still holds a claim on."""
        prompts, sps = _traffic(6)
        router = Router(tiny_model, _cfg(), num_replicas=2,
                        config=_rcfg(finished_retention=2),
                        program_cache=cache_dir)
        res = router.generate(prompts, sps)
        assert len(res) == 6
        assert all(len(r.output_token_ids) == 6 for r in res)
        # claims released afterwards: retention applies again
        assert len(router.finished_results) <= 2
        router.shutdown()

    @pytest.mark.smoke
    def test_router_smoke(self, tiny_model, cache_dir):
        """Smoke tier: boot 2 replicas (warm when the cache is
        populated), serve a tiny trace, verify the metrics source."""
        prompts, sps = _traffic(3)
        router = Router(tiny_model, _cfg(), num_replicas=2,
                        config=_rcfg(),
                        program_cache=cache_dir,
                        metrics_name="serving.router.pytest")
        res = router.generate(prompts, sps)
        assert [len(r.output_token_ids) for r in res] == [6, 6, 6]
        from paddle_tpu import profiler
        rep = profiler.metrics_report()
        assert "serving.router.pytest" in rep
        assert rep["serving.router.pytest"]["requests"]["finished"] == 3
        router.shutdown()
        assert "serving.router.pytest" not in profiler.metrics_report()


# --------------------------------------------------- tp-mesh groundwork
class TestMeshGroundwork:
    def test_tp_sharded_engine_token_identical_and_audited(
            self, tiny_model):
        """EngineConfig(mesh={'tp': 2}) shards the paged KV pools along
        the head axis (and weights along their trailing hidden axis)
        over the virtual CPU mesh; generation matches the unsharded
        engine and the shardlint self-audit stays inside budget."""
        import jax
        from jax.sharding import NamedSharding
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 (virtual) devices")
        prompts, sps = _traffic(4)
        plain = serving.LLMEngine(tiny_model, _cfg())
        ref = plain.generate(prompts, sps)
        plain.shutdown()

        eng = serving.LLMEngine(tiny_model, _cfg(mesh={"tp": 2}))
        for pool in (eng._k_pools[0], eng._v_pools[0]):
            assert isinstance(pool.sharding, NamedSharding)
            assert pool.sharding.spec[1] == "tp"    # the head axis
        res = eng.generate(prompts, sps)
        assert [r.output_token_ids for r in res] == \
            [r.output_token_ids for r in ref]
        # shardlint self-audit over the SAME traced programs
        audit = eng.audit()
        assert audit["compiles_used"] <= audit["compile_bound"]
        assert all(p["within_budget"]
                   for p in audit["programs"].values())
        eng.shutdown()

    def test_mesh_head_divisibility_validated(self, tiny_model):
        with pytest.raises(ValueError, match="num_heads"):
            serving.LLMEngine(tiny_model, _cfg(mesh={"tp": 3}))

    def test_sharded_engine_in_router(self, tiny_model, cache_dir):
        """Mesh plumbing end to end: a router whose factory builds
        tp-sharded engines serves the trace token-identically."""
        import jax
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 (virtual) devices")
        prompts, sps = _traffic(3)
        ref = _sequential_reference(tiny_model, _cfg(), prompts, sps,
                                    cache=AOTProgramCache(cache_dir))
        from paddle_tpu.serving.engine import LLMEngine

        def factory(index):
            return LLMEngine(tiny_model, _cfg(mesh={"tp": 2}))

        router = Router(engine_factory=factory, num_replicas=2,
                        config=_rcfg(warm_boot=False))
        res = router.generate(prompts, sps)
        assert [r.output_token_ids for r in res] == ref
        router.shutdown()


# ------------------------------------------------------ adoption hooks
class TestAdoptionHooks:
    def test_adopt_request_replays_token_identically(self, tiny_model,
                                                     cache_dir):
        """The engine hook itself: adopting (prompt, generated-so-far)
        onto a fresh engine regenerates exactly the continuation the
        origin engine would have produced."""
        cache = AOTProgramCache(cache_dir)
        sp = serving.SamplingParams(max_new_tokens=8, temperature=0.9,
                                    seed=7)
        prompt = [5, 9, 2, 14]
        eng = serving.LLMEngine(tiny_model, _cfg(), program_cache=cache)
        (full,) = eng.generate([prompt], [sp])
        eng.shutdown()

        origin = serving.LLMEngine(tiny_model, _cfg(),
                                   program_cache=cache)
        origin.add_request(prompt, sp)
        events = []
        for _ in range(3):                  # prefill + 2 decode tokens
            events += origin.step()
        partial = [t for _, t, _ in events if t is not None]
        assert full.output_token_ids[:len(partial)] == partial
        origin.shutdown()

        target = serving.LLMEngine(tiny_model, _cfg(),
                                   program_cache=cache)
        streamed = []
        target.adopt_request(prompt, sp, generated_token_ids=partial,
                             stream=lambda r, t, fin: streamed.append(t))
        while target.has_unfinished():
            target.step()
        (req,) = target.finished_requests.values()
        assert req.output_token_ids == full.output_token_ids
        assert target.metrics.requests_adopted == 1
        # already-delivered tokens are never re-streamed
        assert streamed[:-1] == full.output_token_ids[len(partial):] \
            or streamed == full.output_token_ids[len(partial):]
        target.shutdown()

    def test_adopt_finished_request_rejected(self, tiny_model):
        eng = serving.LLMEngine(tiny_model, _cfg())
        sp = serving.SamplingParams(max_new_tokens=2)
        with pytest.raises(ValueError, match="already finished"):
            eng.adopt_request([1, 2, 3], sp, generated_token_ids=[4, 5])
        eng.shutdown()

    def test_release_waiting_hands_over_queued_requests(self,
                                                       tiny_model):
        eng = serving.LLMEngine(tiny_model, _cfg(max_num_seqs=1))
        sp = serving.SamplingParams(max_new_tokens=2)
        for i in range(3):
            eng.add_request([1 + i, 2, 3], sp)
        eng.step()                           # admits exactly one
        handed = eng.release_waiting()
        assert [r.request_id for r in handed] == ["req-1", "req-2"]
        assert eng.scheduler.queue_depth == 0
        while eng.has_unfinished():          # the running one finishes
            eng.step()
        assert eng.metrics.requests_finished == 1
        eng.shutdown()
