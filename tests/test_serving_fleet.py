"""paddle_tpu.serving.fleet — cross-process replica failover, the
KV-RPC wire, the page-state handoff, and disaggregated prefill/decode.

Acceptance contracts pinned here (ISSUE 16):

- the wire protocol is ordered and exactly-once by construction
  (consumed keys deleted; typed errors re-raise on the controller);
- ``export_page_state`` / ``import_page_state`` move a mid-decode
  request between engines token-identically, inside the bounded-compile
  contract (eager scatters: ZERO new recompile-log events), and carry
  the stream watermark so handed-off requests never re-stream;
- the stock Router drives :class:`RemoteEngineClient` proxies through
  mid-stream failover with exactly-once delivery (every stream sees
  each token once and exactly one fin);
- adoption across the process boundary ships deadline AGE, never an
  absolute clock reading — a ``deadline_s`` TTL keeps counting from
  FIRST arrival and never restarts per migration (the satellite-2
  regression);
- a wedged replica (parked step loop, silent heartbeats) draws a
  bounded-time watchdog DEAD verdict, its work migrates with zero
  token loss, and the respawn lands on a SPARE rank booting WARM from
  the shared AOT program cache.

The real 3-process SIGKILL + SIGSTOP proof lives in
tests/test_distributed_multiprocess.py; these tests pin the same
machinery in-process (rank-per-thread over ``LocalKVClient``).
"""
import shutil
import tempfile
import threading
import time

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu import observability as obs
from paddle_tpu import resilience as R
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.resilience import fleet
from paddle_tpu.resilience.faultinject import KINDS, fire
from paddle_tpu.serving.fleet import (DisaggregatedEngine,
                                      FleetServingConfig,
                                      RemoteEngineClient, ReplicaServer,
                                      RemoteReplicaError, ServingFleet,
                                      wire)
from paddle_tpu.serving.router import RouterConfig
from paddle_tpu.serving.scheduler import AdmissionRejected

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def tiny_model():
    P.seed(0)
    return GPTForCausalLM(gpt3_tiny())


@pytest.fixture(scope="module")
def warm_cache(tiny_model):
    """Shared AOT cache, prewarmed ONCE: in-process replica boots then
    load instead of compile — which keeps inline heartbeats flowing
    (a cold multi-second compile inside a boot dispatch would read as
    rank silence to the watchdog) and makes every respawn warm."""
    d = tempfile.mkdtemp(prefix="ptpu_fleet_cache_")
    e = serving.LLMEngine(tiny_model, _cfg(), program_cache=d)
    e.warmup()
    e.shutdown()
    yield d
    shutil.rmtree(d, ignore_errors=True)


def _cfg(**kw):
    d = dict(max_num_seqs=4, page_size=4, max_model_len=48,
             prefill_buckets=(8, 16, 32))
    d.update(kw)
    return serving.EngineConfig(**d)


def _fc(**kw):
    d = dict(collective_timeout_s=8.0, kv_slice_s=0.05,
             heartbeat_interval_s=0.3, suspect_after_s=1.2,
             dead_after_s=2.4, rendezvous_timeout_s=30.0)
    d.update(kw)
    return fleet.FleetConfig(**d)


def _traffic(n=8, seed=7, max_new=6, deadline_s=None):
    rng = np.random.default_rng(seed)
    lens = [3, 7, 12, 5, 17, 2, 9, 4, 11, 6][:n]
    prompts = [list(rng.integers(1, 256, ln)) for ln in lens]
    sps = [serving.SamplingParams(
        max_new_tokens=max_new, temperature=0.7 if i % 2 else 0.0,
        top_k=20 if i % 3 else 0, seed=i, deadline_s=deadline_s)
        for i in range(n)]
    return prompts, sps


def _reference(model, ecfg, prompts, sps, cache=None):
    eng = serving.LLMEngine(model, ecfg, program_cache=cache)
    out = [r.output_token_ids for r in eng.generate(prompts, sps)]
    eng.shutdown()
    return out


class _Cluster:
    """Rank-per-thread replica fleet over one LocalKVClient: each rank
    runs a real :class:`ReplicaServer` serve loop on a daemon thread,
    beating inline (so a parked loop goes heartbeat-silent, exactly
    like a SIGSTOPped process)."""

    def __init__(self, model, ranks, spares=(), cache=None, ecfg=None):
        self.kv = fleet.LocalKVClient()
        self.fc = _fc()
        self.ranks = list(ranks) + list(spares)
        self.servers = {}
        self.threads = {}
        for r in self.ranks:
            def factory(payload, r=r):
                return serving.LLMEngine(
                    model, ecfg or _cfg(), program_cache=cache,
                    metrics_name=f"serving.fleet.r{r}")
            cell = {}
            pub = fleet.HeartbeatPublisher(
                client=self.kv, rank=r,
                interval_s=self.fc.heartbeat_interval_s,
                payload_fn=lambda cell=cell: cell["srv"].telemetry())
            srv = ReplicaServer(self.kv, r, factory, config=self.fc,
                                publisher=pub, inline_beats=True)
            cell["srv"] = srv
            self.servers[r] = srv
            t = threading.Thread(target=srv.serve, daemon=True,
                                 name=f"test-fleet-replica-{r}")
            self.threads[r] = t
            t.start()
        self.monitor = fleet.FleetMonitor(
            client=self.kv, config=self.fc,
            world_fn=lambda: fleet.WorldView(self.ranks, self.ranks[0]))

    def proxy(self, rank, boot=True, abort_if=None):
        p = RemoteEngineClient(self.kv, rank,
                               namespace_fn=fleet.coord_namespace,
                               config=self.fc, abort_if=abort_if)
        if boot:
            p.call("boot", {}, timeout_s=self.fc.rendezvous_timeout_s)
        return p

    def serving_fleet(self, active, spares=()):
        return ServingFleet(
            self.kv,
            FleetServingConfig(active, spares, fleet_config=self.fc),
            router_config=RouterConfig(sleep=lambda s: None),
            monitor=self.monitor)

    def close(self):
        for srv in self.servers.values():
            srv.stop()
        for t in self.threads.values():
            t.join(timeout=5.0)
        try:
            self.monitor.stop()
        except Exception:
            pass


def _collector():
    rec = {"tokens": [], "fins": 0}

    def _stream(rid, tok, fin):
        if tok is not None:
            rec["tokens"].append(int(tok))
        if fin:
            rec["fins"] += 1

    return rec, _stream


# ------------------------------------------------------------- wire
class TestWire:
    def test_rpc_lane_roundtrip_deletes_consumed_keys(self):
        kv = fleet.LocalKVClient()
        ns = "test/ns"
        wire.post_request(kv, ns, 3, 0, "ping", {"x": 1})
        m, p, ctx = wire.read_request(kv, ns, 3, 0, 1.0)
        assert (m, p) == ("ping", {"x": 1})
        assert ctx is None      # no ambient trace -> bare envelope
        assert kv.key_value_dir_get_bytes(wire.req_key(ns, 3, 0)) == []
        wire.post_response(kv, ns, 3, 0, result={"rank": 3})
        assert wire.await_response(kv, ns, 3, 0, 1.0) == {"rank": 3}
        assert kv.key_value_dir_get_bytes(wire.rsp_key(ns, 3, 0)) == []

    def test_trace_context_rides_the_envelope(self):
        from paddle_tpu.observability import TraceContext, use_context
        kv = fleet.LocalKVClient()
        ns = "test/ns"
        tc = TraceContext("rr-7-abc", parent_span_id="1a.2")
        with use_context(tc):
            wire.post_request(kv, ns, 1, 0, "step", {})
        m, p, ctx = wire.read_request(kv, ns, 1, 0, 1.0)
        assert m == "step"
        assert ctx.trace_id == "rr-7-abc"
        assert ctx.parent_span_id == "1a.2"

    def test_typed_errors_reraise_on_controller(self):
        kv = fleet.LocalKVClient()
        ns = "test/ns"
        wire.post_response(kv, ns, 0, 0,
                           error=AdmissionRejected("no_slot", "full"))
        with pytest.raises(AdmissionRejected) as ei:
            wire.await_response(kv, ns, 0, 0, 1.0)
        assert ei.value.reason == "no_slot"
        wire.post_response(kv, ns, 0, 1, error=ValueError("bad geom"))
        with pytest.raises(ValueError, match="bad geom"):
            wire.await_response(kv, ns, 0, 1, 1.0)
        wire.post_response(kv, ns, 0, 2, error=RuntimeError("boom"))
        with pytest.raises(RemoteReplicaError, match="RuntimeError"):
            wire.await_response(kv, ns, 0, 2, 1.0)

    def test_sampling_params_roundtrip(self):
        sp = serving.SamplingParams(max_new_tokens=9, temperature=0.5,
                                    top_k=11, top_p=0.9, seed=4,
                                    deadline_s=2.5)
        back = wire.sp_from_dict(wire.sp_to_dict(sp))
        assert (back.max_new_tokens, back.temperature, back.top_k,
                back.top_p, back.seed, back.deadline_s) == \
            (9, 0.5, 11, 0.9, 4, 2.5)
        assert wire.sp_from_dict(wire.sp_to_dict(None)) is None

    def test_pack_unpack_state_roundtrip(self):
        rng = np.random.default_rng(0)
        state = {
            "prompt_token_ids": [1, 2, 3], "output_token_ids": [9],
            "streamed": 1, "age_s": 1.25, "arrival_index": -7,
            "len": 3,
            "sampling_params": {"max_new_tokens": 4},
            "geometry": {"page_size": 4, "dtype": "float32"},
            "layers": [
                {"k": rng.normal(size=(2, 4, 2, 8)).astype(np.float32),
                 "v": rng.normal(size=(2, 4, 2, 8)).astype(np.float32)}
                for _ in range(2)],
        }
        back = wire.unpack_state(wire.pack_state(state))
        assert back["prompt_token_ids"] == [1, 2, 3]
        assert back["age_s"] == 1.25
        assert back["arrival_index"] == -7
        assert back["geometry"] == state["geometry"]
        assert len(back["layers"]) == 2
        for li in range(2):
            for name in ("k", "v"):
                np.testing.assert_array_equal(
                    back["layers"][li][name], state["layers"][li][name])

    def test_wedge_park_parks_calling_thread(self):
        """``wedge`` with ``park_s`` is the in-process variant: the
        calling thread parks (its inline heartbeats stop) instead of
        SIGSTOPping the whole test process."""
        assert "wedge" in KINDS
        plan = R.FaultPlan([R.FaultSpec(
            "serving.fleet.step", "wedge", at=0,
            payload={"park_s": 0.2})])
        with R.FaultInjector(plan) as inj:
            t0 = time.monotonic()
            fire("serving.fleet.step", step=0)
            assert time.monotonic() - t0 >= 0.2
        assert len(inj.injected) == 1


# -------------------------------------------- heartbeat telemetry rider
class TestHeartbeatTelemetry:
    def test_payload_fn_rides_beat_into_monitor(self):
        kv = fleet.LocalKVClient()
        pub = fleet.HeartbeatPublisher(
            client=kv, rank=2, interval_s=10.0,
            payload_fn=lambda: {"queue_depth": 3, "health": 1})
        assert pub.publish_once()
        mon = fleet.FleetMonitor(
            client=kv, config=_fc(),
            world_fn=lambda: fleet.WorldView([2], 2))
        mon.poll()
        tel = mon.telemetry(2)
        assert tel == {"queue_depth": 3, "health": 1}
        assert mon.telemetry(99) is None

    def test_failing_payload_fn_never_suppresses_the_beat(self):
        kv = fleet.LocalKVClient()

        def bad():
            raise RuntimeError("telemetry exploded")

        pub = fleet.HeartbeatPublisher(client=kv, rank=0,
                                       interval_s=10.0, payload_fn=bad)
        assert pub.publish_once()       # liveness must not hinge on it
        assert pub.seq == 1
        mon = fleet.FleetMonitor(
            client=kv, config=_fc(),
            world_fn=lambda: fleet.WorldView([0], 0))
        mon.poll()
        assert mon.telemetry(0) is None


# ----------------------------------------------- page-state handoff
class TestPageHandoff:
    def test_export_import_token_identical_zero_new_compiles(
            self, tiny_model, warm_cache):
        """The disaggregated core: run to the FIRST token on engine A,
        move pages+state to engine B, finish there — token-identical
        to a monolithic run, with zero new recompile-log events (the
        import is an eager scatter) and the stream watermark carried
        (no token is ever re-streamed across the handoff)."""
        prompts, sps = _traffic(3)
        ref = _reference(tiny_model, _cfg(), prompts, sps,
                         cache=warm_cache)
        ea = serving.LLMEngine(tiny_model, _cfg(),
                               program_cache=warm_cache)
        eb = serving.LLMEngine(tiny_model, _cfg(),
                               program_cache=warm_cache)
        ea.warmup()
        eb.warmup()
        events_before = obs.recompile_log().count
        for p, sp, want in zip(prompts, sps, ref):
            a_rec, a_stream = _collector()
            rid = ea.add_request(p, sp, stream=a_stream)
            first = None
            for _ in range(64):
                evs = ea.step()
                first = next((t for r, t, f in evs
                              if r == rid and t is not None), None)
                if first is not None or any(
                        r == rid and f for r, t, f in evs):
                    break
            state = ea.export_page_state(rid)
            assert not ea.has_unfinished()      # release semantics
            assert state["streamed"] == len(a_rec["tokens"])
            b_rec, b_stream = _collector()
            brid = eb.import_page_state(state, stream=b_stream)
            done = False
            for _ in range(64):
                if any(r == brid and f for r, t, f in eb.step()):
                    done = True
                    break
            assert done
            req = eb.finished_requests.pop(brid)
            assert req.output_token_ids == want
            # exactly-once across the handoff: A streamed the prefix,
            # B streamed the remainder, together the full history
            assert a_rec["tokens"] + b_rec["tokens"] == want
            assert b_rec["fins"] == 1
        assert obs.recompile_log().count == events_before, \
            "page handoff must not compile anything"
        assert ea.metrics.compile_count <= ea.metrics.compile_bound
        assert eb.metrics.compile_count <= eb.metrics.compile_bound
        ea.shutdown()
        eb.shutdown()

    def test_import_rejects_geometry_mismatch(self, tiny_model,
                                              warm_cache):
        ea = serving.LLMEngine(tiny_model, _cfg(),
                               program_cache=warm_cache)
        eb = serving.LLMEngine(tiny_model, _cfg(page_size=8))
        prompts, sps = _traffic(1)
        rid = ea.add_request(prompts[0], sps[0])
        while not any(t is not None for _, t, _ in ea.step()):
            pass
        state = ea.export_page_state(rid)
        with pytest.raises(ValueError, match="geometry mismatch"):
            eb.import_page_state(state)
        # tampered cache length violates the decode-state invariant
        # (lens == prompt + generated - 1: the newest token's KV is
        # written by the NEXT decode step)
        bad = dict(state)
        bad["len"] = state["len"] + 1
        with pytest.raises(ValueError, match="cache length"):
            ea.import_page_state(bad)
        ea.shutdown()
        eb.shutdown()

    def test_import_backpressure_leaves_state_retryable(
            self, tiny_model, warm_cache):
        """A decode engine with no free slot refuses with
        ``AdmissionRejected`` and the exporter still holds the state —
        the handoff defers, never loses."""
        ea = serving.LLMEngine(tiny_model, _cfg(),
                               program_cache=warm_cache)
        eb = serving.LLMEngine(tiny_model, _cfg(max_num_seqs=1),
                               program_cache=warm_cache)
        prompts, sps = _traffic(2)
        states = []
        for p, sp in zip(prompts, sps):
            rid = ea.add_request(p, sp)
            while not any(t is not None for _, t, _ in ea.step()):
                pass
            states.append(ea.export_page_state(rid))
        assert eb.import_page_state(states[0]) is not None
        with pytest.raises(AdmissionRejected) as ei:
            eb.import_page_state(states[1])
        assert ei.value.reason == "no_slot"
        # free the slot, then the SAME state lands fine
        while eb.has_unfinished():
            eb.step()
        assert eb.import_page_state(states[1]) is not None
        ea.shutdown()
        eb.shutdown()

    def test_disaggregated_engine_token_identity(self, tiny_model,
                                                 warm_cache):
        """Local prefill/decode split bounced through the REAL wire
        format (npz blob in the KV store): token-identical to the
        monolithic engine, still zero new compile events."""
        prompts, sps = _traffic(5)
        ref = _reference(tiny_model, _cfg(), prompts, sps,
                         cache=warm_cache)
        pre = serving.LLMEngine(tiny_model, _cfg(),
                                program_cache=warm_cache)
        dec = serving.LLMEngine(tiny_model, _cfg(),
                                program_cache=warm_cache)
        pre.warmup()
        dec.warmup()
        events_before = obs.recompile_log().count
        d = DisaggregatedEngine(pre, dec, client=fleet.LocalKVClient())
        out = d.generate(prompts, sps)
        assert [r.tokens for r in out] == ref
        assert {r.finished_on for r in out} <= {"prefill", "decode"}
        assert d.handoffs >= sum(1 for r in out
                                 if r.finished_on == "decode")
        assert d.handoff_bytes > 0
        assert obs.recompile_log().count == events_before
        pre.shutdown()
        dec.shutdown()


# ------------------------------------------------- remote engine proxy
class TestRemoteEngine:
    def test_remote_generate_token_identical_with_audit(
            self, tiny_model, warm_cache):
        prompts, sps = _traffic(4)
        ref = _reference(tiny_model, _cfg(), prompts, sps,
                         cache=warm_cache)
        c = _Cluster(tiny_model, [1], cache=warm_cache)
        try:
            proxy = c.proxy(1)
            proxy.warmup()
            recs = {}
            for p, sp in zip(prompts, sps):
                rec, stream = _collector()
                recs[proxy.add_request(p, sp, stream=stream)] = rec
            deadline = time.monotonic() + 60.0
            while proxy.has_unfinished():
                assert time.monotonic() < deadline, "remote serve hung"
                proxy.step()
            got = [proxy.finished_requests[rid].output_token_ids
                   for rid in recs]
            assert got == ref
            for rid, rec in recs.items():
                assert rec["tokens"] == \
                    proxy.finished_requests[rid].output_token_ids
                assert rec["fins"] == 1
            audit = proxy.call("audit")
            assert audit["compiled"] <= audit["bound"]
            assert audit["cache_loads"] > 0       # warm-booted replica
            proxy.shutdown()
        finally:
            c.close()

    def test_adoption_preserves_arrive_t_across_the_wire(
            self, tiny_model, warm_cache):
        """Satellite-2 regression: the proxy ships deadline AGE (not an
        absolute clock reading), the server re-anchors it — so the
        request's age SURVIVES the process boundary instead of
        resetting to zero, and a TTL never restarts per migration."""
        c = _Cluster(tiny_model, [1], cache=warm_cache)
        try:
            proxy = c.proxy(1)
            proxy.warmup()
            prompts, sps = _traffic(1, max_new=8)
            sp = serving.SamplingParams(
                max_new_tokens=8, temperature=0.0, seed=3,
                deadline_s=30.0)
            # a request that FIRST arrived ~5s ago on the (simulated)
            # origin replica, already one token in
            erid = proxy.adopt_request(
                prompts[0], sp, generated_token_ids=[17],
                arrive_t=time.perf_counter() - 5.0)
            proxy.step()                      # admit + replay prefill
            r = proxy.call("export_handoff",
                           {"request_id": erid, "hid": "age-probe"})
            blob = fleet.kv_get_bytes(
                c.kv, wire.handoff_key(fleet.coord_namespace(),
                                       "age-probe"), 5.0)
            state = wire.unpack_state(blob)
            assert r["hid"] == "age-probe"
            assert 4.5 <= state["age_s"] <= 15.0, \
                f"deadline TTL restarted: age {state['age_s']}"
        finally:
            c.close()

    def test_adopted_expired_deadline_fires_immediately(
            self, tiny_model, warm_cache):
        """A migrated request whose ORIGINAL arrival is already past
        its TTL expires on the adopter's next step — if migration
        restarted the TTL this would keep generating for 3 more
        seconds."""
        c = _Cluster(tiny_model, [1], cache=warm_cache)
        try:
            proxy = c.proxy(1)
            proxy.warmup()
            prompts, _ = _traffic(1)
            sp = serving.SamplingParams(max_new_tokens=16,
                                        temperature=0.0, seed=0,
                                        deadline_s=3.0)
            rec, stream = _collector()
            erid = proxy.adopt_request(
                prompts[0], sp, generated_token_ids=[5],
                stream=stream, arrive_t=time.perf_counter() - 5.0)
            evs = proxy.step()
            assert (erid, None, True) in evs
            assert proxy.finished_requests[erid].finish_reason == \
                "deadline"
            assert rec["fins"] == 1
        finally:
            c.close()


# --------------------------------------------------- the serving fleet
class TestServingFleet:
    def test_fleet_generate_token_identical(self, tiny_model,
                                            warm_cache):
        prompts, sps = _traffic(6)
        ref = _reference(tiny_model, _cfg(), prompts, sps,
                         cache=warm_cache)
        c = _Cluster(tiny_model, [1, 2], cache=warm_cache)
        try:
            sf = c.serving_fleet([1, 2])
            results = sf.router.generate(prompts, sps)
            assert [r.output_token_ids for r in results] == ref
            for h in sf.router.replicas:
                audit = h.engine.call("audit")
                assert audit["compiled"] <= audit["bound"]
            assert {sf.rank_of(0), sf.rank_of(1)} == {1, 2}
            sf.shutdown()
        finally:
            c.close()

    @pytest.mark.chaos
    def test_stream_exactly_once_across_midstream_failover(
            self, tiny_model, warm_cache):
        """A replica that dies MID-STREAM (injected step fault): its
        requests migrate token-only and replay — and every user stream
        still sees each token exactly once with exactly one fin,
        token-identical to the fault-free reference."""
        prompts, sps = _traffic(6, max_new=8)
        ref = _reference(tiny_model, _cfg(), prompts, sps,
                         cache=warm_cache)
        c = _Cluster(tiny_model, [1, 2], cache=warm_cache)
        try:
            sf = c.serving_fleet([1, 2])
            recs = {}
            rids = []
            for p, sp in zip(prompts, sps):
                rec, stream = _collector()
                rid = sf.router.add_request(p, sp, stream=stream)
                rids.append(rid)
                recs[rid] = rec
            plan = R.FaultPlan([R.FaultSpec("serving.fleet.step",
                                            "exception", at=10)],
                               name="fleet-midstream")
            deadline = time.monotonic() + 90.0
            with R.FaultInjector(plan) as inj:
                while sf.router.has_unfinished():
                    assert time.monotonic() < deadline, "fleet hung"
                    sf.step()
            assert len(inj.injected) == 1, "fault never fired"
            assert sf.router.snapshot()["failovers"] >= 1
            out = [sf.router.finished_results.pop(rid) for rid in rids]
            assert [r.output_token_ids for r in out] == ref
            assert sum(r.migrations for r in out) >= 1
            for rid, r in zip(rids, out):
                assert recs[rid]["tokens"] == r.output_token_ids, \
                    "stream delivery diverged from the final history"
                assert recs[rid]["fins"] == 1
            sf.shutdown()
        finally:
            c.close()

    @pytest.mark.chaos
    def test_wedged_replica_dead_verdict_and_warm_respawn(
            self, tiny_model, warm_cache):
        """The watchdog-TIMEOUT fault: a replica whose step loop parks
        (heartbeats go silent — the in-process stand-in for SIGSTOP)
        draws a DEAD verdict within the configured budget, the pending
        step RPC aborts on the verdict, its requests migrate with zero
        loss, and the respawn claims the SPARE rank, booting WARM from
        the shared AOT cache."""
        prompts, sps = _traffic(6, max_new=8)
        ref = _reference(tiny_model, _cfg(), prompts, sps,
                         cache=warm_cache)
        c = _Cluster(tiny_model, [1, 2], spares=[3], cache=warm_cache)
        try:
            sf = c.serving_fleet([1, 2], spares=[3])
            recs = {}
            rids = []
            for p, sp in zip(prompts, sps):
                rec, stream = _collector()
                rid = sf.router.add_request(p, sp, stream=stream)
                rids.append(rid)
                recs[rid] = rec
            plan = R.FaultPlan([R.FaultSpec(
                "serving.fleet.step", "wedge", at=8,
                payload={"park_s": 6.0})], name="fleet-wedge")
            deadline = time.monotonic() + 120.0
            with R.FaultInjector(plan) as inj:
                while sf.router.has_unfinished():
                    assert time.monotonic() < deadline, "fleet hung"
                    sf.step()
            assert len(inj.injected) == 1, "wedge never fired"
            # bounded-time detection, by VERDICT (not deadline burn)
            dets = sf.detections()
            assert dets, "no watchdog-driven RPC abort recorded"
            assert dets[0]["verdict"] == "dead-verdict"
            assert dets[0]["detect_s"] < 6.0
            assert c.monitor.dead_ranks() == [dets[0]["rank"]]
            # zero token loss, token-identical, exactly-once streams
            out = [sf.router.finished_results.pop(rid) for rid in rids]
            assert [r.output_token_ids for r in out] == ref
            for rid, r in zip(rids, out):
                assert recs[rid]["tokens"] == r.output_token_ids
                assert recs[rid]["fins"] == 1
            # respawn-elsewhere: the replacement runs on the spare
            # rank and booted WARM from the shared AOT cache
            assert sf.respawn_ms, "no respawn recorded"
            wedged = dets[0]["rank"]
            slot = next(i for i in (0, 1)
                        if [1, 2][i] == wedged)
            assert sf.rank_of(slot) == 3
            respawned = sf.router.replicas[slot]
            assert respawned.generation >= 1
            assert respawned.boot_info.get("warm") is True, \
                f"respawn was cold: {respawned.boot_info}"
            sf.shutdown()
        finally:
            c.close()

    @pytest.mark.chaos
    def test_queued_deadline_expiry_during_failover(self, tiny_model,
                                                    warm_cache):
        """Requests queued with a TTL when a replica fails: the TTL
        counts from FIRST arrival through the migration, so
        already-expired requests finish with reason "deadline" on the
        adopter — no hang, no loss, and the untimed requests stay
        token-identical to the fault-free reference."""
        prompts, sps = _traffic(4, max_new=8)
        ref = _reference(tiny_model, _cfg(), prompts, sps,
                         cache=warm_cache)
        dprompts, _ = _traffic(2, seed=11)
        dsps = [serving.SamplingParams(max_new_tokens=8,
                                       temperature=0.0, seed=90 + i,
                                       deadline_s=0.5)
                for i in range(2)]
        c = _Cluster(tiny_model, [1, 2], cache=warm_cache,
                     ecfg=_cfg(max_num_seqs=2))
        try:
            sf = c.serving_fleet([1, 2])
            recs = {}
            rids, drids = [], []
            for p, sp in zip(prompts, sps):
                rec, stream = _collector()
                rid = sf.router.add_request(p, sp, stream=stream)
                rids.append(rid)
                recs[rid] = rec
            for p, sp in zip(dprompts, dsps):
                drids.append(sf.router.add_request(p, sp))
            time.sleep(0.7)          # both TTLs expire while queued
            plan = R.FaultPlan([R.FaultSpec("serving.fleet.step",
                                            "exception", at=2)],
                               name="fleet-deadline-failover")
            deadline = time.monotonic() + 90.0
            with R.FaultInjector(plan) as inj:
                while sf.router.has_unfinished():
                    assert time.monotonic() < deadline, "fleet hung"
                    sf.step()
            assert len(inj.injected) == 1
            assert sf.router.snapshot()["failovers"] >= 1
            out = [sf.router.finished_results.pop(rid) for rid in rids]
            assert [r.output_token_ids for r in out] == ref
            for rid in rids:
                assert recs[rid]["fins"] == 1
            for drid in drids:
                rr = sf.router.finished_results.pop(drid)
                assert rr.finish_reason == "deadline", \
                    f"TTL restarted across failover: {rr.finish_reason}"
            sf.shutdown()
        finally:
            c.close()

    def test_respawn_with_empty_spare_pool_is_retryable(self):
        """The elasticity factory with no spares left must raise
        WITHOUT corrupting the slot bookkeeping — the router requeues
        the respawn and retries, and a later refill would still see
        one retirement per actual respawn."""
        kv = fleet.LocalKVClient()
        cfg = FleetServingConfig([1], spare_ranks=(),
                                 fleet_config=_fc())
        sf = ServingFleet.__new__(ServingFleet)
        sf.client = kv
        sf.config = cfg
        sf._ns = fleet.coord_namespace
        sf._lock = threading.Lock()
        sf._spares = []
        sf._assigned = {0: 1}          # slot 0 already ran on rank 1
        sf._retired = []
        sf.proxies = {}
        sf.respawn_ms = []
        sf.monitor = fleet.FleetMonitor(
            client=kv, config=cfg.fleet_config,
            world_fn=lambda: fleet.WorldView([1], 1))
        for _ in range(3):
            with pytest.raises(RuntimeError, match="spare pool"):
                sf._factory(0)
        assert sf._retired == []       # no phantom retirements
        assert sf._assigned == {0: 1}  # slot still owned by rank 1

    def test_boot_failure_rolls_back_claim(self):
        """A transient boot failure must not burn the claim: a failed
        FIRST boot leaves the slot unassigned so the retry is a first
        boot of the SAME rank (pre-fix it became a phantom respawn,
        and with no spares the second attempt died on "spare pool
        empty" — the deadline-failover flake), and a failed respawn
        boot puts the spare back in the pool."""
        kv = fleet.LocalKVClient()
        cfg = FleetServingConfig(
            [9], spare_ranks=(),
            fleet_config=_fc(rendezvous_timeout_s=0.4))
        sf = ServingFleet.__new__(ServingFleet)
        sf.client = kv
        sf.config = cfg
        sf._ns = fleet.coord_namespace
        sf._lock = threading.Lock()
        sf._spares = []
        sf._assigned = {}
        sf._retired = []
        sf.proxies = {}
        sf.respawn_ms = []
        sf.monitor = fleet.FleetMonitor(
            client=kv, config=cfg.fleet_config,
            world_fn=lambda: fleet.WorldView([9], 9))
        for _ in range(2):             # rank 9 has no server: timeout
            with pytest.raises(Exception) as ei:
                sf._factory(0)
            assert "spare pool" not in str(ei.value)
        assert sf._assigned == {} and sf._retired == []
        # respawn flavor: the failed spare boot goes back in the pool
        sf._assigned = {0: 1}
        sf._spares = [3]
        with pytest.raises(Exception) as ei:
            sf._factory(0)
        assert "spare pool" not in str(ei.value)
        assert sf._spares == [3]       # not leaked
        assert sf._assigned == {0: 1} and sf._retired == []

    def test_warmup_holds_verdicts(self):
        """warmup() is boot-phase work — the replica compiles or
        cache-loads inside the dispatch, beat-silent throughout — so
        the proxy must hold fleet verdicts across the RPC and release
        them afterwards, success or failure."""
        kv = fleet.LocalKVClient()
        calls = []
        p = RemoteEngineClient(
            kv, 9, namespace_fn=fleet.coord_namespace,
            config=_fc(rendezvous_timeout_s=0.2),
            hold_verdict=lambda s: calls.append(("hold", s)),
            release_verdict=lambda: calls.append(("release",)))
        with pytest.raises(Exception):
            p.warmup()             # nobody serves rank 9: times out
        assert calls == [("hold", 0.2), ("release",)]

    def test_monitor_hold_verdict_spans_boot_silence(self):
        """A rank mid-boot goes beat-silent for longer than
        dead_after_s; the boot-phase hold must cap it at SUSPECT
        (DEAD is terminal — a spurious verdict would hang the rank
        forever), and releasing the hold restarts the staleness clock
        so the first post-boot beat is not raced by leftover age."""
        kv = fleet.LocalKVClient()
        clock = [0.0]
        mon = fleet.FleetMonitor(
            client=kv, config=_fc(), time_fn=lambda: clock[0],
            world_fn=lambda: fleet.WorldView([1], 1))
        mon.poll()                     # first observation at t=0
        mon.hold_verdict(1, for_s=10.0)
        clock[0] = 2.0
        assert mon.poll()[1] is fleet.RankState.SUSPECT
        clock[0] = 5.0                 # age 5 > dead_after 2.4: held
        assert mon.poll()[1] is fleet.RankState.SUSPECT
        assert not mon.is_dead(1)
        mon.release_verdict_hold(1)    # boot returned at t=5
        clock[0] = 6.0                 # age counts from release, not t=0
        assert mon.poll()[1] is not fleet.RankState.DEAD
        clock[0] = 7.8                 # real post-boot silence...
        assert mon.poll()[1] is fleet.RankState.SUSPECT
        clock[0] = 9.0                 # ...still escalates on schedule
        assert mon.poll()[1] is fleet.RankState.DEAD

    def test_monitor_hold_expires_with_boot_deadline(self):
        """A rank that never finishes boot still dies on schedule:
        the hold lapses with the boot deadline it was sized to."""
        kv = fleet.LocalKVClient()
        clock = [0.0]
        mon = fleet.FleetMonitor(
            client=kv, config=_fc(), time_fn=lambda: clock[0],
            world_fn=lambda: fleet.WorldView([1], 1))
        mon.poll()
        mon.hold_verdict(1, for_s=3.0)
        clock[0] = 2.0
        assert mon.poll()[1] is fleet.RankState.SUSPECT
        clock[0] = 4.0                 # hold expired, age 4 > 2.4
        assert mon.poll()[1] is fleet.RankState.DEAD

    def test_fleet_serving_config_validates(self):
        with pytest.raises(ValueError, match="at least one"):
            FleetServingConfig([])
        with pytest.raises(ValueError, match="both"):
            FleetServingConfig([1, 2], spare_ranks=[2])
        cfg = FleetServingConfig([1], rpc_timeout_s=0.5,
                                 fleet_config=_fc())
        assert cfg.fleet_config.collective_timeout_s == 0.5
        assert _fc().collective_timeout_s == 8.0   # original untouched


# ------------------------------------------- disagg over remote engines
class TestRemoteDisagg:
    def test_remote_prefill_decode_split_token_identical(
            self, tiny_model, warm_cache):
        """The full disaggregated path over the wire: remote prefill
        replica fills pages, blob parks in the KV, remote decode
        replica imports and finishes — token-identical to the
        monolithic engine, compile audit inside the bound on BOTH
        sides."""
        prompts, sps = _traffic(4)
        ref = _reference(tiny_model, _cfg(), prompts, sps,
                         cache=warm_cache)
        c = _Cluster(tiny_model, [1, 2], cache=warm_cache)
        try:
            pre = c.proxy(1)
            dec = c.proxy(2)
            pre.warmup()
            dec.warmup()
            d = DisaggregatedEngine(pre, dec, client=c.kv)
            out = d.generate(prompts, sps)
            assert [r.tokens for r in out] == ref
            assert d.handoffs >= 1
            assert d.handoff_bytes > 0
            for proxy in (pre, dec):
                audit = proxy.call("audit")
                assert audit["compiled"] <= audit["bound"]
                proxy.shutdown()
        finally:
            c.close()
