"""ReplicaServer — the worker-process side of the serving fleet.

One per replica rank: owns (after the controller's ``boot`` verb) a
real :class:`~paddle_tpu.serving.engine.LLMEngine`, and serves the
:mod:`.wire` RPC lane in a single-threaded loop — every engine call
runs on this one thread, so the engine needs no extra locking and the
whole process inherits the engine's determinism.

The engine is built with NO stream callbacks: streamed-token delivery
is the CONTROLLER's job (exactly-once from the seq-numbered step
responses, see :mod:`.handle`); the server only reports events and
drains ``finished_requests`` into each step response so the
authoritative token history and finish reason cross the wire with the
step that produced them.

Heartbeats: the worker entrypoint installs a
:class:`~paddle_tpu.resilience.fleet.HeartbeatPublisher` with
``payload_fn=server.telemetry`` — every beat carries queue depth,
page occupancy and health state, and a SIGSTOP freezes the publisher
thread together with the serve loop, which is precisely what turns a
wedged replica into a watchdog DEAD verdict.  In-process tests pass
``inline_beats=True`` instead and the loop itself beats between RPCs
(a parked loop then goes silent, same verdict path, no threads).

Chaos hook ``serving.fleet.step`` fires before every engine step:
``rank_kill`` (SIGKILL — the crash path) and ``wedge`` (SIGSTOP /
park — the timeout path) are the two faults of the acceptance proof.
"""
from __future__ import annotations

import threading
import time

from paddle_tpu.observability import span, use_context
from paddle_tpu.resilience import fleet as _fleet
from paddle_tpu.resilience.faultinject import fire as _fire
from paddle_tpu.serving.fleet import wire

__all__ = ["ReplicaServer"]


class ReplicaServer:
    def __init__(self, client, rank, engine_factory, *, config=None,
                 namespace_fn=None, publisher=None, inline_beats=False):
        self._client = client
        self.rank = int(rank)
        self._factory = engine_factory
        self._config = config or _fleet.get_config()
        self._ns = namespace_fn or _fleet.coord_namespace
        self._publisher = publisher
        self._inline_beats = bool(inline_beats)
        self._lock = threading.Lock()   # guards the engine REFERENCE
        self._engine = None             # (calls run on the loop thread)
        self._stop = threading.Event()
        self.steps = 0
        self.requests_served = 0

    @property
    def engine(self):
        with self._lock:
            return self._engine

    def stop(self):
        self._stop.set()

    # ------------------------------------------------------ telemetry
    def telemetry(self):
        """Heartbeat payload (and step-response rider): the live
        admission signals the router's scoring reads.  Runs on the
        publisher thread — every read is a GIL-atomic int/len read of
        engine state, and a mid-mutation glimpse only skews one beat's
        routing score, never correctness."""
        e = self.engine
        if e is None:
            return {"health": 0, "queue_depth": 0,
                    "page_occupancy": 0.0, "num_running": 0,
                    "booted": False}
        return {"health": int(e.health.state),
                "queue_depth": int(e.queue_depth),
                "page_occupancy": round(float(e.page_occupancy), 4),
                "num_running": int(e.num_running),
                "booted": True}

    # ------------------------------------------------------ serve loop
    def serve(self):
        """Blocking request loop; returns after a ``shutdown`` verb or
        :meth:`stop`.  Lane seq starts at 0 and the controller owns
        it, so a request is never skipped or double-served."""
        seq = 0
        recv_s = max(0.25, self._config.kv_slice_s * 2.0)
        last_beat = 0.0
        while not self._stop.is_set():
            if self._inline_beats and self._publisher is not None:
                now = time.monotonic()
                if now - last_beat >= self._publisher._interval:
                    self._publisher.publish_once()
                    last_beat = now
            try:
                method, payload, ctx = wire.read_request(
                    self._client, self._ns(), self.rank, seq, recv_s,
                    config=self._config)
            except _fleet.CollectiveTimeout:
                continue            # empty slice window: poll stop/beat
            try:
                # the envelope's trace context (if any) becomes ambient
                # for the verb, so engine spans on THIS process record
                # under the originating request's trace
                with use_context(ctx):
                    result = self._dispatch(method, payload or {})
            except Exception as e:
                wire.post_response(self._client, self._ns(), self.rank,
                                   seq, error=e)
            else:
                wire.post_response(self._client, self._ns(), self.rank,
                                   seq, result=result)
            self.requests_served += 1
            seq += 1
            if method == "shutdown":
                break

    # ------------------------------------------------------- handlers
    def _dispatch(self, method, p):
        if method == "ping":
            return {"rank": self.rank}
        if method == "boot":
            with span("serving.fleet.boot", rank=self.rank):
                engine = self._factory(p)
            with self._lock:
                self._engine = engine
            return {"ok": True}
        if method == "shutdown":
            self._stop.set()
            e = self.engine
            if e is not None:
                e.shutdown()
            return {"ok": True}
        engine = self.engine
        if engine is None:
            raise RuntimeError(
                f"replica rank {self.rank} has no engine yet — the "
                f"controller must send 'boot' first")
        if method == "warmup":
            return engine.warmup()
        if method == "add":
            return engine.add_request(p["prompt"],
                                      wire.sp_from_dict(p.get("sp")))
        if method == "adopt":
            age_s = p.get("age_s")
            arrive_t = (None if age_s is None
                        else engine.metrics.clock() - float(age_s))
            return engine.adopt_request(
                p["prompt"], wire.sp_from_dict(p.get("sp")),
                generated_token_ids=p.get("generated", ()),
                streamed=p.get("streamed"), arrive_t=arrive_t,
                arrival_index=p.get("arrival_index"))
        if method == "step":
            # the chaos hook of the acceptance proof: rank_kill /
            # wedge land here, mid-decode from the fleet's view
            _fire("serving.fleet.step", rank=self.rank,
                  step=self.steps)
            evs = engine.step()
            self.steps += 1
            finished = []
            while engine.finished_requests:
                rid, req = engine.finished_requests.popitem(last=False)
                finished.append({
                    "rid": rid,
                    "tokens": [int(t) for t in req.output_token_ids],
                    "finish_reason": req.finish_reason})
            return {"events": [[rid, tok, bool(fin)]
                               for rid, tok, fin in evs],
                    "finished": finished,
                    "telemetry": self.telemetry()}
        if method == "release_waiting":
            return [{"rid": r.request_id,
                     "tokens": [int(t) for t in r.output_token_ids]}
                    for r in engine.release_waiting()]
        if method == "export_handoff":
            state = engine.export_page_state(
                p["request_id"], release=p.get("release", True))
            if state is None:
                # finished by the pass in flight the hand-off fetched:
                # the next step's events report it
                return {"hid": p["hid"], "finished": True}
            blob = wire.pack_state(state)
            key = wire.handoff_key(self._ns(), p["hid"])
            _fleet.kv_set_bytes(self._client, key, blob)
            return {"hid": p["hid"], "bytes": len(blob),
                    "pages": len(state["layers"][0][next(
                        iter(state["layers"][0]))])}
        if method == "import_handoff":
            key = wire.handoff_key(self._ns(), p["hid"])
            blob = _fleet.kv_get_bytes(
                self._client, key, self._config.collective_timeout_s,
                site="serving.fleet.handoff", config=self._config)
            state = wire.unpack_state(blob)
            rid = engine.import_page_state(state)
            # consume the blob only AFTER a successful import — a
            # rejected import (no slot/pages yet) must stay retryable
            try:
                self._client.key_value_delete(key)
            except Exception:
                pass
            return rid
        if method == "audit":
            m = engine.metrics
            return {"compiled": int(m.compile_count),
                    "bound": int(m.compile_bound),
                    "cache_loads": int(m.aot_cache_loads),
                    "steps": self.steps,
                    "generated_tokens": int(m.generated_tokens)}
        raise ValueError(f"unknown serving-fleet RPC method {method!r}")
