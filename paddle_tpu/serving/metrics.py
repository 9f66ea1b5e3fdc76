"""Serving observability: counters, gauges, and latency histograms,
exposed as one plain-dict snapshot.

Backing store: the process-wide :mod:`paddle_tpu.observability`
registry.  ``Histogram`` here IS ``observability.metrics.Histogram``
(compatibility alias), every latency histogram is registered under an
engine-labeled Prometheus name (``serving_ttft_seconds{engine=...}``),
and ``note_compile`` bumps the registry's ``serving_compile_total``
counter — so `profiler.metrics_report()` and the Prometheus exporter
both see engine compile counts / TTFT / ITL directly, not through a
diverging side-registry.  The `snapshot()` dict remains the stable
coarse integration surface (`LLMEngine` registers it as a metrics
source; see docs/serving.md 'Metrics reference').
"""
from __future__ import annotations

import collections
import threading
import time
import weakref

from paddle_tpu.observability.metrics import (Histogram, _label_key,
                                              next_instance_label,
                                              registry)

__all__ = ["Histogram", "EngineMetrics"]

# why a decode pass in flight was fetched with none launched behind it:
# an admission due at the next step (or one whose prefill waited it out),
# a decode fault, a pass that would have had to evict, a page hand-off, no
# row left for the next pass (or shutdown), a kind of generation, the
# guard or a pool with a state by slot, which read the host between passes
DRAIN_CAUSES = ("prefill", "fault", "evict", "handoff", "idle", "kind")

# Live-instance count per label set.  Two engines created with the same
# explicit `metrics_name` SHARE registry instruments (same (name,
# labels) key — Prometheus semantics), so the instruments may only be
# dropped when the LAST owner releases; otherwise one engine's
# shutdown() would silently delete a live engine's histograms from the
# registry while its snapshot() kept reporting them — exactly the
# snapshot-vs-Prometheus divergence this layer exists to rule out.
_live_labels = {}
_live_lock = threading.Lock()


def _acquire_labels(labels):
    key = _label_key(labels)
    with _live_lock:
        _live_labels[key] = _live_labels.get(key, 0) + 1


def _release_labels(labels):
    key = _label_key(labels)
    # Drop while still holding _live_lock: deciding n==0 and then
    # dropping outside the lock would let a same-named engine created
    # in the gap lose its freshly re-created instruments.
    with _live_lock:
        n = _live_labels.get(key, 0) - 1
        if n > 0:
            _live_labels[key] = n
            return
        _live_labels.pop(key, None)
        if n == 0:
            registry().drop_labeled(labels)


class EngineMetrics:
    """All engine counters in one place; `snapshot()` is the contract.

    `name` labels this instance's registry instruments; an unnamed
    instance (tests, ad-hoc use) gets a unique generated label so two
    engines never share a histogram by accident."""

    def __init__(self, clock=time.perf_counter, name=None):
        self.clock = clock
        self.started_t = clock()
        reg = registry()
        self.labels = {"engine": name or next_instance_label("engine")}
        labels = self.labels
        _acquire_labels(labels)
        self._released = False
        # GC safety net: an instance dropped without release() must
        # still decrement the live count, or the labels leak forever
        self._finalizer = weakref.finalize(
            self, _release_labels, dict(labels))
        # counters
        self.requests_received = 0
        self.requests_admitted = 0
        self.requests_finished = 0
        self.requests_evicted = 0
        self.requests_rejected = 0   # backpressure (queue_full/draining)
        self.requests_expired = 0    # deadline enforcement
        self.requests_adopted = 0    # router failover migrations in
        self.decode_fault_recoveries = 0
        self.guard_anomalies = 0     # sentinel guard-flagged requests
        self.prefill_steps = 0
        self.decode_steps = 0
        self.prompt_tokens = 0
        self.generated_tokens = 0
        self.compile_count = 0
        self.compile_bound = 0
        self.aot_cache_loads = 0     # warm-boot program-cache hits
        self._compile_counter = reg.counter(
            "serving_compile_total", labels=labels,
            help="XLA programs compiled by the serving engine")
        self._aot_load_counter = reg.counter(
            "serving_aot_load_total", labels=labels,
            help="programs loaded from the AOT cache instead of compiled")
        # gauges (engine pushes current values)
        self.queue_depth = 0
        self.running = 0
        self.pages_in_use = 0
        self.pages_total = 0
        self.pages_live = 0          # pages the last decode step read
        self.health = "healthy"      # engine-pushed health-state name
        self.health_state = reg.gauge(
            "serving_health_state", labels=labels,
            help="engine health: 0 healthy / 1 degraded / 2 draining")
        # live admission signals for the multi-engine router's scrape
        # path (observability.export.serve_prometheus): refreshed from
        # the plain attrs above by sync_gauges() at every engine step
        self.queue_depth_gauge = reg.gauge(
            "serving_queue_depth", labels=labels,
            help="requests waiting for admission")
        self.page_occupancy_gauge = reg.gauge(
            "serving_page_occupancy", labels=labels,
            help="KV page-pool occupancy fraction (0..1)")
        self.pages_live_gauge = reg.gauge(
            "serving_pages_live", labels=labels,
            help="KV pages the last decode step's attention had to read")
        # histograms (seconds) — registry-owned, engine-labeled
        self.ttft = reg.histogram(
            "serving_ttft_seconds", labels=labels,
            help="time to first token")
        # TTFT stage decomposition (fleettrace): for a fresh request
        # TTFT = queue + prefill exactly; decode is the resume latency
        # of migrated/adopted work (time from adoption on THIS engine
        # to the first token it produces) and is absent otherwise
        self.ttft_queue = reg.histogram(
            "serving_ttft_queue_seconds", labels=labels,
            help="TTFT stage: arrival to prefill start (queue wait)")
        self.ttft_prefill = reg.histogram(
            "serving_ttft_prefill_seconds", labels=labels,
            help="TTFT stage: prefill start to first token")
        self.ttft_decode = reg.histogram(
            "serving_ttft_decode_seconds", labels=labels,
            help="TTFT stage: adoption/import to first resumed token")
        self.inter_token = reg.histogram(
            "serving_inter_token_seconds", labels=labels,
            help="inter-token latency")
        self.e2e_latency = reg.histogram(
            "serving_e2e_latency_seconds", labels=labels,
            help="request end-to-end latency")
        self.prefill_step_s = reg.histogram(
            "serving_prefill_step_seconds", labels=labels,
            help="prefill step wall time")
        self.decode_step_s = reg.histogram(
            "serving_decode_step_seconds", labels=labels,
            help="decode step wall time")
        # sample calls by the work their batch asked of the sampler:
        # greedy | draw | top_k | top_p | top_k+top_p
        self.sampler_paths = collections.Counter()
        # expert routing (models with expert layers only): created by
        # the first note_experts, so an engine without experts registers
        # nothing and snapshots as before
        self.moe_tokens_routed = 0   # token-expert pairs, all layers
        # per-slot recurrent state (pools with state layers only)
        self.state_pool_bytes = 0
        self.state_slot_bytes = 0    # one slot's state in one layer
        self.state_recurrence = None
        self.state_admits_total = 0  # slots whose state a prefill overwrote
        # per-slot rings of window layers (pools with window layers only)
        self.window_pool_bytes = 0
        self.window_rows_live = 0    # rows of one window layer the last
        #                              decode step's passes read
        self.moe_imbalance = None    # histogram of heaviest / mean load
        # generation by diffusion over blocks (0 = a next-token engine,
        # which snapshots as before)
        self.block_length = 0
        self.decode_forwards_total = 0   # live slots summed over passes
        self.commit_passes_total = 0     # passes that stored a final block
        self.tokens_fixed_total = 0      # positions fixed by denoising
        # run-ahead: decode passes launched while the previous pass's
        # tokens were still on the device, and the times a pass in flight
        # was fetched with none behind it, by cause (DRAIN_CAUSES)
        self.passes_ahead = 0
        self.drains = collections.Counter()

    def note_experts(self, pairs, tokens_max, mean_load):
        """One program's routing: `pairs` token-expert pairs over all
        expert layers, the heaviest expert's tokens (worst layer) and
        the mean load of an expert in a layer."""
        if self.moe_imbalance is None:
            self.moe_imbalance = registry().histogram(
                "serving_moe_expert_imbalance", labels=self.labels,
                help="heaviest expert's tokens over the mean load, "
                     "worst layer of a program")
        self.moe_tokens_routed += int(pairs)
        self.moe_imbalance.observe(float(tokens_max) / mean_load)

    def release(self):
        """Release this instance's claim on its registry instruments —
        a finite-lifetime engine must not grow the registry forever.
        The instruments are dropped only when the last same-labeled
        instance releases (idempotent)."""
        if self._released:
            return
        self._released = True
        self._finalizer.detach()
        _release_labels(self.labels)

    def sync_gauges(self):
        """Mirror the engine-pushed plain attrs into their registry
        gauges, so the Prometheus scrape and snapshot() can't diverge
        (same invariant the histograms get by being registry-owned)."""
        self.queue_depth_gauge.set(self.queue_depth)
        self.page_occupancy_gauge.set(
            self.pages_in_use / self.pages_total if self.pages_total
            else 0.0)
        self.pages_live_gauge.set(self.pages_live)

    def note_aot_load(self):
        """One program loaded from the persisted AOT cache — NOT a
        compile: deliberately outside `note_compile` and the recompile
        log, so a warm boot's compile count stays zero."""
        self.aot_cache_loads += 1
        self._aot_load_counter.inc()

    def note_compile(self):
        self.compile_count += 1
        self._compile_counter.inc()
        if self.compile_bound and self.compile_count > self.compile_bound:
            raise RuntimeError(
                f"recompile storm: {self.compile_count} compiles exceeds "
                f"the declared bound {self.compile_bound} — a shape "
                f"escaped the bucket set")

    def snapshot(self):
        """Plain-dict view of everything (stable keys; see
        docs/serving.md 'Metrics reference')."""
        elapsed = max(self.clock() - self.started_t, 1e-9)
        moe = ({} if self.moe_imbalance is None else {"moe": {
            "tokens_routed": self.moe_tokens_routed,
            "expert_imbalance": self.moe_imbalance.summary()}})
        state = ({} if not self.state_pool_bytes else {"state": {
            "pool_bytes": self.state_pool_bytes,
            "slot_layer_bytes": self.state_slot_bytes,
            "recurrence": self.state_recurrence,
            "admits_total": self.state_admits_total}})
        window = ({} if not self.window_pool_bytes else {"window": {
            "pool_bytes": self.window_pool_bytes,
            "rows_live": self.window_rows_live}})
        blocks = ({} if not self.block_length else {"blocks": {
            "block_length": self.block_length,
            "decode_forwards_total": self.decode_forwards_total,
            "commit_passes_total": self.commit_passes_total,
            "tokens_fixed_total": self.tokens_fixed_total,
            "tokens_per_forward": round(
                self.tokens_fixed_total / self.decode_forwards_total, 4)
            if self.decode_forwards_total else 0.0}})
        return {
            **moe,
            **state,
            **window,
            **blocks,
            "uptime_s": round(elapsed, 3),
            "requests": {
                "received": self.requests_received,
                "admitted": self.requests_admitted,
                "finished": self.requests_finished,
                "evicted": self.requests_evicted,
                "rejected": self.requests_rejected,
                "expired": self.requests_expired,
                "adopted": self.requests_adopted,
            },
            "queue_depth": self.queue_depth,
            "running": self.running,
            "health": self.health,
            "decode_fault_recoveries": self.decode_fault_recoveries,
            "guard_anomalies": self.guard_anomalies,
            "steps": {
                "prefill": self.prefill_steps,
                "decode": self.decode_steps,
            },
            "tokens": {
                "prompt": self.prompt_tokens,
                "generated": self.generated_tokens,
                "per_s": round(self.generated_tokens / elapsed, 2),
            },
            "pages": {
                "in_use": self.pages_in_use,
                "total": self.pages_total,
                "live": self.pages_live,
                "utilization": round(
                    self.pages_in_use / self.pages_total, 4)
                if self.pages_total else 0.0,
            },
            "compiles": {
                "count": self.compile_count,
                "bound": self.compile_bound,
                "cache_loads": self.aot_cache_loads,
            },
            "ttft_ms": self.ttft.summary(),
            "inter_token_ms": self.inter_token.summary(),
            "e2e_latency_ms": self.e2e_latency.summary(),
            "prefill_step_ms": self.prefill_step_s.summary(),
            "decode_step_ms": self.decode_step_s.summary(),
            "sampler_paths": dict(self.sampler_paths),
            "run_ahead": {
                "passes_ahead": self.passes_ahead,
                "drains": {c: self.drains[c] for c in DRAIN_CAUSES},
            },
        }
