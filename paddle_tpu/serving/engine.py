"""LLMEngine — continuous-batching serving over a paged KV cache.

Execution model (the Gemma-on-TPU serving recipe, PAPERS.md): a SMALL,
FIXED set of compiled programs serves every request mix —

- one PREFILL program per prompt-length bucket: ``[1, bucket]`` token
  ids in, dense causal attention, KV scattered into the shared paged
  pools, last-real-token logits out;
- ONE DECODE program at the full slot width ``[B, 1]``: every live
  sequence appends its token at its own length and attends over its own
  pages (ragged continuation batching — no re-padding, ever);
- one SAMPLER program per width (prefill=1, decode=B) with every knob
  (temperature/top-k/top-p/seed) as a traced operand.

Compile count is therefore bounded by ``len(buckets) + 3`` for the life
of the engine; `EngineMetrics.note_compile` hard-fails past the bound
(the recompile storm tracelint TL3xx polices, turned into a runtime
assertion).

Continuous batching: new requests join the running decode batch at step
boundaries (admission → bucketed prefill → slot in the decode batch),
finished sequences free their pages immediately, and when the pool runs
dry the latest-arrived running request is deterministically preempted
(recompute-style: replayed later by prefilling prompt + generated
tokens; positional sampling seeds make the replay token-identical —
bit-exact on CPU; on TPU a replayed position is computed by the prefill
program instead of the decode program, so a near-tie in bf16 logits
could in principle resolve differently across an eviction).

Run-ahead: where the kind of generation allows it (next-token, no
guard, no state by slot in the pool), a step launches the NEXT decode
pass — its rows' ids taken on the device from the pass in flight's
sampler output — before it fetches the tokens of the pass in flight, so
the host's work of a step (capacity, launches, delivery, admission) runs
while the device decodes.  Draws are keyed by (seed, position), so the
tokens are the synchronous loop's; an admission, a fault, an eviction or
a hand-off first ends the pass in flight (docs/serving.md, "The step
loop").

Everything host-side here is orchestration over device arrays; the only
jax entry points are the compiled step programs, so the engine runs
bit-deterministically on the CPU mesh (``JAX_PLATFORMS=cpu``) and
unchanged on TPU.
"""
from __future__ import annotations

import functools
import inspect
import time
import weakref
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.autograd import no_grad
from paddle_tpu.observability import (TraceContext, current_context,
                                      note_aot_compile, span)
from paddle_tpu.core.dispatch import apply
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.nn.paged_attention import PageAllocator
from paddle_tpu.quantization.kv_cache import resolve_kv_cache_dtype
from paddle_tpu.resilience.faultinject import fire as _fire
from paddle_tpu.resilience.faultinject import note_recovery
from paddle_tpu.resilience.health import HealthMonitor
from paddle_tpu.serving.generation import EXPERT_STATS, make_generation
from paddle_tpu.serving.kv_pool import make_page_pool
from paddle_tpu.serving.metrics import EngineMetrics
from paddle_tpu.serving.request import (GenerationResult, Request,
                                        RequestState, SamplingParams)
from paddle_tpu.serving.sampler import sampler_path
from paddle_tpu.serving.scheduler import (AdmissionRejected, Scheduler,
                                          default_buckets)

__all__ = ["AdmissionRejected", "EngineConfig", "LLMEngine",
           "PagedKVContext"]


class EngineConfig:
    """Sizing and shape-bucketing knobs for :class:`LLMEngine`.

    - `max_num_seqs`: decode batch width B (slots).
    - `page_size` / `num_pages`: shared-pool geometry.  The default pool
      holds every slot at `max_model_len` (no preemption pressure);
      size it DOWN to oversubscribe memory and exercise preemption.
    - `prefill_buckets`: the closed set of padded prompt shapes; the
      engine never compiles any other prefill width.
    - `eos_token_id`: default stop token for requests that don't set one.
    - `max_queue_depth`: bounded admission — `add_request` past this
      waiting-queue depth raises :class:`AdmissionRejected` (explicit
      backpressure) instead of queueing unboundedly.
    - `crash_safe_decode`: a decode-step exception evicts-and-requeues
      the offending request (replayed token-identically) instead of
      killing the engine.
    - `health_*`: thresholds for the HEALTHY→DEGRADED→DRAINING state
      machine driven by live page-pool occupancy; DRAINING rejects new
      admissions until pressure falls.
    - `mesh`: tp-sharding groundwork — a ``jax.sharding.Mesh`` (or a
      ``{"tp": n}`` dict resolved over the first n devices) over which
      the engine shards the per-layer paged KV pools along the HEAD
      axis and the weights along their trailing hidden-multiple axis;
      every program lowers as one SPMD computation over the mesh.
      `num_heads` must divide by the tp extent.
    - `kv_cache_dtype`: None (pools stored at `dtype`) or a
      quantization code dtype ("int8", "fp8_e4m3", "fp8_e5m2") — the
      per-layer pools become
      per-page-scaled ``(codes, scales)`` pairs
      (paddle_tpu/quantization/kv_cache.py; docs/quantization.md has
      the storage format and the tolerance contract).  Activations and
      logits stay at `dtype`; only KV storage narrows.
    - `guard`: the serving half of the training sentinel
      (docs/resilience.md "Numerics sentinel") — the decode program
      additionally returns a per-slot anomaly flag pair (non-finite
      logits row; quantized-KV page-scale overflow) computed in-trace,
      and a flagged request is evicted-and-requeued through the
      crash-safe-decode path instead of poisoning the shared pools.
      After ``guard_requeue_limit`` guard evictions the request
      finishes with ``finish_reason="anomaly"`` (a deterministic
      poison would otherwise replay forever).  ``guard_scale_limit``
      additionally bounds quantized page scales (None = finite-only).
    """

    def __init__(self, max_num_seqs=8, page_size=16, max_model_len=256,
                 num_pages=None, prefill_buckets=None,
                 growth_reserve_pages=1, eos_token_id=None,
                 dtype=jnp.float32, finished_retention=1024,
                 max_queue_depth=None, crash_safe_decode=True,
                 health_degraded_at=0.85, health_drain_at=0.97,
                 health_recover_at=0.70, mesh=None, kv_cache_dtype=None,
                 guard=False, guard_scale_limit=None,
                 guard_requeue_limit=2):
        if max_num_seqs < 1:
            raise ValueError("max_num_seqs must be >= 1")
        self.max_num_seqs = int(max_num_seqs)
        self.page_size = int(page_size)
        self.max_model_len = int(max_model_len)
        self.max_pages_per_seq = -(-self.max_model_len // self.page_size)
        if num_pages is None:
            num_pages = self.max_num_seqs * self.max_pages_per_seq + 1
        self.num_pages = int(num_pages)
        if prefill_buckets is None:
            prefill_buckets = default_buckets(self.max_model_len)
        buckets = tuple(sorted(int(b) for b in prefill_buckets))
        if not buckets or buckets[-1] > self.max_model_len:
            raise ValueError(
                f"prefill_buckets {buckets} must be non-empty and "
                f"<= max_model_len {self.max_model_len}")
        self.prefill_buckets = buckets
        self.growth_reserve_pages = int(growth_reserve_pages)
        self.eos_token_id = eos_token_id
        self.dtype = dtype
        # finished Request objects kept for post-hoc inspection via
        # `engine.finished_requests`; oldest are dropped past this cap
        # so a long-running step() loop cannot grow without bound
        self.finished_retention = int(finished_retention)
        self.max_queue_depth = (int(max_queue_depth)
                                if max_queue_depth is not None else None)
        self.crash_safe_decode = bool(crash_safe_decode)
        self.health_degraded_at = float(health_degraded_at)
        self.health_drain_at = float(health_drain_at)
        self.health_recover_at = float(health_recover_at)
        self.mesh = mesh                 # Mesh | {"tp": n} | None
        # resolve eagerly so a typo'd dtype fails at config build, not
        # first step; the spec itself is re-derived by the engine
        self.kv_cache_dtype = (None if kv_cache_dtype is None
                               else resolve_kv_cache_dtype(
                                   kv_cache_dtype).name)
        self.guard = bool(guard)
        self.guard_scale_limit = (float(guard_scale_limit)
                                  if guard_scale_limit is not None
                                  else None)
        self.guard_requeue_limit = int(guard_requeue_limit)

    @property
    def compile_bound(self):
        """Declared ceiling on XLA compiles for the engine's lifetime:
        one prefill per bucket + one decode + two sampler widths."""
        return len(self.prefill_buckets) + 3


class PagedKVContext:
    """The cache-aware attention hook handed to ``model(..., kv_ctx=)``.

    Lives only INSIDE a traced step function: it carries the traced
    per-layer pool arrays and a layer cursor; each attention layer calls
    :meth:`attend` exactly once per forward.

    - mode "prefill": dense causal attention over the (padded) prompt —
      the padded tail only pollutes its own discarded rows — plus a
      batched scatter of the real tokens' K/V into the pages.
    - mode "decode": one-token append + attention over the row's pages
      at its own length (ragged).

    What is written and how it is read back is `pool`'s (serving/
    kv_pool.py: the engine's cache kind).  A model that declares a
    LATENT cache calls :meth:`latent_prefill` / :meth:`latent_decode` in
    :meth:`attend`'s place (its pools are ``k_pools`` alone); a layer
    that declares a per-slot STATE calls :meth:`recur` (`slot`: the
    admitted slot, a prefill program's extra operand for such a pool);
    a model with experts reports each expert layer's load through
    :meth:`note_expert_counts`.
    """

    def __init__(self, pool, k_pools, v_pools, tables, lens, mode,
                 slot=None):
        self.pool = pool
        self.k_pools = list(k_pools)
        self.v_pools = list(v_pools)
        self.tables = tables
        self.lens = lens
        self.mode = mode
        self.slot = slot
        self._layer = 0
        self.expert_counts = []      # one traced [experts] int32 a layer
        # one bool a grouped expert product, at trace time: the kernel?
        self.grouped_products = []

    def _next_layer(self):
        li = self._layer
        self._layer += 1
        if li >= len(self.k_pools):
            raise RuntimeError(
                f"model has more attention layers ({li + 1}+) than the "
                f"engine allocated pools for ({len(self.k_pools)})")
        return li

    def latent_prefill(self, q, k, v, rows):
        """Prefill of a latent-cache layer, expanded form: dense causal
        attention of ``q`` / ``k [b, s, H, d_qk]`` and ``v [b, s, H,
        d_v]`` (Tensors) -> ``[b, s, H, d_v]``; the prompt's latent
        ``rows [b, s, w]`` are scattered into this layer's pool."""
        li = self._next_layer()

        def fn(qv, kv, vv, rv):
            out, self.k_pools[li] = self.pool.prefill(
                qv, kv, vv, rv, self.k_pools[li], self.tables, self.lens)
            return out

        return apply(fn, q, k, v, rows)

    def latent_decode(self, q, rows, rank, scale):
        """Decode of a latent-cache layer, absorbed form: append each
        slot's new row ``rows [b, 1, w]``, then attend ``q [b, 1, H, w]``
        (``[q~ | q_r]``, unscaled) over the slot's live rows; the sum is
        over a row's first ``rank`` columns -> ``[b, 1, H, rank]``."""
        li = self._next_layer()

        def fn(qv, rv):
            out, self.k_pools[li] = self.pool.decode(
                qv, rv, rank, scale, self.k_pools[li], self.tables,
                self.lens)
            return out

        return apply(fn, q, rows)

    def recur(self, fn, *inputs):
        """A state layer's one hook: ``fn(conv, ssm, lens, *values) ->
        (out, conv', ssm')`` runs on this layer's per-slot state (every
        slot's in decode; a fresh zero state in prefill) and what it
        hands back is stored (in place; at the admitted slot).  Returns
        ``out`` as a Tensor."""
        li = self._next_layer()

        def run(*values):
            out, self.k_pools[li], self.v_pools[li] = self.pool.recur(
                fn, self.k_pools[li], self.v_pools[li], self.lens,
                self.slot, values)
            return out

        return apply(run, *inputs)

    def note_expert_counts(self, counts):
        """An expert layer's tokens per expert in this forward (traced
        ``[experts]`` int32), for the engine's routing counters."""
        self.expert_counts.append(counts)

    def expert_stats(self):
        """``[experts_hit, expert_tokens_max, kernel_products, products]``
        int32: experts that got at least one token, summed over layers,
        and the heaviest expert's tokens in the worst layer — padding
        rows of the batch or bucket included, as the program routed them
        —, then how many of the program's grouped expert products took
        the Pallas kernel, of how many (constants of the trace)."""
        counts = jnp.stack(self.expert_counts)               # [L, E]
        took = self.grouped_products
        return jnp.stack([jnp.sum(counts > 0), jnp.max(counts),
                          sum(took), len(took)]).astype(jnp.int32)

    def attend(self, q, k, v):
        """q/k/v: Tensor [b, s, n_head, head_dim] -> Tensor same shape
        (attention output); writes this layer's K/V into its pools."""
        li = self._next_layer()
        step = self.pool.layer_step(li, self.mode, self.slot)

        def fn(qv, kv, vv):
            out, self.k_pools[li], self.v_pools[li] = step(
                qv, kv, vv, self.k_pools[li], self.v_pools[li],
                self.tables, self.lens)
            return out

        return apply(fn, q, k, v)


class LLMEngine:
    """Continuous-batching engine over any kv_ctx-aware decoder model.

    The model contract (`models/gpt.py` is the reference attach point):

    - ``model.config`` exposes ``num_layers``, ``num_heads``,
      ``hidden_size`` (head_dim = hidden_size // num_heads);
    - ``model(input_ids, position_ids=..., kv_ctx=...)`` returns
      ``[b, s, vocab]`` logits, with every attention layer delegating to
      ``kv_ctx.attend(q, k, v)`` when a context is passed;
    - optionally ``model.kv_cache_spec()`` declares what a layer caches.
      Absent (GPT): a K and a V pool a layer of ``num_heads x head_dim``.
      ``{"kind": "latent", "row_width": w, "value_width": r,
      "num_layers": n}``
      (``models/deepseek_v3.py``): ONE pool a layer of row pages
      ``[num_pages, page_size, w padded to lane tiles]``, written and read
      through ``kv_ctx.latent_prefill`` / ``latent_decode``; such a model
      takes ``logits_positions=`` in prefill (the head on one position a
      row) and, if it has expert layers (``num_expert_layers``), reports
      their load through ``kv_ctx.note_expert_counts``.  The latent pool
      is plain and on one device: ``kv_cache_dtype`` and a multi-device
      ``mesh`` refuse it by name.
      ``{"kind": "layers", "layers": [...]}``
      (``models/granitemoehybrid.py``): a declaration PER LAYER — ``kv``
      with its own ``num_heads`` / ``head_dim`` (and ``query_heads``,
      ``scale`` for grouped queries), read through ``kv_ctx.attend``, or
      ``state`` (``conv``, ``ssm`` shapes), a per-SLOT recurrent state
      reached through ``kv_ctx.recur``; a prefill overwrites its slot's
      state, decode advances it in place, the hand-off carries it.

    The declaration and ``EngineConfig(kv_cache_dtype=, dtype=, mesh=)``
    pick ONE pool object (serving/kv_pool.py) that owns the cache's
    format: entry shapes, sharding, the traced write and read, the
    hand-off blocks and the fingerprint's attention term.  The engine
    keeps the arrays, the allocator, the scheduler and the programs.

    Public surface: :meth:`add_request`, :meth:`step`, :meth:`generate`,
    :attr:`metrics`, :meth:`shutdown`.
    """

    def __init__(self, model, config=None, metrics_name=None,
                 program_cache=None, clock=None):
        self.config = config or EngineConfig()
        cfg = self.config
        self._model = model
        model.eval()
        mc = model.config
        self._num_heads = int(mc.num_heads)
        self._head_dim = int(mc.hidden_size) // int(mc.num_heads)
        if cfg.max_model_len > int(getattr(mc, "max_seq_len",
                                           cfg.max_model_len)):
            raise ValueError(
                f"max_model_len {cfg.max_model_len} exceeds the model's "
                f"max_seq_len {mc.max_seq_len}")

        self._params = {k: t._value for k, t in model.state_dict().items()}
        self._init_mesh(cfg.mesh)
        if self._mesh is not None:
            self._params = {k: jax.device_put(
                v, self._param_sharding(v))
                for k, v in self._params.items()}
        elif self._device is not None:
            self._params = {k: jax.device_put(v, self._device)
                            for k, v in self._params.items()}

        B, P = cfg.max_num_seqs, cfg.max_pages_per_seq
        # the cache kind, chosen once; the arrays are engine state
        self._pool = make_page_pool(model, cfg, self._mesh)
        self._k_pools, self._v_pools = self._pool.allocate(self._device)
        # the kind of generation, chosen once (serving/generation.py)
        self._gen = make_generation(model, cfg)
        # a pass launched and not fetched (`generation.Pass`): the kind's
        # passes run ahead of the host unless the guard reads the host
        # between them, or the pool keeps a state by slot — a pass writes
        # it in place, so a hand-off could not take back a row of the pass
        # in flight; tests reach the synchronous loop by clearing
        # `_run_ahead`
        self._run_ahead = (self._gen.runs_ahead and not cfg.guard
                           and not self._pool.state_layers)
        self._ahead = None
        self._moe_layers = int(getattr(model, "num_expert_layers", 0))
        self._moe_experts = int(getattr(mc, "n_routed_experts", 0))
        self._moe_top_k = int(getattr(mc, "num_experts_per_tok", 0))
        self._moe_stats = None       # the last program's expert_stats
        # a model whose forward takes `logits_positions=` runs its head
        # on the last real prompt token alone
        self._head_on_last = "logits_positions" in inspect.signature(
            model.forward).parameters
        self._tables = np.zeros((B, P), np.int32)      # host-canonical
        self._lens = np.zeros((B,), np.int32)          # host-canonical
        self._alloc = PageAllocator(cfg.num_pages, B, P)
        self._slots = [None] * B                       # Request | None

        self.scheduler = Scheduler(cfg.prefill_buckets, cfg.page_size,
                                   cfg.growth_reserve_pages,
                                   max_queue_depth=cfg.max_queue_depth)
        from paddle_tpu.observability.metrics import next_instance_label
        # a monotonic default label, never id()-derived: a reused id
        # after GC would silently merge this engine's registry metrics
        # into a dead engine's accumulated totals
        self._metrics_name = (metrics_name
                              or next_instance_label("serving.engine"))
        # the engine's histograms/compile counter live in the shared
        # observability registry under this engine's label — the
        # snapshot-source registration below is the coarse view of the
        # SAME instruments, so the two can never diverge
        # `clock` injects the engine's whole timebase (arrive_t stamps,
        # deadline TTLs, TTFT/ITL histograms — everything reads
        # metrics.clock): the virtual-time traffic driver passes a
        # VirtualClock so latency accounting is deterministic; None =
        # wall clock, exactly as before
        self.metrics = (EngineMetrics(clock=clock,
                                      name=self._metrics_name)
                        if clock is not None
                        else EngineMetrics(name=self._metrics_name))
        self.metrics.compile_bound = cfg.compile_bound
        self.metrics.pages_total = cfg.num_pages - 1   # page 0 reserved
        self.metrics.state_pool_bytes = self._pool.state_nbytes
        if self._pool.window_layers:
            self.metrics.window_pool_bytes = self._pool.window_nbytes
        self.metrics.block_length = self._gen.block_length
        # health state machine over live page-pool occupancy; the gauge
        # is EngineMetrics-owned so its registry lifecycle matches
        self.health = HealthMonitor(
            degraded_at=cfg.health_degraded_at,
            drain_at=cfg.health_drain_at,
            recover_at=cfg.health_recover_at,
            gauge=self.metrics.health_state)
        self._decode_fault_streak = 0

        # AOT program cache (serving/aot_cache.py): a warm boot loads
        # every program this engine would compile instead of compiling
        # it — the whole-program-compilation-as-deployment-artifact
        # model.  A str is a cache directory; None disables.
        if isinstance(program_cache, str):
            from paddle_tpu.serving.aot_cache import AOTProgramCache
            program_cache = AOTProgramCache(program_cache)
        self._program_cache = program_cache
        self._program_fp = None
        if program_cache is not None:
            from paddle_tpu.serving.aot_cache import engine_fingerprint
            self._program_fp = engine_fingerprint(
                mc, cfg, self._params, self._mesh,
                attention=self.attention_path, experts=self.experts_path)

        self._compiled = {}
        self._requests = {}          # live (queued or running) only
        # finished requests move here (bounded by finished_retention);
        # generate() drains its own, step()-loop users may inspect/pop
        self.finished_requests = OrderedDict()
        self._next_id = 0

        from paddle_tpu import profiler
        # weak registration: a dropped engine (no shutdown()) must stay
        # collectable and self-evict from the registry on the next report
        mref = weakref.ref(self.metrics)
        name = self._metrics_name

        def _snapshot():
            m = mref()
            if m is None:
                # instruments are released by the EngineMetrics GC
                # finalizer; here only the source entry is evicted —
                # and only if it is still OURS (a newer engine may have
                # re-registered the same name)
                from paddle_tpu.observability.metrics import registry
                registry().unregister_source(name, expected=_snapshot)
                return {"error": "engine collected"}
            return m.snapshot()

        self._snapshot_fn = _snapshot
        profiler.register_metrics_source(name, _snapshot)

    # ------------------------------------------------- mesh groundwork
    def _init_mesh(self, mesh):
        """Resolve EngineConfig.mesh into (mesh, shardings).

        tp groundwork (ROADMAP item 3): the paged KV pools shard as
        their kind says (serving/kv_pool.py: along the HEAD axis) and
        every other operand is either mesh-replicated or weight-sharded
        by :meth:`_param_sharding`; all programs then lower as SPMD
        computations over the mesh.
        A ``{"tp": n}`` dict builds a mesh over the first n devices
        (virtual CPU devices in tests, real chips on TPU).

        A mesh of ONE device is not an SPMD program: it resolves to the
        off-mesh engine pinned to that device (``self._device``) — how
        the router puts replica i on chip i.  ``mesh=None`` leaves
        placement to JAX's default device.
        """
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        if isinstance(mesh, dict):
            axes = tuple(mesh.keys())
            shape = tuple(int(s) for s in mesh.values())
            n = 1
            for s in shape:
                n *= s
            devices = jax.devices()
            if n > len(devices):
                raise ValueError(
                    f"mesh {dict(mesh)} needs {n} devices but only "
                    f"{len(devices)} are visible")
            mesh = Mesh(np.asarray(devices[:n]).reshape(shape), axes)
        self._device = None
        if mesh is not None and mesh.devices.size == 1:
            self._device = mesh.devices.flat[0]
            mesh = None
        if mesh is None:
            self._mesh = None
            self._repl_sharding = None
            return
        tp = int(mesh.shape.get("tp", 1))
        if tp > 1 and self._num_heads % tp:
            raise ValueError(
                f"num_heads {self._num_heads} must divide by the tp "
                f"extent {tp} to shard KV pools along the head axis")
        self._mesh = mesh
        self._repl_sharding = NamedSharding(mesh, PartitionSpec())

    def _param_sharding(self, arr):
        """Head-axis weight sharding heuristic: shard the LAST axis
        whose extent is a multiple of hidden (= heads * head_dim) over
        tp — column-parallel projections and embeddings — and replicate
        everything else (LN scales, biases, scalar state).  A
        best-effort groundwork rule: any consistent choice is
        numerically a relayout, and GSPMD inserts the collectives."""
        from jax.sharding import NamedSharding, PartitionSpec
        tp = int(self._mesh.shape.get("tp", 1))
        hidden = self._num_heads * self._head_dim
        if tp > 1 and getattr(arr, "ndim", 0) >= 2:
            for ax in range(arr.ndim - 1, -1, -1):
                d = int(arr.shape[ax])
                if d and d % hidden == 0 and (d // tp) % (
                        self._head_dim) == 0:
                    spec = [None] * arr.ndim
                    spec[ax] = "tp"
                    return NamedSharding(self._mesh,
                                         PartitionSpec(*spec))
        return self._repl_sharding

    def _place(self, value, sharding=None):
        """Device placement for program operands: this engine's device
        off-mesh (JAX's default when it was given none); an explicit
        mesh placement (replicated by default) on the mesh, so every
        input of an SPMD program lives on the same device set."""
        if not isinstance(value, jax.Array):
            value = np.asarray(value)
        if self._mesh is None:
            return jax.device_put(value, self._device)
        return jax.device_put(
            value, sharding if sharding is not None else self._repl_sharding)

    @property
    def attention_path(self):
        """What the decode program's attention was built from — the
        AOT fingerprint's term for it: the Pallas kernel at its
        revision, or the XLA composition (the pool kind's to say)."""
        return self._pool.attention_path + self._gen.path

    @property
    def experts_path(self):
        """What a model with expert layers builds its grouped expert
        products from (``distributed.moe.experts_path()``), the AOT
        fingerprint's term for them; None for a model without."""
        if not self._moe_layers:
            return None
        from paddle_tpu.distributed.moe import experts_path
        return experts_path()

    @property
    def program_fingerprint(self):
        """The AOT-cache fingerprint (None when no cache is attached):
        model config + param tree + engine geometry + mesh + jax/backend
        versions — docs/serving.md 'AOT program cache' has the schema."""
        return self._program_fp

    # ------------------------------------------------------------ API
    def _resolve_params(self, sampling_params):
        """Fill in the engine-level eos default."""
        sp = sampling_params or SamplingParams(
            eos_token_id=self.config.eos_token_id)
        if sp.eos_token_id is None and self.config.eos_token_id is not None:
            sp = SamplingParams(
                max_new_tokens=sp.max_new_tokens,
                temperature=sp.temperature, top_k=sp.top_k,
                top_p=sp.top_p, seed=sp.seed,
                eos_token_id=self.config.eos_token_id,
                denoising_steps=sp.denoising_steps, remasking=sp.remasking,
                confidence_threshold=sp.confidence_threshold)
        return sp

    def _validate_request(self, prompt, sp):
        """Raise ValueError unless (prompt, sp) is servable end to end —
        called BEFORE anything is enqueued, so a bad request can never
        strand earlier ones in the queue."""
        if not prompt:
            raise ValueError("prompt must contain at least one token")
        self._gen.check_params(sp)
        total_max = self._gen.positions_needed(len(prompt),
                                               sp.max_new_tokens)
        if total_max > self.config.max_model_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({sp.max_new_tokens}) = {total_max} exceeds "
                f"max_model_len {self.config.max_model_len}")
        # the WORST-CASE replay length must be bucketable, not just the
        # bare prompt: an eviction after g generated tokens (g can reach
        # max_new_tokens - 1) replays prompt + g through prefill
        self.scheduler.bucket_for_len(len(prompt) + sp.max_new_tokens - 1)
        # the request must be SERVABLE alone on an empty pool: its final
        # length's pages, and — the admission gate's view — its worst
        # replay length plus the scheduler's growth reserve (otherwise
        # add_request accepts work that deadlocks the queue forever)
        need_total = max(
            self._alloc.pages_needed(total_max, self.config.page_size),
            self.scheduler.pages_for_prompt(total_max - 1))
        if need_total > self.config.num_pages - 1:
            raise ValueError(
                f"request needs up to {need_total} pages (incl. the "
                f"admission growth reserve) but the pool only has "
                f"{self.config.num_pages - 1}")

    def add_request(self, prompt_token_ids, sampling_params=None,
                    stream=None):
        """Queue one request; returns its request id.  Admission happens
        at the next :meth:`step` boundary.  Raises
        :class:`AdmissionRejected` under backpressure (waiting queue at
        `max_queue_depth`, or health DRAINING)."""
        sp = self._resolve_params(sampling_params)
        prompt = [int(t) for t in prompt_token_ids]
        self._validate_request(prompt, sp)
        if not self.health.admitting:
            self.metrics.requests_rejected += 1
            raise AdmissionRejected(
                "draining",
                f"engine {self._metrics_name} page-pool pressure "
                f"{self.health.last_pressure:.2f}")
        rid = f"req-{self._next_id}"
        req = Request(rid, prompt, sp, arrival_index=self._next_id,
                      stream=stream)
        # distributed-trace identity: the router installs the request's
        # TraceContext ambiently (use_context) around this call — local
        # single-engine use leaves it None and nothing changes
        req.trace = current_context()
        req.arrive_t = self.metrics.clock()
        if sp.deadline_s is not None:
            req.deadline_t = req.arrive_t + sp.deadline_s
        try:
            self.scheduler.enqueue(req)
        except AdmissionRejected:
            self.metrics.requests_rejected += 1
            raise
        self._next_id += 1
        self._requests[rid] = req
        self.metrics.requests_received += 1
        return rid

    def adopt_request(self, prompt_token_ids, sampling_params=None,
                      generated_token_ids=(), stream=None, streamed=None,
                      arrive_t=None, arrival_index=None):
        """Router failover hook: enqueue a request that already
        generated tokens on ANOTHER replica.  The adopted request
        enters at the queue FRONT in the evicted-replay posture —
        ``generated_token_ids`` ride along in ``replay_token_ids``, the
        replay prefill reconstructs the KV cache, and the (seed,
        absolute-position) sampler regenerates the continuation
        token-identically — so a replica crash or drain migrates work
        with zero data loss and zero token divergence.

        `streamed` marks how many tokens the ORIGIN already delivered
        to the stream callback (default: all of `generated_token_ids`),
        so the new replica never re-streams them.  `arrive_t` carries
        the ORIGINAL arrival time (same `metrics.clock` timebase) so a
        `deadline_s` TTL keeps counting from first arrival instead of
        restarting on every migration, and `arrival_index` carries the
        caller's global age ordering so the fleet-oldest request does
        not become this engine's freshest — and therefore preferred —
        LIFO preemption victim.  Raises
        :class:`AdmissionRejected` while this engine is DRAINING, and
        ``ValueError`` when the replayed request could never be served
        here — both leave the request with the caller."""
        sp = self._resolve_params(sampling_params)
        prompt = [int(t) for t in prompt_token_ids]
        generated = [int(t) for t in generated_token_ids]
        self._validate_request(prompt, sp)
        if len(generated) >= sp.max_new_tokens:
            raise ValueError(
                f"request already finished ({len(generated)} of "
                f"{sp.max_new_tokens} tokens) — nothing to adopt")
        if not self.health.admitting:
            self.metrics.requests_rejected += 1
            raise AdmissionRejected(
                "draining",
                f"engine {self._metrics_name} page-pool pressure "
                f"{self.health.last_pressure:.2f}")
        rid = f"req-{self._next_id}"
        req = Request(rid, prompt, sp,
                      arrival_index=(self._next_id if arrival_index
                                     is None else int(arrival_index)),
                      stream=stream)
        req.output_token_ids = generated
        req.fixed_at = [None] * len(generated)
        req._streamed = len(generated) if streamed is None \
            else min(int(streamed), len(generated))
        # adopted == evicted-elsewhere: requests_admitted/ttft are the
        # ORIGIN replica's events, not this one's
        req.num_evictions = 1
        req.trace = current_context()
        req.arrive_t = (self.metrics.clock() if arrive_t is None
                        else float(arrive_t))
        # resume latency: adoption on THIS engine to its first token
        # (the ttft_decode stage; absent for never-migrated requests)
        req._resume_t = self.metrics.clock()
        if sp.deadline_s is not None:
            req.deadline_t = req.arrive_t + sp.deadline_s
        self.scheduler.requeue_front(req)
        self._next_id += 1
        self._requests[rid] = req
        self.metrics.requests_adopted += 1
        with span("serving.adopt", ctx=req.trace, request=rid,
                  generated=len(generated)):
            pass
        return rid

    def release_waiting(self):
        """Router drain hook: withdraw every still-QUEUED request
        (freshly waiting or evicted-and-requeued — none own slots or
        pages) and hand the Request objects to the caller, which now
        owns their fate (typically ``adopt_request`` on another
        replica).  Running requests are untouched: their pages are
        local, so they finish here."""
        reqs = self.scheduler.drain_waiting()
        for r in reqs:
            self._requests.pop(r.request_id, None)
        if reqs:
            with span("serving.release_waiting", count=len(reqs)):
                pass
        return reqs

    # ------------------------------------- disaggregated page handoff
    def export_page_state(self, request_id, release=True):
        """Disaggregated prefill→decode hook: snapshot one RUNNING
        request's KV pages + scheduler state into a host dict a DECODE
        engine can :meth:`import_page_state` — the page-moving
        counterpart of token-only adoption, for when re-running prefill
        on the target is the cost being disaggregated away.

        The payload carries, per layer, the request's owned pages as
        the pool kind encodes them (``self._pool.export``: named blocks
        a layer), plus prompt/generated tokens, sampling params, stream
        watermark, deadline AGE (``metrics.clock`` is per-process — the
        absolute ``arrive_t`` never crosses a process boundary), and
        the pool geometry the importer validates against.  With
        `release` (default) the request leaves this engine entirely —
        slot, pages and live-table entry — so prefill workers stay
        empty-handed between handoffs.  A pass in flight is discarded
        first: what is exported is the state the last step left."""
        self._gen.check_handoff()
        self._drain("handoff")
        req = self._requests.get(request_id)
        if req is None or req.slot is None:
            raise ValueError(
                f"request {request_id!r} is not running here — only a "
                f"RUNNING (slot-owning) request has pages to export")
        slot = req.slot
        L = int(self._lens[slot])
        pages = list(self._alloc.owned_pages(slot))
        sp = req.sampling_params
        state = {
            "prompt_token_ids": list(req.prompt_token_ids),
            "output_token_ids": list(req.output_token_ids),
            "streamed": int(req._streamed),
            "age_s": max(0.0, self.metrics.clock() - req.arrive_t),
            "arrival_index": int(req.arrival_index),
            "len": L,
            "sampling_params": {
                "max_new_tokens": sp.max_new_tokens,
                "temperature": sp.temperature,
                "top_k": sp.top_k, "top_p": sp.top_p, "seed": sp.seed,
                "eos_token_id": sp.eos_token_id,
                "deadline_s": sp.deadline_s,
            },
            "geometry": dict(self._pool.geometry),
            "layers": self._pool.export((self._k_pools, self._v_pools),
                                        pages, slot),
        }
        if req.trace is not None:
            # trace identity rides the handoff blob so the decode
            # engine's spans join the originating request's trace
            state["trace"] = req.trace.to_dict()
        with span("serving.page_export", ctx=req.trace,
                  request=request_id, pages=len(pages), tokens=L,
                  release=bool(release)):
            if release:
                req.transition(RequestState.EVICTED)
                self._release_slot(req)
                self._requests.pop(request_id, None)
        return state

    def import_page_state(self, state, stream=None):
        """Decode-side half of the disaggregated handoff: rebuild the
        exported request in THIS engine — allocate fresh pages, write
        the shipped KV blocks into the local pools (eager ``.at[]``
        scatter: no new compiled program, the bounded-compile contract
        is untouched), and enter the request directly at DECODE.  Token
        identity is inherited from the deterministic ``(seed, absolute
        position)`` sampler: the next sampled position is exactly where
        the prefill engine left off.  Returns the new request id.

        Raises ``ValueError`` on a geometry mismatch and
        :class:`AdmissionRejected` when no slot/pages are free or this
        engine is DRAINING (the exporter still holds the state dict and
        can retry elsewhere)."""
        self._gen.check_handoff()
        geo = state["geometry"]
        for k, want in self._pool.geometry.items():
            if geo.get(k) != want:
                raise ValueError(
                    f"page-state geometry mismatch on {k!r}: exporter "
                    f"{geo.get(k)!r} vs importer {want!r}")
        sp = SamplingParams(**state["sampling_params"])
        prompt = [int(t) for t in state["prompt_token_ids"]]
        generated = [int(t) for t in state["output_token_ids"]]
        self._validate_request(prompt, sp)
        L = int(state["len"])
        if L != len(prompt) + len(generated) - 1:
            raise ValueError(
                f"page-state cache length {L} does not match "
                f"prompt+generated-1 = "
                f"{len(prompt) + len(generated) - 1} (the newest "
                f"token's KV is written by the NEXT decode step)")
        if not self.health.admitting:
            self.metrics.requests_rejected += 1
            raise AdmissionRejected(
                "draining",
                f"engine {self._metrics_name} page-pool pressure "
                f"{self.health.last_pressure:.2f}")
        n_pages = self._pool.exported_pages(state["layers"])
        try:
            slot = self._slots.index(None)
        except ValueError:
            self.metrics.requests_rejected += 1
            raise AdmissionRejected(
                "no_slot", f"engine {self._metrics_name} has no free "
                f"decode slot for an imported request")
        if not self._alloc.can_allocate(slot, n_pages):
            self.metrics.requests_rejected += 1
            raise AdmissionRejected(
                "no_pages",
                f"engine {self._metrics_name} cannot allocate "
                f"{n_pages} pages for an imported request")
        rid = f"req-{self._next_id}"
        req = Request(rid, prompt, sp,
                      arrival_index=int(state.get(
                          "arrival_index", self._next_id)),
                      stream=stream)
        req.output_token_ids = generated
        req.fixed_at = [None] * len(generated)
        req._streamed = min(int(state.get("streamed", len(generated))),
                            len(generated))
        req.num_evictions = 1     # admitted/ttft were the exporter's
        req.trace = (TraceContext.from_dict(state.get("trace"))
                     or current_context())
        req.arrive_t = self.metrics.clock() - float(
            state.get("age_s", 0.0))
        req._resume_t = self.metrics.clock()
        if sp.deadline_s is not None:
            req.deadline_t = req.arrive_t + sp.deadline_s
        self._next_id += 1
        self._slots[slot] = req
        req.slot = slot
        pages = [page for _pos, page in self._alloc.allocate(slot,
                                                             n_pages)]
        for pos, page in enumerate(pages):
            self._tables[slot, pos] = page
        self._k_pools, self._v_pools = self._pool.import_(
            (self._k_pools, self._v_pools), np.asarray(pages),
            state["layers"], slot)
        self._lens[slot] = L
        req.transition(RequestState.PREFILL)
        req.transition(RequestState.DECODE)
        self._requests[rid] = req
        self.metrics.requests_adopted += 1
        with span("serving.page_import", ctx=req.trace, request=rid,
                  pages=n_pages, tokens=L):
            pass
        return rid

    def has_unfinished(self):
        return (self.scheduler.has_waiting()
                or any(r is not None for r in self._slots))

    # live admission telemetry — the same signals the step-boundary
    # scrape gauges export, read at the source so an in-process router
    # balancing a BURST of admissions between steps sees each one land
    @property
    def queue_depth(self):
        return self.scheduler.queue_depth

    @property
    def num_running(self):
        return sum(1 for r in self._slots if r is not None)

    @property
    def page_occupancy(self):
        total = self.config.num_pages - 1      # page 0 reserved
        if not total:
            return 0.0
        return (total - self._alloc.num_free_pages) / total

    def step(self):
        """One engine iteration: admit + prefill new requests at the
        step boundary, then one continuous-batched decode step.  Returns
        ``[(request_id, token_id, finished), ...]`` for tokens produced
        this step; a preemption surfaces as ``(request_id, None, False)``
        (the request re-enters the queue and will be replayed)."""
        events = []
        with span("serving.step", running=self.num_running,
                  waiting=self.queue_depth) as step_span:
            admitted = self._step_inner(events)
            step_span.set(admitted=admitted, tokens=sum(
                1 for _rid, tok, _fin in events if tok is not None))
        return events

    def _step_inner(self, events):
        with span("serving.gauges"):
            self._expire_deadlines(events)
        self._ahead = self._in_flight()
        with span("serving.admit"):
            admitted = self._admit(events)
        # the prefills' first-token fetch waited out the pass in flight.
        # Where slots are short (none left free, or requests still
        # waiting) its tokens come now and the step launches afresh, so
        # the admitted rows' second token comes this step, as in the
        # synchronous loop, and their slots free no later; else they join
        # the pass launched ahead, a step later, and the step stays short
        if admitted and (self.scheduler.has_waiting()
                         or not self._free_slot_count()):
            self._drain("prefill", events)
        running = [r for r in self._slots if r is not None]
        if running:
            self._decode_step(events)
        elif not admitted and self.scheduler.has_waiting() \
                and self.health.admitting:
            # (DRAINING holds the queue on purpose — not a deadlock)
            head = self.scheduler.peek()
            raise RuntimeError(
                f"scheduler deadlock: nothing running and request "
                f"{head.request_id} (prompt {len(head.replay_token_ids)} "
                f"tokens) cannot be admitted — the page pool "
                f"({self._alloc.num_free_pages} free) is too small")
        with span("serving.gauges"):
            self._refresh_gauges()
        return admitted

    def generate(self, prompts, sampling_params=None):
        """Sync facade: serve `prompts` (list of token-id lists) to
        completion; returns :class:`GenerationResult` per prompt in
        input order."""
        if prompts and isinstance(prompts[0], int):
            raise TypeError("generate expects a LIST of prompts "
                            "(each a list of token ids)")
        if isinstance(sampling_params, (list, tuple)):
            if len(sampling_params) != len(prompts):
                raise ValueError("one SamplingParams per prompt required")
            sps = list(sampling_params)
        else:
            sps = [sampling_params] * len(prompts)
        # all-or-nothing: validate the whole batch BEFORE enqueueing so
        # a bad prompt can't strand its predecessors in the queue
        pairs = [([int(t) for t in p], self._resolve_params(sp))
                 for p, sp in zip(prompts, sps)]
        for prompt, sp in pairs:
            self._validate_request(prompt, sp)
        rids = []
        try:
            for p, sp in pairs:
                rids.append(self.add_request(p, sp))
        except AdmissionRejected:
            # all-or-nothing under backpressure too: withdraw the
            # partial batch (no step() has run, so the withdrawn
            # requests own no slots or pages) instead of stranding it
            # in the bounded queue with no rids returned
            for r in rids:
                self.scheduler.withdraw(self._requests.pop(r))
            raise
        reqs = [self._requests[r] for r in rids]   # hold refs: _finish
        while self.has_unfinished():               # moves them out of
            self.step()                            # the live table
        for r in rids:
            self.finished_requests.pop(r, None)
        return [GenerationResult(req) for req in reqs]

    def shutdown(self):
        """Unregister from the profiler metrics registry and release
        this engine's claim on its registry-owned instruments (shared
        instruments survive until the last same-named engine goes).  A
        pass in flight is discarded."""
        self._drain("idle")
        from paddle_tpu.observability.metrics import registry
        registry().unregister_source(self._metrics_name,
                                     expected=self._snapshot_fn)
        self.metrics.release()

    # ----------------------------------------------------- deadlines
    def _expire_deadlines(self, events):
        """Step-boundary deadline sweep: queued requests past their TTL
        finish with reason "deadline"; running ones release their slot
        and pages first.  Deterministic — driven by `metrics.clock`
        and queue/slot order only."""
        now = self.metrics.clock()
        expired = self.scheduler.pop_expired(now)
        for slot in range(self.config.max_num_seqs):
            r = self._slots[slot]
            if r is not None and r.past_deadline(now):
                expired.append(r)
        for req in expired:
            with span("serving.deadline", request=req.request_id,
                      state=req.state.value,
                      overrun_s=round(now - req.deadline_t, 4)):
                self.metrics.requests_expired += 1
                self._finish(req, "deadline", now)
                req.deliver(finished=True)
                events.append((req.request_id, None, True))

    # ----------------------------------------------------- admission
    def _free_slot_count(self):
        return sum(1 for r in self._slots if r is None)

    def _admit(self, events):
        admitted = 0
        while True:
            req = self.scheduler.pop_admissible(
                self._free_slot_count(), self._alloc.num_free_pages)
            if req is None:
                break
            self._prefill(req, events)
            admitted += 1
        return admitted

    def _prefill(self, req, events):
        cfg = self.config
        t0 = self.metrics.clock()
        req.transition(RequestState.PREFILL)
        tokens = req.replay_token_ids
        L = len(tokens)
        # the positions this prefill stores (a kind may leave the tail of
        # the prompt to its first pass; none stored = no program runs)
        covered = self._gen.prefill_len(L)
        bucket = self.scheduler.bucket_for_len(covered) if covered else 0
        with span("serving.prefill", ctx=req.trace,
                  request=req.request_id, bucket=bucket,
                  tokens=L, **self._pool.prefill_attrs(covered, bucket),
                  **self._gen.prefill_attrs(covered)) as span_:
            self._prefill_inner(req, events, cfg, t0, tokens, covered,
                                bucket, span_)

    def _note_experts(self, span_, rows):
        """After a sample fetch that carried a program's expert stats:
        put them on the program's span and into the counters.  `rows`:
        the tokens the program routed in every expert layer."""
        if not self._moe_layers or self._moe_stats is None:
            return
        hit, tokens_max, took, products = (int(x) for x in self._moe_stats)
        self._moe_stats = None
        span_.set(experts_hit=hit, expert_tokens_max=tokens_max)
        # the same numbers also go down as a marker, which the benchmark's
        # readers read (a capture now keeps the span's exit attributes
        # too); `grouped_kernel`: the share of the program's grouped
        # products that took the Pallas kernel
        with span("serving.experts", experts_hit=hit,
                  expert_tokens_max=tokens_max, rows=rows,
                  layers=self._moe_layers,
                  grouped_kernel=took / products if products else 0.0):
            pass
        self.metrics.note_experts(
            rows * self._moe_top_k * self._moe_layers, tokens_max,
            rows * self._moe_top_k / self._moe_experts)

    def _prefill_inner(self, req, events, cfg, t0, tokens, covered, bucket,
                       span_):
        slot = self._slots.index(None)
        self._slots[slot] = req
        req.slot = slot

        need = self._alloc.pages_needed(covered, cfg.page_size)
        for pos, page in self._alloc.allocate(slot, need):
            self._tables[slot, pos] = page

        head = stats = ()
        if covered:
            fn = self._get_prefill(bucket)
            with span("serving.launch", program="prefill", bucket=bucket):
                ids = np.zeros((1, bucket), np.int32)
                ids[0, :covered] = tokens[:covered]
                pos_ids = np.arange(bucket, dtype=np.int32)[None, :]
                length = np.array([covered], np.int32)
                operands = (
                    self._place(self._tables[slot:slot + 1]),
                    self._place(ids), self._place(pos_ids),
                    self._place(length),
                    *(self._place(x) for x in self._pool.slot_operands(slot)))
                out = fn(self._params, self._k_pools, self._v_pools,
                         *operands)
            # what the kind's program puts first, the pools, the stats
            n = self._gen.prefill_heads
            head, stats = out[:n], out[n + 2:]
            self._k_pools, self._v_pools = out[n:n + 2]
        self._lens[slot] = covered
        # a slot's per-slot state, if the pool keeps one, is now this
        # request's: whatever ran there before is overwritten
        self.metrics.state_admits_total += bool(self._pool.state_layers)
        self._gen.admitted(self, req, slot, tokens, head, stats, span_,
                           bucket, t0, events)

    def _note_prefill(self, req, tokens, t0, now, ran=True):
        """An admission's counters (`ran`: a prefill program ran for it);
        for a fresh request the queue and prefill stages of its time to
        first token."""
        if ran:
            self.metrics.prefill_steps += 1
            self.metrics.prefill_step_s.observe(now - t0)
        self.metrics.prompt_tokens += tokens
        if req.num_evictions == 0:
            self.metrics.requests_admitted += 1
            # stage decomposition: queue-wait (arrival -> prefill start)
            # + prefill
            self.metrics.ttft_queue.observe(max(0.0, t0 - req.arrive_t))
            self.metrics.ttft_prefill.observe(max(0.0, now - t0))

    def _deliver(self, req, toks, fixed_at, now, events, gap=True):
        """`toks` reach `req` together, in order, at `now` (one stamp, ONE
        inter-token gap); delivery stops at the token that finishes the
        request."""
        if req.first_token_t is None and req.num_evictions == 0:
            self.metrics.ttft.observe(now - req.arrive_t)
        if gap and req.last_token_t is not None:
            self.metrics.inter_token.observe(now - req.last_token_t)
        for tok, at in zip(toks, fixed_at):
            req.append_token(tok, now=now, fixed_at=at)
            self._observe_resume(req, now)
            self.metrics.generated_tokens += 1
            self._post_token(req, events, now)
            if req.is_finished:
                break

    # -------------------------------------------------------- decode
    def _decode_step(self, events):
        with span("serving.decode", live=self.num_running,
                  kernel=self._pool.decode_kernel,
                  **self._pool.decode_attrs(self.num_running),
                  **self._gen.decode_attrs(self)) as span_:
            self._decode_step_inner(events, span_)

    def _decode_step_inner(self, events, span_):
        """One pass's handling: the pass in flight — or, with none, one
        launched now — gets a successor launched behind it (its rows' ids
        taken on the device from this pass's sampler output) before its
        own tokens are fetched and delivered.  A kind that cannot run
        ahead, or a pass whose successor cannot be launched yet, is
        fetched with none behind it: the next step launches afresh."""
        t0 = self.metrics.clock()
        done, self._ahead = self._ahead, None
        launched = []
        if done is None:
            done = self._launch(events)
            if done is None:
                return
            launched.append(done)
        if not self._run_ahead:
            self.metrics.drains["kind"] += 1
        else:
            self._ahead = self._launch(events, after=done)
            if self._ahead is not None:
                launched.append(self._ahead)
                self.metrics.passes_ahead += 1
        # `pages_live`: the pages the passes launched here read
        self.metrics.pages_live = sum(p.pages for p in launched)
        span_.set(pages_live=self.metrics.pages_live,
                  ahead=int(self._ahead is not None))
        if self._pool.window_layers:
            # `window_rows_live`: the ring rows of one window layer the
            # passes launched here read
            self.metrics.window_rows_live = sum(p.window_rows
                                                for p in launched)
            span_.set(window_rows_live=self.metrics.window_rows_live)
        self._gen.decoded(self, done, span_, t0, events)

    def _launch(self, events, after=None):
        """The capacity pass and the launch of one decode pass over the
        live slots (and, for a kind that samples at launch, its sampler);
        returns the `generation.Pass`, or None when none was launched.

        `after`: the pass in flight.  Each row it still serves is one
        position longer than the host knows and takes its id from
        `after`'s sampler output; a row whose token in `after` is its last
        is left out.  Such a pass is not launched — `after` is then
        fetched with none behind it, and the cause counted — where it
        would run over no row, where the next step admits a request (its
        prefill would wait out the pass, and its first pass would come a
        step late), where the pool would have to evict, or where a fault
        fires."""
        cfg = self.config
        B = cfg.max_num_seqs
        ahead = np.zeros((B,), np.int32)
        if after is not None:
            for s, r, ev in after.rows:
                ahead[s] = self._serving(s, r, ev)
        last = [s for s, r in enumerate(self._slots) if ahead[s] and len(
            r.output_token_ids) + 1 >= r.sampling_params.max_new_tokens]
        live = [(s, r) for s, r in enumerate(self._slots)
                if r is not None and s not in last]
        if after is not None and (not live or self._admission_due(len(last))):
            self.metrics.drains["prefill" if live else "idle"] += 1
            return None
        if not live:
            return None
        with span("serving.capacity") as capacity:
            grown = self._make_room(events, live, ahead, after is not None)
            capacity.set(grown=grown or 0)
        if grown is None:
            self.metrics.drains["evict"] += 1
            return None
        live = [(s, r) for s, r in live if self._slots[s] is r]
        if not live:
            return None
        rows = self._gen.rows
        in_pass = np.zeros((B,), np.bool_)
        in_pass[[s for s, _r in live]] = True
        lens = np.where(in_pass, self._lens + ahead, 0).astype(np.int32)
        pages = int(np.sum(-(-(lens[in_pass] + rows) // cfg.page_size)))
        fn = self._get_decode()
        fault = None
        with span("serving.launch", program="decode", width=B * rows):
            operands = self._gen.decode_operands(self, live, ahead, after)
            if cfg.guard:
                operands += (self._place(self._poison_vector(live)),)
            try:
                # chaos hook: `exception` faults here simulate a crashed
                # decode (payload `request_id` names the offender)
                _fire("serving.decode", step=self.metrics.decode_steps)
                out = fn(self._params, self._k_pools, self._v_pools,
                         self._place(np.where(in_pass[:, None],
                                              self._tables, 0)),
                         self._place(lens), *operands)
            except Exception as e:
                if not cfg.crash_safe_decode:
                    raise
                fault = e
        if fault is not None:
            self._recover_decode_fault(fault, events)
            if after is not None:
                self.metrics.drains["fault"] += 1
            return None
        stats = ()
        if self._moe_layers:
            *out, last = out
            stats = (last,)
        if cfg.guard:
            logits, self._k_pools, self._v_pools, flags = out
            live = self._quarantine_flagged(live, flags, events)
        else:
            logits, self._k_pools, self._v_pools = out
        self._decode_fault_streak = 0
        done = self._gen.launched(self, live, ahead, logits, stats, pages)
        done.window_rows = self._pool.live_rows(lens[in_pass])
        return done

    def _admission_due(self, ending):
        """Whether the next step admits the queue's head, into a slot free
        now or one of `ending` slots whose request ends with the pass in
        flight."""
        head = self.scheduler.peek()
        return head is not None and self.scheduler.admissible(
            head, self._free_slot_count() + ending,
            self._alloc.num_free_pages)

    def _serving(self, slot, req, evictions):
        """Whether `slot` still serves `req` as it did when a pass was
        launched over it (not finished, expired or evicted since)."""
        return self._slots[slot] is req and req.num_evictions == evictions

    def _in_flight(self):
        """The pass in flight, or None: one none of whose rows is served
        any more (each finished or expired) is dropped unfetched."""
        done = self._ahead
        if done is not None and not any(self._serving(s, r, ev)
                                        for s, r, ev in done.rows):
            self.metrics.drains["idle"] += 1
            done = None
        return done

    def _drain(self, cause, events=None):
        """End the pass in flight, so that the next is launched afresh:
        fetch it and deliver its tokens to `events` (an admission), or,
        outside a step (a hand-off, shutdown), discard it unfetched — the
        engine's state is then the one the last step left, and the next
        pass computes those tokens again (draws keyed by (seed, position),
        over the same K/V; a pool with a state by slot does not run
        ahead)."""
        done, self._ahead = self._in_flight(), None
        if done is None:
            return
        self.metrics.drains[cause] += 1
        if events is not None:
            with span("serving.drain", cause=cause) as span_:
                self._gen.decoded(self, done, span_, self.metrics.clock(),
                                  events)

    def _make_room(self, events, live, ahead, waits):
        """The capacity pass before a decode pass over `live` ``[(slot,
        request)]``, each row ``ahead[slot]`` positions longer than the
        host's length; returns the pages it allocated.  With `waits` (a
        pass launched ahead) it evicts nobody for want of pages: None
        where the pool would have to, or where a chaos plan evicted."""
        cfg = self.config
        grown = 0
        # chaos hook: injected pool exhaustion drives ONE deterministic
        # preemption round through the REAL victim-selection path (the
        # same code a genuinely dry pool exercises below)
        spec = _fire("serving.pool", step=self.metrics.decode_steps)
        if spec is not None and spec.kind == "pool_exhaust":
            for _ in range(int(spec.payload.get("victims", 1))):
                victim = self.scheduler.select_victim(
                    [r for r in self._slots if r is not None])
                if victim is None:
                    break
                self._evict(victim, events)
                note_recovery("serving.pool", "pool_exhaust",
                              victim=victim.request_id)
            if waits:
                return None
        # every live row must fit what this pass writes (one more token,
        # or its in-flight block)
        need = {s: self._alloc.pages_needed(
            int(self._lens[s] + ahead[s]) + self._gen.rows, cfg.page_size)
            for s, _r in live}
        # the pool running dry preempts the latest-arrived running request
        # (a pass launched ahead leaves that to the next synchronous pass,
        # which needs the pages granted here too)
        for slot, req in live:
            if self._slots[slot] is not req:
                continue                       # preempted meanwhile
            while not self._alloc.can_allocate(slot, need[slot]):
                if waits:
                    return None
                victim = self.scheduler.select_victim(
                    [r for r in self._slots if r is not None])
                if victim is None:
                    raise RuntimeError(
                        "paged pool exhausted with nothing left to "
                        "preempt")
                self._evict(victim, events)
                if victim is req:
                    break
            if self._slots[slot] is not req:
                continue                       # row preempted itself
            for pos, page in self._alloc.allocate(slot, need[slot]):
                self._tables[slot, pos] = page
                grown += 1
        return grown

    def _note_decode(self, t0):
        """A decode pass's counters; returns the stamp its tokens get."""
        now = self.metrics.clock()
        self.metrics.decode_steps += 1
        self.metrics.decode_step_s.observe(now - t0)
        return now

    def _poison_vector(self, live):
        """The guarded decode's injection operand: zeros in production;
        a ``serving.logits`` fault poisons the victim's row (nan_grad →
        NaN, bitflip → +inf) so detection is exercised through the REAL
        compiled program — deterministic, and the program never
        changes."""
        cfg = self.config
        poison = np.zeros((cfg.max_num_seqs, 1), np.float32)
        spec = _fire("serving.logits", step=self.metrics.decode_steps)
        if spec is not None and spec.kind in ("bitflip", "nan_grad") \
                and live:
            rid = spec.payload.get("request_id")
            if rid is not None:
                # request-targeted fault: if the target is no longer
                # live (finished/quarantined), the fault is spent —
                # never redirect the poison onto an innocent request
                victim = next((r for _s, r in live
                               if r.request_id == rid), None)
            else:
                victim = max((r for _s, r in live),
                             key=lambda r: r.arrival_index)
            if victim is not None:
                poison[victim.slot, 0] = (np.nan
                                          if spec.kind == "nan_grad"
                                          else np.inf)
        return poison

    def _quarantine_flagged(self, live, flags, events):
        """Guard verdicts -> evictions: every flagged live request is
        evicted-and-requeued (the crash-safe path — its replay prefill
        rebuilds clean pools from prompt + generated tokens, and its
        freed pages are rewritten before any read), EXCEPT a request
        already guard-evicted ``guard_requeue_limit`` times, which
        finishes with ``finish_reason="anomaly"`` (a deterministic
        poison must not replay forever).  Returns the surviving live
        list."""
        fl = np.asarray(flags)
        flagged = [(s, r) for s, r in live if fl[s].any()]
        if not flagged:
            return live
        from paddle_tpu.resilience.sentinel import note_anomaly
        now = self.metrics.clock()
        for s, r in flagged:
            kind = ("nan_logits" if fl[s, 0]
                    else "scale_overflow")
            note_anomaly(kind, "serving.decode",
                         step=self.metrics.decode_steps,
                         request=r.request_id)
            r.num_guard_evictions = getattr(
                r, "num_guard_evictions", 0) + 1
            self.metrics.guard_anomalies += 1
            with span("serving.guard", request=r.request_id, kind=kind,
                      evictions=r.num_guard_evictions):
                if r.num_guard_evictions > \
                        self.config.guard_requeue_limit:
                    self._finish(r, "anomaly", now)
                    r.deliver(finished=True)
                    events.append((r.request_id, None, True))
                else:
                    self._evict(r, events)
            note_recovery("serving.decode", kind,
                          request=r.request_id)
        return [(s, r) for s, r in live if self._slots[s] is r]

    def _recover_decode_fault(self, exc, events):
        """Crash-safe decode: a failed decode program left no state
        behind (pools/lens update only on success, page grows are
        idempotent), so the engine evicts-and-requeues the OFFENDING
        request and keeps serving.  The offender is the exception's
        `request_id` when it names one (injected faults, request-
        poisoned inputs), else the latest-arrived live request — the
        same deterministic victim order preemption uses.  Requeued, not
        killed: the replay prefill regenerates its tokens exactly, so
        recovery is token-identical for every surviving request.

        A full batch of consecutive faults (streak > max_num_seqs)
        means the fault is NOT request-local (hung device, poisoned
        weights) — rethrow rather than spin forever."""
        live = [r for r in self._slots if r is not None]
        self._decode_fault_streak += 1
        if not live or self._decode_fault_streak > self.config.max_num_seqs:
            raise exc
        rid = getattr(exc, "request_id", None)
        offender = next((r for r in live if r.request_id == rid), None)
        if offender is None:
            offender = max(live, key=lambda r: r.arrival_index)
        with span("serving.decode_fault", request=offender.request_id,
                  exc=type(exc).__name__, streak=self._decode_fault_streak):
            self._evict(offender, events)
        self.metrics.decode_fault_recoveries += 1
        note_recovery("serving.decode", "exception",
                      request=offender.request_id,
                      exc=type(exc).__name__)

    # ------------------------------------------------------ sampling
    def _sample(self, logits, reqs, width, carry=(), **kw):
        """The sampler over `logits`, one Request (or None: a padding
        row, a dead slot) a row or slot; the generation kind says what a
        row's key is and what comes back."""
        return self._gen.sample(self, logits, reqs, width, carry, **kw)

    def _run_sampler(self, width, logits, keys, temps, top_ks, top_ps,
                     carry=(), fetch=True):
        """One call of the ``sample/<width>`` program and its ONE blocking
        fetch.  `keys`: the per-row operands a draw's key is made of, as
        the generation kind orders them (seeds, positions, ...).  `carry`:
        the expert stats of the program that made `logits` (a model with
        expert layers), which ride the fetch into ``_moe_stats``.  Returns
        the fetched array less the stats; without `fetch`, the program's
        output as it stands on the device (:meth:`_fetch` reads it)."""
        # the searches this call's program will run: it branches on the
        # same three facts of the same operands
        draws, any_k, any_p = sampler_path(temps, top_ks, top_ps)
        path = ("greedy" if not draws else "top_k+top_p" if any_k and any_p
                else "top_k" if any_k else "top_p" if any_p else "draw")
        self.metrics.sampler_paths[path] += 1
        with span("serving.sample", width=width, path=path):
            fn = self._get_sampler(width)
            with span("serving.launch", program="sample", width=width):
                operands = (self._place(logits),
                            *(self._place(k) for k in keys),
                            self._place(temps), self._place(top_ks),
                            self._place(top_ps))
                res = fn(*operands, *carry)
            if not fetch:
                return res
            return self._fetch(res, carry)

    def _fetch(self, res, carry):
        """The blocking fetch of a sampler's output `res`: the array less
        the expert stats it carries (with `carry`), which go to
        ``_moe_stats``."""
        with span("serving.fetch"):
            out = np.asarray(res)
        if carry:
            self._moe_stats = out[-EXPERT_STATS:]
            out = out[:-EXPERT_STATS]
        return out

    # ------------------------------------------------- finish / evict
    def _observe_resume(self, req, now):
        """First token after an adoption/import on THIS engine closes
        the ttft_decode stage (resume latency of migrated work)."""
        if req._resume_t is not None:
            self.metrics.ttft_decode.observe(max(0.0,
                                                 now - req._resume_t))
            req._resume_t = None

    def _post_token(self, req, events, now):
        reason = req.should_stop()
        if reason is not None:
            self._finish(req, reason, now)
        req.deliver(finished=req.is_finished)
        events.append((req.request_id, req.output_token_ids[-1],
                       req.is_finished))

    def _finish(self, req, reason, now):
        req.finish_reason = reason
        req.transition(RequestState.FINISHED)
        if req.slot is not None:     # queued deadline expiry has none
            self._release_slot(req)
        req.finish_t = now
        self.metrics.requests_finished += 1
        self.metrics.e2e_latency.observe(now - req.arrive_t)
        if req.trace is not None:
            # the trace's terminal marker (fleettrace timelines key on
            # it) — recorded ONLY for traced requests, so untraced
            # engines see zero new spans
            with span("serving.finish", ctx=req.trace,
                      request=req.request_id, reason=reason,
                      tokens=len(req.output_token_ids)):
                pass
        # move out of the live table so a perpetual serving loop cannot
        # accumulate one Request (+ stream closure) per request served
        self._requests.pop(req.request_id, None)
        self.finished_requests[req.request_id] = req
        while len(self.finished_requests) > self.config.finished_retention:
            self.finished_requests.popitem(last=False)

    def _evict(self, req, events):
        """Deterministic preemption: free everything, requeue at the
        queue front; the replay prefill later reconstructs the cache
        from prompt + generated tokens (token-identical, see sampler)."""
        with span("serving.preempt", request=req.request_id,
                  generated=len(req.output_token_ids)):
            req.transition(RequestState.EVICTED)
            self._release_slot(req)
            req.num_evictions += 1
            self.metrics.requests_evicted += 1
            self.scheduler.requeue_front(req)
            events.append((req.request_id, None, False))

    def _release_slot(self, req):
        slot = req.slot
        self._alloc.release(slot)
        self._tables[slot, :] = 0
        self._lens[slot] = 0
        self._slots[slot] = None
        req.slot = None

    def _refresh_gauges(self):
        m = self.metrics
        m.queue_depth = self.scheduler.queue_depth
        m.running = sum(1 for r in self._slots if r is not None)
        m.pages_in_use = (self.config.num_pages - 1
                          - self._alloc.num_free_pages)
        state = self.health.update(
            m.pages_in_use / m.pages_total if m.pages_total else 0.0)
        m.health = state.name.lower()
        m.sync_gauges()    # queue-depth / page-occupancy scrape gauges

    # ------------------------------------------------- compiled steps
    def _run_model(self, params, ids, pos_ids, ctx, **kw):
        """Traced: rebind params, run the cache-aware forward."""
        sd = self._model.state_dict()
        saved = [(t, t._value) for t in sd.values()]
        try:
            for k, t in sd.items():
                t._value = params[k]
            from paddle_tpu.distributed.moe import grouped_tally
            with no_grad(), grouped_tally() as took:
                out = self._model(Tensor(ids), position_ids=Tensor(pos_ids),
                                  kv_ctx=ctx, **kw)
            ctx.grouped_products.extend(took)
            return out._value
        finally:
            for t, v in saved:
                t._value = v

    def _step_out_shardings(self):
        """out_shardings for the prefill/decode step programs in mesh
        mode (None otherwise): logits replicated, pools as their kind
        shards them — pinning the output layout to the input layout is
        what keeps the pool arrays reusable call-over-call without a
        resharding copy (or a surprise cache miss)."""
        if self._mesh is None:
            return None
        return (self._repl_sharding, *self._pool.out_shardings())

    def _kv_context(self, k_pools, v_pools, tables, lens, mode, *slot):
        """Traced: the cache-aware attention hook of one program."""
        return PagedKVContext(self._pool, k_pools, v_pools, tables, lens,
                              mode, *slot)

    def _prefill_example(self, bucket):
        """Example operands of a prefill program at `bucket`."""
        cfg = self.config
        return (
            self._params, self._k_pools, self._v_pools,
            jnp.zeros((1, cfg.max_pages_per_seq), jnp.int32),
            jnp.zeros((1, bucket), jnp.int32),
            jnp.zeros((1, bucket), jnp.int32),
            jnp.zeros((1,), jnp.int32),
            *(jnp.asarray(x) for x in self._pool.slot_operands(0)))

    def _decode_example(self, rows):
        """Example operands of the decode program at `rows` ids a slot."""
        cfg = self.config
        return (
            self._params, self._k_pools, self._v_pools,
            jnp.zeros((cfg.max_num_seqs, cfg.max_pages_per_seq),
                      jnp.int32),
            jnp.zeros((cfg.max_num_seqs,), jnp.int32),
            jnp.zeros((cfg.max_num_seqs, rows), jnp.int32))

    def _expert_stats(self, ctx):
        """The extra output of a program of a model with expert layers
        (nothing for any other model)."""
        return (ctx.expert_stats(),) if self._moe_layers else ()

    def _guard_flags(self, logits, k_pools, v_pools, tables, lens):
        """Traced per-slot anomaly flags ``[B, 2]`` f32: column 0 is
        the logit finite-check (any non-finite value in the row's
        logits), column 1 the pool kind's scale-overflow check (a
        quantized pool: a non-finite — or above ``guard_scale_limit`` —
        page scale on any page the row actually uses, any layer)."""
        bad_logits = jnp.any(~jnp.isfinite(logits), axis=-1)     # [B]
        bad_scale = self._pool.scale_overflow(
            (k_pools, v_pools), tables, lens,
            self.config.guard_scale_limit)
        return jnp.stack([bad_logits, bad_scale],
                         axis=-1).astype(jnp.float32)

    def _guarded_out_shardings(self):
        """Decode out_shardings with the guard-flag output appended
        (replicated, like the logits)."""
        base = self._step_out_shardings()
        if base is None:
            return None
        return (*base, self._repl_sharding)

    def _get_prefill(self, bucket):
        key = ("prefill", bucket)
        if key in self._compiled:
            return self._compiled[key]
        fn, example, donate, out_sh = self._gen.prefill_program(self, bucket)
        return self._compile(key, fn, example, donate=donate,
                             out_shardings=out_sh)

    def _get_decode(self):
        key = ("decode",)
        if key in self._compiled:
            return self._compiled[key]
        fn, example, donate, out_sh = self._gen.decode_program(self)
        return self._compile(key, fn, example, donate=donate,
                             out_shardings=out_sh)

    def _get_sampler(self, width):
        key = ("sample", width)
        if key in self._compiled:
            return self._compiled[key]
        fn, example, donate, out_sh = self._gen.sampler_program(self, width)
        return self._compile(key, fn, example, donate=donate,
                             out_shardings=out_sh)

    def warmup(self):
        """Boot hook: compile — or load from the AOT program cache —
        EVERY program this engine can ever run (each prefill bucket,
        the decode step, both sampler widths).  Returns a summary dict;
        ``boot_ms`` is the cold-vs-warm number the router bench lane
        reports.  Idempotent."""
        t0 = time.perf_counter()
        for b in self.config.prefill_buckets:
            self._get_prefill(b)
        self._get_decode()
        for width in self._gen.sampler_widths():
            self._get_sampler(width)
        return {
            "programs": len(self._compiled),
            "compiled": self.metrics.compile_count,
            "cache_loads": self.metrics.aot_cache_loads,
            "boot_ms": round((time.perf_counter() - t0) * 1e3, 3),
        }

    # ---------------------------------------------------- self-audit
    @property
    def params_bytes(self):
        return sum(int(v.nbytes) for v in self._params.values())

    @property
    def kv_pool_bytes(self):
        """Total bytes of the paged K+V pools across all layers (the
        page budget, in bytes).  Quantized pools count codes AND their
        per-page scales — the honest narrow-storage number the
        hbm_budget/perfgate gates see; a per-slot state counts too."""
        return self._pool.nbytes

    @property
    def kv_bytes_per_token(self):
        """Pool storage bytes per token of KV capacity across all
        layers — the serving-density metric the perfgate `quantization`
        target and the bench `--worker-quant` lane budget.  (Page 0 is
        reserved, but its bytes and its capacity cancel exactly, so
        this is total pool bytes over total page slots.)"""
        return self.kv_pool_bytes / (self.config.num_pages
                                     * self.config.page_size)

    @property
    def hbm_budget_bytes(self):
        """Documented per-program peak-HBM budget: weights + both the
        input and output aliases of the KV pools (XLA donates them, but
        the static estimate sees both live) + a fixed activations
        margin.  The decode/prefill programs must stay inside this —
        asserted by the shardlint self-audit gate in CI."""
        return self.params_bytes + 2 * self.kv_pool_bytes + (64 << 20)

    def audit_programs(self):
        """{name: ClosedJaxpr} for every program the engine will ever
        compile, traced (not compiled) from the same builders."""
        import jax
        progs = {}
        for b in self.config.prefill_buckets:
            fn, example, *_ = self._gen.prefill_program(self, b)
            progs[f"prefill_{b}"] = jax.jit(fn).trace(*example).jaxpr
        fn, example, *_ = self._gen.decode_program(self)
        progs["decode"] = jax.jit(fn).trace(*example).jaxpr
        for width in self._gen.sampler_widths():
            fn, example, *_ = self._gen.sampler_program(self, width)
            progs[f"sample_{width}"] = jax.jit(fn).trace(*example).jaxpr
        return progs

    def audit(self, config=None):
        """shardlint self-audit: run the SL-rule audit over every engine
        program against the documented compile + page budgets.  Returns
        a plain dict (JSON-able) — the CI gate asserts every program's
        ``within_budget`` and that the compile bound holds."""
        from paddle_tpu import analysis
        cfg = config or analysis.AuditConfig(
            hbm_budget_bytes=self.hbm_budget_bytes)
        out = {
            "compile_bound": self.config.compile_bound,
            "compiles_used": len(self._compiled),
            "pages_total": self.config.num_pages - 1,
            "params_mb": round(self.params_bytes / (1 << 20), 3),
            "kv_pool_mb": round(self.kv_pool_bytes / (1 << 20), 3),
            "kv_cache_dtype": self.config.kv_cache_dtype,
            "kv_bytes_per_token": round(self.kv_bytes_per_token, 3),
            "hbm_budget_mb": round(self.hbm_budget_bytes / (1 << 20), 3),
            "programs": {},
        }
        for name, jaxpr in self.audit_programs().items():
            findings, rep = analysis.audit_jaxpr(
                jaxpr, where=f"<serving {name}>", config=cfg)
            d = rep.to_dict()
            d["findings"] = [f.format() for f in findings]
            d["within_budget"] = not any(f.code == "SL301"
                                         for f in findings)
            out["programs"][name] = d
        return out

    def _compile(self, key, fn, example_args, donate=(),
                 out_shardings=None):
        """AOT compile + count: every program the engine will ever run
        passes through here, so `metrics.compile_count` is exact.

        `donate` names arg positions (the KV pools) XLA may alias
        in-place — without it every decode step materializes a second
        copy of the whole cache.  CPU's backend can't donate these and
        would warn on every call, so donation is accelerator-only.

        With an AOT program cache attached, the cache is consulted
        FIRST: a hit loads the persisted executable and records NO
        compile event anywhere (the warm-boot contract the router's
        zero-recompile acceptance test pins); a miss compiles as usual
        and persists the result for the next replica."""
        prog_name = "/".join(str(p) for p in key)
        if self._program_cache is not None:
            from paddle_tpu.serving.aot_cache import program_devices
            devices = program_devices(self._params, self._mesh)
            compiled = self._program_cache.load(self._program_fp,
                                               prog_name, devices)
            if compiled is not None:
                with span("serving.aot_load", program=str(key),
                          fingerprint=self._program_fp):
                    pass
                self.metrics.note_aot_load()
                self._compiled[key] = compiled
                return compiled

        pinned = (None if self._device is None
                  else jax.sharding.SingleDeviceSharding(self._device))

        def _struct(a):
            if self._mesh is None:
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=pinned)
            sh = getattr(a, "sharding", None)
            if not isinstance(sh, jax.sharding.NamedSharding):
                sh = self._repl_sharding    # host-built example operand
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

        shapes = jax.tree_util.tree_map(_struct, example_args)
        if jax.default_backend() == "cpu":
            donate = ()
        jit_kw = {"donate_argnums": donate}
        if out_shardings is not None:
            jit_kw["out_shardings"] = out_shardings
        if self._program_cache is not None:
            # the store below needs an executable that has never run
            # (XLA:CPU refuses to serialize one whose sort has
            # executed), and jax's lowering cache would hand the
            # module-level sampler function the executable an earlier
            # engine already ran — a fresh wrapper compiles a fresh one
            fn = functools.partial(fn)
        t0 = time.perf_counter()
        with span("serving.compile", program=str(key)):
            compiled = jax.jit(fn, **jit_kw).lower(
                *shapes).compile()
        # the serving compile choke point reports into the same
        # recompile log as StaticFunction cache misses: one timeline
        # answers "what compiled, when, and against what bound" — record
        # BEFORE the storm check so an over-bound compile is the best-
        # documented event in the log, not a missing one; cache LAST so
        # a storm RuntimeError leaves no over-bound program behind that
        # a catch-and-retry caller could silently keep serving from
        note_aot_compile(
            prog_name,
            compile_ms=round((time.perf_counter() - t0) * 1e3, 3),
            cache_size=len(self._compiled) + 1,
            bound=self.config.compile_bound, engine=self._metrics_name)
        self.metrics.note_compile()
        self._compiled[key] = compiled
        if self._program_cache is not None:
            self._program_cache.store(self._program_fp, prog_name,
                                      compiled, devices)
        return compiled
