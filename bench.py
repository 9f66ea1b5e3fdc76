"""Headline benchmark: ResNet-50 training throughput (images/sec/chip).

Baseline (SURVEY.md §6 / BASELINE.json): PaddleClas ResNet-50 on A100 fp16
≈ 800-1000 img/s; TPU v5e target ≥ 1000 img/s bf16, batch 256, to_static path.

Prints ONE JSON line on stdout:
  {"metric", "value", "unit", "vs_baseline", ...}
with the device it ran on ("platform", "device_kind"), "mfu", and the other
lanes' keys ("bert_base_tokens_s", "serving_tokens_s", ...).

Every lane is one worker process (`bench.py --worker-<lane>`); this parent
never imports JAX, because a chip belongs to one process at a time.  The
four device lanes (resnet, bert, ernie, serving) run one after another and
need a TPU: without one a worker exits non-zero — nothing reruns on the CPU
under a device metric's name, nothing is replayed from a file.  The CPU
lanes (static analysis, cost model, host-side telemetry — counts, not device
metrics) run concurrently with `JAX_PLATFORMS=cpu`.  A worker past its time
limit is killed with its process group.  The exit code is the first failing
worker's.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE_IMG_S = 1000.0
# lane -> wall limit in seconds; device lanes own the chip one at a time
DEVICE_LANES = (("resnet", 600), ("bert", 600), ("ernie", 600),
                ("serving", 600))
CPU_LANES = (("shardlint", 150), ("racelint", 90), ("protolint", 90),
             ("numlint", 150), ("kernlint", 150), ("obs", 150),
             ("resilience", 150), ("fleet", 150), ("sentinel", 240),
             ("profile", 150), ("remat", 150), ("router", 240),
             ("traffic", 300), ("fleetserving", 300), ("quant", 150))

# Training FLOPs per image for ResNet-50 @224. The familiar "4.1 GFLOPs"
# is the MAC convention; TPU peak TFLOP/s counts multiply and add
# separately, so fwd ≈ 8.2 GF and train ≈ 3x fwd. XLA cost analysis of
# our compiled step agrees: 6.143e12 flops / 256 images = 24.0 GF/img
# (tools/profile_resnet.py). r2 reported mfu with the MAC convention,
# understating it 2x.
_RESNET50_TRAIN_FLOPS = 24.0e9


# --------------------------------------------------------------- workers
def _resnet_variant(remat, batch, warmup, iters):
    import numpy as np

    import paddle_tpu as P
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    P.seed(0)
    # NHWC (r3, VERDICT #2): profiling the r2 bench showed the forward
    # dominated by per-channel BN statistics reductions — in NCHW those
    # reduce across the lane dimension; channels-last keeps C on lanes
    # and is the layout XLA prefers for MXU convs.
    model = resnet50(num_classes=1000, data_format="NHWC", remat=remat)
    opt = P.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                               parameters=model.parameters())

    @P.jit.to_static
    def train_step(x, y):
        opt.clear_grad()
        with P.amp.auto_cast(level="O1", dtype="bfloat16"):
            logits = model(x)
        loss = F.cross_entropy(logits, y)
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)
    x = P.to_tensor(
        rng.standard_normal((batch, 224, 224, 3)).astype(np.float32))
    y = P.to_tensor(rng.integers(0, 1000, (batch,)), dtype="int64")

    for _ in range(warmup):
        loss = train_step(x, y)
    loss.block_until_ready()

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = train_step(x, y)
    # the final loss is serially dependent on every step (params chain
    # through the optimizer), so syncing on it waits for the whole run
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    return dt, train_step, x, y


def _resnet_extra(chip, dt, iters, batch, train_step, x, y, remat):
    # Where the time goes (r3 profile, tools/profile_resnet.py): the step
    # is HBM-bandwidth-bound, not compute- or host-bound. XLA cost
    # analysis of the compiled step gives flops + bytes; bytes/step over
    # the measured step time vs the chip's HBM peak explains the MFU
    # ceiling (arithmetic intensity ~65 flop/byte < v5e ridge ~240).
    entry = next(iter(train_step._compiled.values()))
    cost = entry.jitted.lower([t._value for t in entry.state_list],
                              [x._value, y._value]).compile().cost_analysis()
    step_s = dt / iters
    return {
        "remat": remat,
        "hbm_gb_per_step": round(cost["bytes accessed"] / 1e9, 2),
        "hbm_bw_util": round(
            cost["bytes accessed"] / step_s / chip.bw_bytes, 4),
        "xla_flops_per_img": round(cost["flops"] / batch / 1e9, 2),
    }


def _time_mlm(train_step, args, warmup, iters, batch, seq, prefix):
    """Shared MLM-lane harness: warmup, chained timing loop, XLA cost
    analysis. Returns (tokens/sec, extra-dict with {prefix}_ keys)."""
    for _ in range(warmup):
        loss = train_step(*args)
    loss.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = train_step(*args)
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    tok_s = batch * seq * iters / dt

    entry = next(iter(train_step._compiled.values()))
    cost = entry.jitted.lower(
        [t._value for t in entry.state_list],
        [a._value for a in args]).compile().cost_analysis()
    flops_per_token = cost["flops"] / (batch * seq)
    return tok_s, {
        f"{prefix}_xla_flops_per_token": round(flops_per_token / 1e9, 3),
        "_flops_per_token": flops_per_token,
    }


def _bench_bert(batch):
    """Second metric: BERT-base masked-LM train step, tokens/sec (seq 512)."""
    import numpy as np

    import paddle_tpu as P
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    seq, warmup, iters = 512, 2, 8
    cfg = BertConfig(dropout=0.0, attention_dropout=0.0)  # bert-base

    P.seed(0)
    model = BertForPretraining(cfg)
    opt = P.optimizer.AdamW(learning_rate=1e-4,
                            parameters=model.parameters())

    @P.jit.to_static
    def train_step(ids, labels):
        opt.clear_grad()
        with P.amp.auto_cast(level="O1", dtype="bfloat16"):
            pred, _ = model(ids)
        loss = F.cross_entropy(
            pred.reshape([-1, cfg.vocab_size]), labels.reshape([-1]))
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)
    ids = P.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)), dtype="int64")
    labels = P.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)), dtype="int64")
    return _time_mlm(train_step, (ids, labels), warmup, iters, batch, seq,
                     "bert")


def _bench_ernie(batch):
    """Third metric: ERNIE-3.0-base masked-LM train step, tokens/sec
    (seq 512) — BASELINE.json's headline metric literally names
    "ERNIE-3.0 tokens/sec/chip" (same harness as the BERT lane; ERNIE
    adds task-type embeddings and a 40k vocab head)."""
    import numpy as np

    import paddle_tpu as P
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.ernie import ErnieForPretraining, ernie_3_0_base

    seq, warmup, iters = 512, 2, 8
    cfg = ernie_3_0_base(dropout=0.0, attention_dropout=0.0)

    P.seed(0)
    model = ErnieForPretraining(cfg)
    opt = P.optimizer.AdamW(learning_rate=1e-4,
                            parameters=model.parameters())

    @P.jit.to_static
    def train_step(ids, task_ids, labels):
        opt.clear_grad()
        with P.amp.auto_cast(level="O1", dtype="bfloat16"):
            pred = model(ids, task_type_ids=task_ids)
        loss = F.cross_entropy(
            pred.reshape([-1, cfg.vocab_size]), labels.reshape([-1]))
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)
    ids = P.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)), dtype="int64")
    task_ids = P.to_tensor(np.zeros((batch, seq)), dtype="int64")
    labels = P.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)), dtype="int64")

    return _time_mlm(train_step, (ids, task_ids, labels), warmup, iters,
                     batch, seq, "ernie")


def _bench_serving():
    """Serving lane: continuous-batched generation through
    paddle_tpu.serving.LLMEngine (paged KV cache, bucketed prefill, one
    compiled decode step).  Reports decode tokens/s, time-to-first-token,
    and p50/p99 inter-token latency from the engine's own metrics — the
    same snapshot a production process exports via profiler
    metrics_report()."""
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    mcfg = GPTConfig(vocab_size=32000, hidden_size=1024, num_layers=8,
                     num_heads=16, max_seq_len=1024, dropout=0.0,
                     attention_dropout=0.0)
    ecfg = serving.EngineConfig(max_num_seqs=16, page_size=16,
                                max_model_len=512,
                                prefill_buckets=(64, 128, 256, 512))
    n_req, max_new = 32, 64

    P.seed(0)
    model = GPTForCausalLM(mcfg)
    engine = serving.LLMEngine(model, ecfg)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(
        1, mcfg.vocab_size,
        int(rng.integers(4, ecfg.prefill_buckets[-1] // 2))))
        for _ in range(n_req)]
    sps = [serving.SamplingParams(max_new_tokens=max_new, temperature=0.8,
                                  top_p=0.95, seed=i)
           for i in range(n_req)]
    t0 = time.perf_counter()
    results = engine.generate(prompts, sps)
    wall = time.perf_counter() - t0
    snap = engine.metrics.snapshot()
    generated = sum(len(r.output_token_ids) for r in results)
    out = {
        "serving_tokens_s": round(generated / wall, 2),
        "serving_requests": n_req,
        "serving_batch": ecfg.max_num_seqs,
        "serving_ttft_ms_p50": snap["ttft_ms"]["p50"],
        "serving_ttft_ms_p99": snap["ttft_ms"]["p99"],
        "serving_itl_ms_p50": snap["inter_token_ms"]["p50"],
        "serving_itl_ms_p99": snap["inter_token_ms"]["p99"],
        "serving_evictions": snap["requests"]["evicted"],
        "serving_compiles": snap["compiles"]["count"],
        "serving_compile_bound": snap["compiles"]["bound"],
    }
    engine.shutdown()
    return out


def worker_serving():
    dev, _chip = _tpu()
    out = _bench_serving()
    out["serving_platform"] = dev.platform
    print(json.dumps(out), flush=True)
    return 0


def worker_obs():
    """Observability lane: instrumentation-overhead + recompile-
    attribution check over the gpt hybrid train step.  Pure CPU — the
    span/recompile machinery is host-side Python, so its cost is
    platform-independent and the lane never touches the chip.

    Reports (merged into every BENCH line):
      obs_span_overhead_pct   — wall-time cost of leaving spans on,
                                asserted < 2% (the production contract),
                                measured WITH the Prometheus scrape
                                endpoint live AND the fleettrace spool
                                armed (the fleet production shape)
      obs_recompile_count     — compile events seen by the log (the
                                forced retrace makes this >= 2)
      obs_recompile_attrib    — which argument the last event blamed
      obs_fleet_trace_requests — traces in the micro two-rank fleet
                                merge below
      obs_spool_bytes         — bytes this lane's telemetry spool wrote
      obs_clock_skew_ms       — KV clock-handshake skew bound from the
                                same merge
    """
    import statistics
    import tempfile

    import numpy as np

    import paddle_tpu as P
    import paddle_tpu.nn.functional as F
    from paddle_tpu import observability as obs
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny

    P.seed(0)
    cfg = gpt3_tiny()
    model = GPTForCausalLM(cfg)
    opt = P.optimizer.AdamW(learning_rate=1e-4,
                            parameters=model.parameters())

    @P.jit.to_static
    def train_step(ids, labels):
        opt.clear_grad()
        logits = model(ids)
        loss = F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1]))
        loss.backward()
        opt.step()
        return loss

    rng = np.random.default_rng(0)

    def mk(seq):
        return (P.to_tensor(rng.integers(0, cfg.vocab_size, (2, seq)),
                            dtype="int64"),
                P.to_tensor(rng.integers(0, cfg.vocab_size, (2, seq)),
                            dtype="int64"))

    ids, labels = mk(32)
    train_step(ids, labels)                 # first compile
    ids_w, labels_w = mk(48)
    train_step(ids_w, labels_w)             # forced retrace (shape)

    def time_loop(iters):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = train_step(ids, labels)
        loss.block_until_ready()
        return time.perf_counter() - t0

    # the <2% contract is measured in the production shape: roofline
    # profiler imported, live Prometheus scrape endpoint running on its
    # daemon thread, AND the fleettrace telemetry spool armed — span
    # spooling is gated by set_enabled, so the off/on delta charges the
    # spool's per-span JSONL write to the instrumentation bill exactly
    # as a production fleet worker pays it
    spool_dir = tempfile.mkdtemp(prefix="ptpu_obs_spool_")
    spool = obs.fleettrace.arm_spool(spool_dir, rank=0,
                                     metrics_interval_s=None)
    scrape = obs.export.serve_prometheus(port=0)
    try:
        time_loop(5)                        # warm the timing path
        # min-over-a-pooled-sample estimator: on shared/1-core CI hosts
        # a single 20-iter loop carries multi-percent scheduler jitter,
        # so per-attempt medians routinely fake a >2% "overhead".  The
        # min of an interleaved, growing sample pool filters additive
        # noise — a fail requires EVERY on-sample to run slow, which
        # only true instrumentation cost produces.
        offs, ons = [], []
        overhead = None
        for attempt in range(5):
            for _ in range(3):
                obs.set_enabled(False)
                offs.append(time_loop(20))
                obs.set_enabled(True)
                ons.append(time_loop(20))
            overhead = max(0.0,
                           (min(ons) - min(offs)) / min(offs) * 100.0)
            if overhead < 2.0:
                break
        obs.set_enabled(True)
    finally:
        scrape.shutdown()
        spool_bytes = spool.bytes_written
        obs.fleettrace.disarm()

    # micro fleet merge: a second "rank" spool + in-process KV clock
    # handshake, two traced request spans, one merge — the numbers the
    # controller's fleet report carries, kept honest in CI
    from paddle_tpu.resilience.fleet import LocalKVClient
    kv = LocalKVClient()
    ns = "bench/obs"
    sp0 = obs.fleettrace.TelemetrySpool(spool_dir, rank=0, tag="m")
    sp0.note_clock(obs.fleettrace.clock_handshake(
        kv, 0, namespace=ns, timeout_s=2.0))
    sp1 = obs.fleettrace.TelemetrySpool(spool_dir, rank=1, tag="m")
    sp1.note_clock(obs.fleettrace.clock_handshake(
        kv, 1, namespace=ns, timeout_s=2.0))
    for i, sp in enumerate((sp0, sp1)):
        ctx = obs.TraceContext.new(hint=f"bench-{i}")
        with obs.use_context(ctx):
            with obs.span("serving.router.admit", request=f"bench-{i}"):
                pass
            with obs.span("serving.finish", request=f"bench-{i}"):
                pass
        for rec in obs.recorder().spans()[-2:]:
            sp.note_span(rec)
        sp.close()
    tel = obs.fleettrace.merge_spools(spool_dir)
    fleet_summary = tel.summary()

    events = obs.recompile_log().events()
    jit_events = [e for e in events if e.kind == "jit" and e.changes]
    out = {
        "obs_span_overhead_pct": round(overhead, 3),
        "obs_recompile_count": obs.recompile_log().count,
        "obs_recompile_attrib": (", ".join(jit_events[-1].changed_args())
                                 if jit_events else ""),
        "obs_spans_recorded": obs.recorder().total_recorded,
        "obs_fleet_trace_requests": fleet_summary["traces"],
        "obs_spool_bytes": int(spool_bytes),
        "obs_clock_skew_ms": fleet_summary["clock_skew_ms"],
    }
    # the lane's contract: leaving instrumentation on must cost < 2%.
    # Gate BEFORE emitting the result line — the orchestrator merges any
    # JSON it can read, so printing first would let an over-budget lane
    # ride into the report as if the gate passed
    assert overhead < 2.0, (
        f"span instrumentation overhead {overhead:.2f}% >= 2%")
    assert out["obs_fleet_trace_requests"] >= 2 \
        and out["obs_spool_bytes"] > 0, (
        "fleettrace micro-merge produced no traces/spool bytes")
    print(json.dumps(out), flush=True)
    return 0


def worker_resilience():
    """Resilience lane: crash-safe checkpoint write/restore cost plus
    the recovery-step overhead of a torn-write fallback, over a
    synthetic ~16 MB train state.  Pure CPU — checkpointing is
    host-side work (pickle + fsync + atomic rename), so its cost is
    platform-independent and the lane never touches the chip.

    Reports (merged into every BENCH line):
      resilience_ckpt_write_ms        — median durable save() wall ms
      resilience_ckpt_restore_ms      — median load() (digest verify +
                                        unpickle) wall ms
      resilience_recovery_overhead_ms — EXTRA cost of a restore that
                                        must detect a torn newest
                                        checkpoint and fall back to
                                        last-good (the chaos-path price
                                        on top of a clean restore)
      resilience_ckpt_mb              — payload size the times refer to
    """
    import shutil
    import statistics
    import tempfile

    import numpy as np

    from paddle_tpu import resilience as R

    rng = np.random.default_rng(0)
    state = {"step": 0, "model": {
        f"w{i}": rng.standard_normal((1024, 2048)).astype(np.float32)
        for i in range(2)}}
    data_mb = sum(a.nbytes for a in state["model"].values()) / 1e6

    tdir = tempfile.mkdtemp(prefix="ptpu_resil_bench_")
    try:
        ck = R.Checkpointer(tdir, keep=3)
        writes = []
        for step in range(5):
            state["step"] = step
            t0 = time.perf_counter()
            ck.save(step, state)
            writes.append((time.perf_counter() - t0) * 1e3)

        restores = []
        for _ in range(3):
            t0 = time.perf_counter()
            got = ck.load()
            restores.append((time.perf_counter() - t0) * 1e3)
        assert got is not None and got[0] == 4, "clean restore failed"
        clean_ms = statistics.median(restores)

        # tear the NEXT payload write, then time the fallback restore —
        # the same skip-and-recover path the chaos suite proves correct
        plan = R.FaultPlan([R.FaultSpec("io.save", "torn_write", at=0)],
                           name="bench-torn")
        with R.FaultInjector(plan):
            ck.save(5, state)
        t0 = time.perf_counter()
        step, _ = ck.load()
        recovery_ms = (time.perf_counter() - t0) * 1e3
        assert step == 4, f"fallback restored step {step}, wanted 4"
    finally:
        shutil.rmtree(tdir, ignore_errors=True)

    print(json.dumps({
        "resilience_ckpt_mb": round(data_mb, 2),
        "resilience_ckpt_write_ms": round(statistics.median(writes), 2),
        "resilience_ckpt_restore_ms": round(clean_ms, 2),
        "resilience_recovery_overhead_ms": round(
            max(0.0, recovery_ms - clean_ms), 2),
    }), flush=True)
    return 0


def worker_fleet():
    """Fleet fault-tolerance lane: the rank-kill → detect →
    reconfigure → resume ladder as a rank-per-thread world over
    ``fleet.LocalKVClient`` (same blocking semantics as the
    coordination-service client, zero gRPC).  Pure CPU and
    deterministic in structure; the wall numbers are the real cost of
    the fleet machinery (watchdog classification latency, join-barrier
    rendezvous, quorum manifest commit).  The multi-PROCESS version of
    this ladder — real SIGKILL through a real coordinator — is the
    chaos gate's job; this lane keeps its cost trended on every BENCH
    report.

    Reports (merged into every BENCH line):
      fleet_detection_ms       — publisher death → watchdog DEAD verdict
      fleet_reconfigure_ms     — slowest survivor's join-barrier
                                 reconfigure to world size 2
      fleet_ckpt_commit_ms     — rank 0 wall for a 3-shard quorum
                                 checkpoint save (digest gather +
                                 manifest commit)
      fleet_resume_identical   — 1.0 iff both survivors restored the
                                 identical replicated state and exact
                                 resharded dp rows (asserted before
                                 printing)
      fleet_world_size_after   — post-reconfigure world size (2)
    """
    import shutil
    import tempfile
    import threading

    import numpy as np

    from paddle_tpu.resilience import fleet

    kv = fleet.LocalKVClient()
    cfg = fleet.FleetConfig(
        collective_timeout_s=10.0, kv_slice_s=0.05,
        heartbeat_interval_s=0.05, suspect_after_s=0.2,
        dead_after_s=0.4, rendezvous_timeout_s=10.0)
    worlds = {r: fleet.WorldView([0, 1, 2], r) for r in range(3)}
    pubs = {r: fleet.HeartbeatPublisher(
        client=kv, rank=r, interval_s=cfg.heartbeat_interval_s).start()
        for r in range(3)}
    mon = fleet.FleetMonitor(client=kv, config=cfg,
                             world_fn=lambda: worlds[0])
    tdir = None
    try:
        # warm up: every publisher has actually beaten at least twice
        # (a first-poll HEALTHY is grace, not evidence) and the
        # watchdog has observed the fleet healthy
        deadline = time.monotonic() + 10.0
        while any(p.seq < 2 for p in pubs.values()) or \
                any(s is not fleet.RankState.HEALTHY
                    for s in mon.poll().values()):
            assert time.monotonic() < deadline, "fleet never healthy"
            time.sleep(0.02)

        # ---- quorum checkpoint at world size 3 ----
        tdir = tempfile.mkdtemp(prefix="ptpu_fleet_bench_")
        rng = np.random.default_rng(0)
        wref = rng.standard_normal((256, 256)).astype(np.float32)
        cks, commit_ms = {}, {}

        def save(r):
            ck = fleet.DistributedCheckpointer(
                tdir, client=kv, world=worlds[r], timeout_s=10.0)
            cks[r] = ck
            t0 = time.perf_counter()
            ck.save(1, sharded={"rows": np.full((4,), r, np.int64)},
                    replicated={"w": wref} if r == 0 else None)
            commit_ms[r] = (time.perf_counter() - t0) * 1e3

        ts = [threading.Thread(target=save, args=(r,))
              for r in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert len(commit_ms) == 3, "quorum save did not complete"

        # ---- kill rank 2, time the DEAD verdict ----
        t_kill = time.perf_counter()
        pubs[2].stop()
        deadline = time.monotonic() + 15.0
        while 2 not in mon.dead_ranks():
            assert time.monotonic() < deadline, "no DEAD verdict"
            mon.poll()
            time.sleep(0.01)
        detection_ms = (time.perf_counter() - t_kill) * 1e3
        # the verdict must land within the configured window (+ slack)
        assert detection_ms / 1e3 <= cfg.dead_after_s + 5.0

        # ---- survivors reconfigure + reload resharded ----
        recfg_ms, states = {}, {}

        def recover(r):
            t0 = time.perf_counter()
            nw = fleet.reconfigure([2], client=kv, config=cfg,
                                   world_view=worlds[r],
                                   install=False)
            recfg_ms[r] = (time.perf_counter() - t0) * 1e3
            _, st = cks[r].load(world_size=nw.size, rank=nw.rank)
            states[r] = (nw, st)

        ts = [threading.Thread(target=recover, args=(r,))
              for r in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert len(states) == 2, "a survivor failed to recover"

        identical = True
        for r, (nw, st) in states.items():
            identical &= nw.size == 2
            identical &= bool(np.array_equal(st["replicated"]["w"],
                                             wref))
            want = ([0, 0, 0, 0, 1, 1] if nw.rank == 0
                    else [1, 1, 2, 2, 2, 2])
            identical &= bool(np.array_equal(st["sharded"]["rows"],
                                             want))
        # identity is a correctness gate, not a metric: fail the lane
        # loudly rather than print a lying number
        assert identical, "resumed state diverged from the checkpoint"
    finally:
        for p in pubs.values():
            p.stop()
        mon.stop()
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)

    print(json.dumps({
        "fleet_detection_ms": round(detection_ms, 2),
        "fleet_reconfigure_ms": round(max(recfg_ms.values()), 2),
        "fleet_ckpt_commit_ms": round(commit_ms[0], 2),
        "fleet_resume_identical": 1.0,
        "fleet_world_size_after": 2,
    }), flush=True)
    return 0


def worker_sentinel():
    """Training-sentinel lane: the detect → skip → rollback → resume
    ladder on a tiny eager model under a deterministic nan_grad fault
    plan, plus the in-trace probe's cost-model overhead on the
    optimized gpt flagship (tools/perfgate.py ``sentinel`` target).

    Reports (merged into every BENCH line):
      sentinel_detect_steps       — steps from injection to the first
                                    AnomalyDetected (contract: 1)
      sentinel_skips              — zero-update steps the guard gated
      sentinel_rollbacks          — checkpoint rollbacks triggered
      sentinel_rollback_identity  — 1.0 iff the rolled-back-and-resumed
                                    trajectory + final weights EXACTLY
                                    match the fault-free run (asserted
                                    before printing)
      sentinel_overhead_pct       — guarded-vs-unguarded cost-model
                                    bytes/step on the gpt target,
                                    asserted < 2.0 before printing
    """
    import shutil
    import tempfile

    import numpy as np

    t_start = time.time()

    import paddle_tpu as P
    import paddle_tpu.nn as nn
    from paddle_tpu import resilience as R

    CKPT_STEP, FAULT_STEP, TOTAL, SKIPS = 4, 7, 10, 2

    def batch(step):
        rng = np.random.default_rng(1000 + step)
        X = rng.standard_normal((8, 6)).astype(np.float32)
        y = rng.standard_normal((8, 3)).astype(np.float32)
        return P.to_tensor(X), P.to_tensor(y)

    def run(ckpt_dir, plan):
        P.seed(0)
        model = nn.Linear(6, 3)
        opt = P.optimizer.AdamW(learning_rate=0.05,
                                parameters=model.parameters(),
                                guard=True)
        ck = R.Checkpointer(ckpt_dir, keep=2)
        # lr_cooldown 1.0: the identity contract is exact-match for a
        # TRANSIENT fault (docs/resilience.md); a cooldown would
        # deliberately change the resumed trajectory
        sent = R.TrainingSentinel(checkpointer=ck, model=model,
                                  optimizer=opt, skip_limit=SKIPS,
                                  lr_cooldown=1.0)
        inj = R.FaultInjector(plan) if plan is not None else None
        if inj is not None:
            R.faultinject.install(inj)
        losses = {}
        try:
            step = 1
            while step <= TOTAL:
                X, y = batch(step)
                opt.clear_grad()
                loss = ((model(X) - y) ** 2).mean()
                loss.backward()
                opt.step()
                act = sent.observe(step, loss=float(loss.numpy()),
                                   summary=opt.guard_summary())
                if act is R.SentinelAction.ROLLBACK:
                    step = sent.resume_step
                    continue
                if act is R.SentinelAction.OK:
                    losses[step] = float(loss.numpy())
                    if step == CKPT_STEP:
                        ck.save_train_state(step, model, opt)
                        sent.note_checkpoint(step)
                step += 1
        finally:
            if inj is not None:
                R.faultinject.uninstall(inj)
        w = np.asarray(model.weight._value).copy()
        return losses, w, sent

    tdir = tempfile.mkdtemp(prefix="ptpu_sentinel_bench_")
    try:
        clean_losses, clean_w, _ = run(os.path.join(tdir, "a"), None)
        plan = R.FaultPlan([R.FaultSpec("optimizer.grads", "nan_grad",
                                        at=FAULT_STEP - 1,
                                        times=SKIPS)],
                           seed=3, name="bench-sentinel")
        fault_losses, fault_w, sent = run(os.path.join(tdir, "b"), plan)

        assert sent.anomalies, "guard never detected the injected NaN"
        detect_steps = sent.anomalies[0].step - FAULT_STEP + 1
        assert detect_steps == 1, (
            f"detection took {detect_steps} steps (contract: 1)")
        assert sent.rollbacks == 1, sent.rollbacks
        identical = (fault_losses == clean_losses
                     and bool(np.array_equal(fault_w, clean_w)))
        # identity is a correctness gate, not a metric: fail the lane
        # loudly rather than print a lying number
        assert identical, "rollback-resume diverged from fault-free run"
    finally:
        shutil.rmtree(tdir, ignore_errors=True)

    # probe overhead on the flagship (deterministic cost model)
    tools_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools")
    sys.path.insert(0, tools_dir)
    try:
        import perfgate
        overhead = perfgate.target_sentinel()
    finally:
        sys.path.remove(tools_dir)
    pct = overhead["guard_bytes_overhead_pct"]
    assert pct < 2.0, (
        f"guard overhead {pct}% breaches the <2% detection-cost "
        f"contract")

    print(json.dumps({
        "sentinel_detect_steps": detect_steps,
        "sentinel_skips": sent.skips_total,
        "sentinel_rollbacks": sent.rollbacks,
        "sentinel_rollback_identity": 1.0,
        "sentinel_overhead_pct": pct,
        "sentinel_guard_bytes_per_step": overhead[
            "guard_bytes_per_step"],
        "sentinel_elapsed_s": round(time.time() - t_start, 2),
    }), flush=True)
    return 0


def worker_shardlint():
    """Static-analysis lane: shardlint's cost audit of the flagship
    programs (GPT hybrid train step + serving prefill/decode).  Pure
    CPU trace — never touches the chip — so every BENCH run
    records estimated peak-HBM and MXU padding-waste alongside the
    measured wall-time lanes."""
    tools_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools")
    sys.path.insert(0, tools_dir)
    try:
        import shardlint
        out = shardlint.bench_report()
    finally:
        # remove by value: importing tools/shardlint.py prepends its own
        # REPO entry, so pop(0) would evict the wrong path
        sys.path.remove(tools_dir)
    print(json.dumps(out), flush=True)
    return 0


def worker_profile():
    """Roofline-profiler lane: deterministic cost-model numbers for the
    gpt hybrid train step (observability.profile — the same numbers
    tools/perfgate.py gates on).  Pure CPU trace — never touches the
    chip — so every BENCH run records bytes/flops per step, the
    heaviest layer, and the memory-bound fraction next to the measured
    wall-time lanes."""
    tools_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools")
    sys.path.insert(0, tools_dir)
    try:
        import perfgate
        out = perfgate.bench_report()
    finally:
        # remove by value: importing tools/perfgate.py prepends its own
        # REPO entry, so pop(0) would evict the wrong path
        sys.path.remove(tools_dir)
    print(json.dumps(out), flush=True)
    return 0


def worker_remat():
    """Remat lane: remat-on vs remat-off bytes/step from the
    deterministic cost model (tools/perfgate.remat_report) — the honest
    replacement for the resnet lane's bare "remat" bool.  Pure CPU
    trace, never touches the chip; merged into every BENCH report."""
    tools_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools")
    sys.path.insert(0, tools_dir)
    try:
        import perfgate
        out = perfgate.remat_report()
    finally:
        # remove by value: importing tools/perfgate.py prepends its own
        # REPO entry, so pop(0) would evict the wrong path
        sys.path.remove(tools_dir)
    print(json.dumps(out), flush=True)
    return 0


def worker_router():
    """Router lane: multi-replica serving through
    paddle_tpu.serving.router — 3 replicas sharing one AOT program
    cache, a mixed traffic trace, and one injected mid-decode replica
    crash absorbed by failover.  Pure CPU (the lane tracks router
    overhead, failover cost, and the cold-vs-warm AOT boot ratio, all
    host-side effects) — never touches the chip, so its numbers
    ride along on every BENCH report.

    Reports (merged into every BENCH line):
      router_tokens_per_s          — fleet decode throughput under the
                                     trace (incl. the failover stall)
      router_failover_count        — replica crashes absorbed (>= 1 by
                                     construction, or the lane fails)
      router_boot_ms_cold          — replica boot compiling the ladder
      router_boot_ms_warm          — replica boot loading the AOT cache
      router_boot_ms_cold_vs_warm  — the scale-out payoff ratio
      router_spillover_count       — admissions spilled on rejection
    """
    import shutil
    import statistics
    import tempfile

    import numpy as np

    import paddle_tpu as P
    from paddle_tpu import resilience as R
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.router import Router, RouterConfig

    mcfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, dropout=0.0,
                     attention_dropout=0.0)
    ecfg = serving.EngineConfig(max_num_seqs=4, page_size=8,
                                max_model_len=64,
                                prefill_buckets=(16, 32),
                                crash_safe_decode=False)
    P.seed(0)
    model = GPTForCausalLM(mcfg)
    cache_dir = tempfile.mkdtemp(prefix="ptpu_router_bench_")
    try:
        router = Router(model, ecfg, num_replicas=3,
                        config=RouterConfig(sleep=lambda s: None),
                        program_cache=cache_dir)
        boots = [h.boot_info for h in router.replicas]
        cold = [b["boot_ms"] for b in boots if not b.get("warm")]
        warm = [b["boot_ms"] for b in boots if b.get("warm")]

        rng = np.random.default_rng(0)
        n_req, max_new = 24, 12
        # worst-case replay (prompt + max_new - 1) must stay bucketable
        prompts = [list(rng.integers(1, mcfg.vocab_size,
                                     int(rng.integers(4, 21))))
                   for _ in range(n_req)]
        sps = [serving.SamplingParams(max_new_tokens=max_new,
                                      temperature=0.8, top_p=0.95,
                                      seed=i) for i in range(n_req)]
        # one injected replica crash mid-trace: throughput is measured
        # WITH the failover (migration + warm respawn) in the loop
        plan = R.FaultPlan(
            [R.FaultSpec("serving.decode", "exception", at=8)],
            name="bench-router")
        t0 = time.perf_counter()
        with R.FaultInjector(plan):
            results = router.generate(prompts, sps)
        wall = time.perf_counter() - t0
        generated = sum(len(r.output_token_ids) for r in results)
        snap = router.snapshot()
        out = {
            "router_tokens_per_s": round(generated / wall, 2),
            "router_replicas": 3,
            "router_requests": n_req,
            "router_failover_count": snap["failovers"],
            "router_respawn_count": snap["respawns"],
            "router_spillover_count": snap["spillovers"],
            "router_boot_ms_cold": round(statistics.median(cold), 1)
            if cold else None,
            "router_boot_ms_warm": round(statistics.median(warm), 1)
            if warm else None,
        }
        if cold and warm:
            out["router_boot_ms_cold_vs_warm"] = round(
                statistics.median(cold) / statistics.median(warm), 2)
        # lane contracts, gated BEFORE the result line prints: the
        # injected crash must actually have exercised failover, with
        # zero data loss under it
        assert snap["failovers"] >= 1, "injected crash never fired"
        assert generated == n_req * max_new, (
            f"data loss across failover: {generated} tokens != "
            f"{n_req * max_new}")
        router.shutdown()
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


def worker_traffic():
    """Traffic lane: the deterministic load-generation harness
    (paddle_tpu.serving.traffic) driven on a VIRTUAL clock — a
    workload-model burst trace against the router with the SLO
    autoscaler in the loop, a binary-search capacity probe at 1 vs 3
    replicas, and the same spec chaos-composed with a mid-decode
    replica crash plus a qps_surge.  Pure CPU and virtual-time, so
    every latency number below is a property of the SCHEDULE, not of
    this host — byte-stable across runs and machines.

    Reports (merged into every BENCH line):
      traffic_goodput_under_slo_pct    — finished complete AND under the
                                         class TTFT SLO, burst trace
      traffic_ttft_p99_ms              — p99 TTFT (virtual ms)
      traffic_scaleup_reaction_ticks   — burst onset -> spare replica
                                         admitting, in driver ticks
      traffic_capacity_qps_1r / _3r    — max sustained QPS at the TTFT
                                         SLO per replica count
      traffic_chaos_goodput_pct        — goodput with crash + qps_surge
                                         composed onto the same spec
    """
    import shutil
    import tempfile

    import paddle_tpu as P
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import traffic
    from paddle_tpu.serving.router import Router, RouterConfig

    mcfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, dropout=0.0,
                     attention_dropout=0.0)
    ecfg = serving.EngineConfig(max_num_seqs=4, page_size=8,
                                max_model_len=64,
                                prefill_buckets=(16, 32),
                                crash_safe_decode=False)
    P.seed(0)
    model = GPTForCausalLM(mcfg)
    cache_dir = tempfile.mkdtemp(prefix="ptpu_traffic_bench_")
    quantum = 0.01
    burst = traffic.TrafficSpec(
        name="bench-burst", seed=11,
        arrival={"kind": "onoff", "base_qps": 2.0, "burst_qps": 40.0,
                 "period_s": 2.0, "duty": 0.35},
        duration_s=2.0, prompt_len=((1.0, 4, 16),),
        output_tokens=((1.0, 4, 8),),
        classes=(traffic.DeadlineClass("interactive", ttft_slo_s=0.5),))

    def factory(n, clock):
        return Router(model, ecfg, num_replicas=n,
                      config=RouterConfig(sleep=lambda s: None),
                      program_cache=cache_dir, clock=clock)

    try:
        # -- phase A: burst trace with the autoscaler in the loop ------
        clock = traffic.VirtualClock()
        router = factory(3, clock)
        router.park(1)
        router.park(2)
        router.step()           # drain the parked slots into the pool
        scaler = traffic.SLOAutoscaler(
            router,
            slo=traffic.SLO(ttft_p99_s=0.5, queue_high=3.0,
                            queue_low=0.5),
            config=traffic.AutoscalerConfig(min_replicas=1, up_after=2,
                                            down_after=30, cooldown=5),
            clock=clock, name="bench")
        driver = traffic.TrafficDriver(
            router, burst, clock, quantum_s=quantum, name="bench-burst",
            on_tick=lambda d: scaler.observe())
        rep = driver.run()
        snap = scaler.snapshot()
        reaction = (max(snap["reaction_times_s"])
                    if snap["reaction_times_s"] else None)
        driver.release()
        scaler.release()
        router.shutdown()

        # -- phase B: capacity probe, 1 vs 3 replicas ------------------
        probe = burst.with_rate(8.0, duration_s=1.2)
        cap = traffic.probe_capacity(
            factory, probe, slo_ttft_s=0.25, replica_counts=(1, 3),
            qps_lo=1.0, qps_hi=150.0, iters=5, goodput_min=0.95,
            quantum_s=quantum, name="bench-capacity")

        # -- phase C: same spec chaos-composed -------------------------
        chaos = traffic.TrafficSpec.from_dict(burst.to_dict())
        chaos.name = "bench-chaos"
        chaos.fault_plan = {
            "name": "bench-traffic-chaos",
            "faults": [
                {"site": "serving.decode", "kind": "exception", "at": 8},
                {"site": "serving.traffic.tick", "kind": "qps_surge",
                 "at": 30, "payload": {"requests": 6}},
            ],
        }
        clock2 = traffic.VirtualClock()
        router2 = factory(2, clock2)
        driver2 = traffic.TrafficDriver(router2, chaos, clock2,
                                        quantum_s=quantum,
                                        name="bench-chaos")
        chaos_rep = driver2.run()
        failovers = router2.snapshot()["failovers"]
        driver2.release()
        router2.shutdown()

        out = {
            "traffic_goodput_under_slo_pct": round(
                100.0 * rep["goodput_frac"], 2),
            "traffic_offered_qps": rep["offered_qps"],
            "traffic_ttft_p99_ms": rep["ttft_p99_ms"],
            "traffic_scale_ups": snap["scale_ups"],
            "traffic_scale_downs": snap["scale_downs"],
            "traffic_scaleup_reaction_ticks": (
                int(round(reaction / quantum))
                if reaction is not None else None),
            "traffic_scaleup_reaction_ms": (
                round(reaction * 1e3, 3) if reaction is not None
                else None),
            "traffic_capacity_qps_1r": cap.max_qps(1),
            "traffic_capacity_qps_3r": cap.max_qps(3),
            "traffic_chaos_goodput_pct": round(
                100.0 * chaos_rep["goodput_frac"], 2),
            "traffic_chaos_token_loss": chaos_rep["token_loss"],
            "traffic_chaos_surges": chaos_rep["surge_injected"],
        }
        # lane contracts, gated BEFORE the result line prints
        assert snap["scale_ups"] >= 1 and reaction is not None, (
            "burst never triggered a scale-up")
        assert snap["scale_downs"] >= 1, (
            "autoscaler never drained the spare back after the burst")
        assert rep["goodput_frac"] >= 0.95, (
            f"goodput under SLO collapsed: {rep['goodput_frac']}")
        assert (cap.max_qps(1) or 0) > 0, "1-replica capacity probe dead"
        assert (cap.max_qps(3) or 0) >= (cap.max_qps(1) or 0), (
            "capacity not monotone in replica count: "
            f"{cap.max_qps(3)} < {cap.max_qps(1)}")
        assert failovers >= 1, "injected chaos crash never fired"
        assert chaos_rep["surge_injected"] >= 1, "qps_surge never fired"
        assert chaos_rep["goodput_frac"] >= 0.90, (
            f"chaos goodput out of budget: {chaos_rep['goodput_frac']}")
        assert chaos_rep["token_loss"] == 0, (
            f"token loss under chaos: {chaos_rep['token_loss']}")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


def worker_fleetserving():
    """Multi-host serving-fleet lane: a REAL 4-process fleet
    (controller + 2 replica workers + 1 prespawned spare, each its own
    OS process rendezvousing through ``paddle_tpu.distributed.launch``)
    driven through a mixed trace with one SIGKILL and one SIGSTOP-wedge
    mid-decode.  Pure CPU (the lane tracks cross-process failover
    detection latency, zero-loss migration, and warm respawn-elsewhere
    cost — all host-side effects), so its numbers ride along on every
    BENCH report.

    Reports (merged into every BENCH line):
      fleetserving_tokens_per_s       — fleet decode throughput under
                                        the trace, BOTH failovers in
                                        the measured window
      fleetserving_failover_detect_ms — median RPC-abort latency from
                                        fault to watchdog DEAD verdict
      fleetserving_respawn_ms         — respawn-elsewhere wall (boot on
                                        the spare rank, warm from the
                                        shared AOT cache)
      fleetserving_failover_count     — failovers absorbed (>= 2 by
                                        construction, or the lane fails)
    """
    import shutil
    import signal
    import socket
    import statistics
    import tempfile

    import numpy as np

    repo = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(repo, "paddle_tpu", "serving", "fleet",
                          "worker.py")
    tdir = tempfile.mkdtemp(prefix="ptpu_fleetsrv_bench_")
    out_dir = os.path.join(tdir, "out")
    cache_dir = os.path.join(tdir, "cache")
    os.makedirs(out_dir)
    os.makedirs(cache_dir)

    kill_rank, wedge_rank, spare_rank = 1, 2, 3
    rng = np.random.default_rng(0)
    prompts = [list(int(t) for t in rng.integers(1, 256, ln))
               for ln in (3, 7, 12, 5, 9, 2, 11, 6)]
    scenario = {
        "seed": 0,
        "model": {"vocab_size": 256, "hidden_size": 64,
                  "num_layers": 2, "num_heads": 4, "max_seq_len": 128,
                  "dropout": 0.0, "attention_dropout": 0.0},
        "engine": {"max_num_seqs": 4, "page_size": 4,
                   "max_model_len": 48,
                   "prefill_buckets": [8, 16, 32]},
        "cache_dir": cache_dir, "out_dir": out_dir,
        "controller_rank": 0, "worker_ranks": [kill_rank, wedge_rank],
        "spare_ranks": [spare_rank],
        "prompts": prompts,
        "sampling": [{"max_new_tokens": 10,
                      "temperature": 0.7 if i % 2 else 0.0,
                      "top_k": 20 if i % 3 else 0, "seed": i}
                     for i in range(len(prompts))],
        # one replica SIGKILLed, the other SIGSTOP-wedged mid-decode:
        # throughput is measured with BOTH recoveries in the loop
        "faults": {
            str(kill_rank): [{"site": "serving.fleet.step",
                              "kind": "rank_kill", "at": 5}],
            str(wedge_rank): [{"site": "serving.fleet.step",
                               "kind": "wedge", "at": 8}],
        },
        "serve_budget_s": 120.0, "finalize_s": 6.0,
    }
    scenario_path = os.path.join(tdir, "scenario.json")
    with open(scenario_path, "w") as fh:
        json.dump(scenario, fh)

    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PTPU_FLEET_TIMEOUT_S": "10",
        "PTPU_FLEET_KV_SLICE_S": "0.25",
        "PTPU_FLEET_HB_INTERVAL_S": "0.4",
        "PTPU_FLEET_RENDEZVOUS_TIMEOUT_S": "20",
        "PADDLE_LAUNCH_ID": f"benchfleetsrv{os.getpid()}",
    })
    for k in ("PADDLE_MASTER", "PADDLE_NNODES", "PADDLE_TRAINER_ID"):
        env.pop(k, None)
    procs = {
        r: subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--master", f"127.0.0.1:{port}", "--nnodes", "4",
             "--rank", str(r), worker, scenario_path],
            cwd=repo, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        for r in range(4)}
    ctl_path = os.path.join(out_dir, "controller.json")
    try:
        deadline = time.monotonic() + 180.0
        while not os.path.exists(ctl_path):
            assert procs[0].poll() is None, (
                f"controller exited rc={procs[0].returncode} without "
                f"a result")
            assert time.monotonic() < deadline, "fleet lane hung"
            time.sleep(0.2)
        # the wedged rank is frozen by a real SIGSTOP — put it down so
        # the reap below can finish
        if procs[wedge_rank].poll() is None:
            procs[wedge_rank].kill()
        for r, p in procs.items():
            p.wait(timeout=max(1.0, deadline - time.monotonic()))
        for r in (kill_rank, wedge_rank):
            assert procs[r].returncode == -signal.SIGKILL, (
                f"rank {r} rc={procs[r].returncode}")

        with open(ctl_path) as fh:
            res = json.load(fh)
        # lane contracts, gated BEFORE the result line prints
        assert len(res["fleet"]) == len(res["ref"]) == len(prompts)
        for want, got in zip(res["ref"], res["fleet"]):
            assert got["tokens"] == want["tokens"], (
                "data loss across failover")
            assert got["stream_tokens"] == got["tokens"], got
            assert got["stream_fins"] == 1, got
        dets = res["detections"]
        assert {d["rank"] for d in dets} == {kill_rank, wedge_rank}
        assert all(d["detect_s"] <= 11.0 for d in dets), dets
        assert res["snapshot"]["failovers"] >= 2, res["snapshot"]
        assert res["respawn_ms"], "no respawn recorded"
        assert res["boots"][0].get("warm") is True, (
            f"respawn on the spare was a cold boot: {res['boots']}")
        out = {
            "fleetserving_tokens_per_s": res["tokens_per_s"],
            "fleetserving_failover_detect_ms": round(
                statistics.median(d["detect_s"] for d in dets) * 1e3,
                1),
            "fleetserving_respawn_ms": round(res["respawn_ms"][0], 1),
            "fleetserving_failover_count": res["snapshot"]["failovers"],
            "fleetserving_replicas": 2,
            "fleetserving_requests": len(prompts),
        }
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


def worker_quant():
    """Quantization lane: the two quantized memory planes' density
    numbers (paddle_tpu/quantization — ROADMAP item 2).  Pure CPU
    accounting over the serving-target geometry, never touches the
    chip, so every BENCH report records what quantized storage buys:

      quant_kv_bytes_per_token_{f32,bf16,int8} — pool storage per token
      quant_kv_vs_{bf16,f32}_ratio             — the perfgate-gated
                                                 density win (<= 0.55x
                                                 bf16 asserted here too)
      quant_seqs_at_budget_{f32,bf16,int8}     — concurrent max-length
                                                 sequences inside the
                                                 FIXED default-f32-pool
                                                 HBM budget
      quant_allreduce_bytes / _wide / _ratio   — EQuARX wire model for
                                                 a 1M-element gradient
                                                 sync at axis size 8
    """
    t0 = time.time()
    tools_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools")
    sys.path.insert(0, tools_dir)
    try:
        import perfgate
        import jax.numpy as jnp

        from paddle_tpu.quantization.collectives import \
            quantized_all_reduce_wire_bytes

        build = perfgate._quant_engines()
        engines = {}
        try:
            engines["f32"] = build()
            engines["bf16"] = build(dtype=jnp.bfloat16)
            engines["int8"] = build(kv_cache_dtype="int8")
            bpt = {k: e.kv_bytes_per_token for k, e in engines.items()}
            # fixed HBM budget = the default f32 pool's bytes; capacity
            # = whole max-length sequences that fit inside it
            budget = engines["f32"].kv_pool_bytes
            seq_len = engines["f32"].config.max_model_len
            caps = {k: int(budget // (bpt[k] * seq_len))
                    for k in engines}
        finally:
            for e in engines.values():
                e.shutdown()
        wire = quantized_all_reduce_wire_bytes(1 << 20, axis_size=8)
        out = {
            "quant_kv_bytes_per_token_f32": round(bpt["f32"], 2),
            "quant_kv_bytes_per_token_bf16": round(bpt["bf16"], 2),
            "quant_kv_bytes_per_token_int8": round(bpt["int8"], 2),
            "quant_kv_vs_bf16_ratio": round(bpt["int8"] / bpt["bf16"], 4),
            "quant_kv_vs_f32_ratio": round(bpt["int8"] / bpt["f32"], 4),
            "quant_seqs_at_budget_f32": caps["f32"],
            "quant_seqs_at_budget_bf16": caps["bf16"],
            "quant_seqs_at_budget_int8": caps["int8"],
            "quant_allreduce_bytes": wire["allreduce_bytes"],
            "quant_allreduce_bytes_wide": wire["allreduce_bytes_wide"],
            "quant_allreduce_vs_wide_ratio":
                wire["allreduce_quant_vs_wide_ratio"],
            "quant_elapsed_s": round(time.time() - t0, 2),
        }
        # lane contracts, checked BEFORE the result line prints: the
        # density win the docs claim must hold on the numbers reported
        assert out["quant_kv_vs_bf16_ratio"] <= 0.55, out
        assert caps["int8"] >= 2 * caps["f32"], out
    finally:
        sys.path.remove(tools_dir)
    print(json.dumps(out), flush=True)
    return 0


def worker_numlint():
    """Static-analysis lane #3: numlint's numerics & precision-flow
    audit of the flagship programs (finding count + per-rule
    breakdown).  Pure CPU trace, concurrent with the device lanes — every
    BENCH run records the numerics-hazard picture next to the
    shardlint cost audit."""
    tools_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools")
    sys.path.insert(0, tools_dir)
    try:
        import numlint
        out = numlint.bench_report()
    finally:
        sys.path.remove(tools_dir)
    print(json.dumps(out), flush=True)
    return 0


def worker_kernlint():
    """Static-analysis lane #4: kernlint's KLxxx audit of every Pallas
    kernel interior (finding count + per-rule breakdown over the
    flagship, the serving programs, and each ops/pallas kernel traced
    standalone in interpret mode).  Pure CPU trace, concurrent with
    the device lanes — every BENCH run records the kernel-interior hazard
    picture next to the numerics audit."""
    tools_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools")
    sys.path.insert(0, tools_dir)
    try:
        import kernlint
        out = kernlint.bench_report()
    finally:
        sys.path.remove(tools_dir)
    print(json.dumps(out), flush=True)
    return 0


def worker_racelint():
    """Static-analysis lane #2: racelint's host-concurrency audit of
    the whole package (finding count + per-rule breakdown).  Pure
    stdlib AST — no jax import at all — so every BENCH run records
    the concurrency-hazard picture next to the shardlint cost audit."""
    repo = os.path.dirname(os.path.abspath(__file__))
    tools_dir = os.path.join(repo, "tools")
    sys.path.insert(0, tools_dir)
    try:
        from _bootstrap import light_paddle_tpu
        light_paddle_tpu(repo)
        from paddle_tpu.analysis import race_rules
        out = race_rules.bench_report()
    finally:
        sys.path.remove(tools_dir)
    print(json.dumps(out), flush=True)
    return 0


def worker_protolint():
    """Static-analysis lane #5: protolint's coordination-KV protocol
    audit of the whole package (finding count + per-rule breakdown).
    Pure stdlib AST — no jax import at all — so every BENCH run
    records the KV-protocol hygiene picture next to the concurrency
    audit."""
    repo = os.path.dirname(os.path.abspath(__file__))
    tools_dir = os.path.join(repo, "tools")
    sys.path.insert(0, tools_dir)
    try:
        from _bootstrap import light_paddle_tpu
        light_paddle_tpu(repo)
        from paddle_tpu.analysis import proto_rules
        out = proto_rules.bench_report()
    finally:
        sys.path.remove(tools_dir)
    print(json.dumps(out), flush=True)
    return 0


def _tpu():
    """(device, ChipSpec) of the chip a device lane runs on.  No TPU, or
    a device kind with no published peaks, is an error — a device lane
    never answers for a missing or unknown device."""
    from paddle_tpu.observability.profile import attached_chip
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    dev, chip = attached_chip()
    enable_compile_cache()
    return dev, chip


def _resnet_line(dev, chip, img_s, extra):
    out = {
        "metric": "resnet50_train_throughput",
        "unit": "images/sec/chip",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "value": round(img_s, 2),
        "vs_baseline": round(img_s / BASELINE_IMG_S, 4),
        "mfu": round(img_s * _RESNET50_TRAIN_FLOPS / chip.peak_flops, 4),
    }
    out.update(extra)
    return out


def worker_resnet():
    dev, chip = _tpu()
    batch, warmup, iters = 256, 5, 25  # ~125 ms/step: timing noise <1%

    dt, ts, x, y = _resnet_variant(False, batch, warmup, iters)
    img_s = batch * iters / dt
    line = _resnet_line(dev, chip, img_s, _resnet_extra(
        chip, dt, iters, batch, ts, x, y, False))

    # HBM-bound step + idle MXU: rematerializing the residual stages can
    # net throughput — measure and report the faster variant
    it2 = max(10, iters // 2)
    dt2, ts2, x2, y2 = _resnet_variant(True, batch, 3, it2)
    img_s2 = batch * it2 / dt2
    if img_s2 > img_s:
        line = _resnet_line(dev, chip, img_s2, _resnet_extra(
            chip, dt2, it2, batch, ts2, x2, y2, True))
    print(json.dumps(line), flush=True)
    return 0


def _mlm_worker(prefix, tok_key, bench_fn):
    """Shared BERT/ERNIE worker at batch 48 (measured on v5e 2026-07-31
    for BERT: 48 -> 91.6k tok/s, 32 -> 86.5k, 16 -> 82.3k, 56 -> 88.3k
    regresses, 64 -> HBM OOM).  Per-lane platform tag: a lane's numbers
    stay attributable when merged next to another lane's."""
    dev, chip = _tpu()
    batch = 48
    tok_s, extra = bench_fn(batch)
    out = {tok_key: round(tok_s, 2),
           f"{prefix}_platform": dev.platform,
           f"{prefix}_batch": batch}
    fpt = extra.pop("_flops_per_token")
    out.update(extra)
    out[f"{prefix}_mfu"] = round(tok_s * fpt / chip.peak_flops, 4)
    print(json.dumps(out), flush=True)
    return 0


def worker_bert():
    return _mlm_worker("bert", "bert_base_tokens_s", _bench_bert)


def worker_ernie():
    return _mlm_worker("ernie", "ernie_tokens_s", _bench_ernie)


# --------------------------------------------------------------- orchestrator
def _spawn(lane, cpu):
    """Start one worker in its own session (so its whole process group
    can be stopped), stdout piped back, stderr passed through."""
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), f"--worker-{lane}"],
        env=env, stdout=subprocess.PIPE, text=True, start_new_session=True)


def _kill_process_group(proc):
    """SIGKILL `proc`'s whole process group (it was spawned with
    start_new_session, so its pid IS the pgid and any children die with
    it).  Returns True when the group was signalled."""
    import signal
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (OSError, ProcessLookupError):
        return False
    return True


def _finish(proc, limit_s):
    """Wait for a worker.  Returns (exit code, the last JSON line it
    printed or None).  A worker past `limit_s` is killed with its
    process group and reported as exit code 124."""
    try:
        out, _ = proc.communicate(timeout=limit_s)
        rc = proc.returncode
    except subprocess.TimeoutExpired:
        _kill_process_group(proc)
        out, _ = proc.communicate()
        rc = 124
    if rc != 0:
        return rc, None
    for line in reversed(out.strip().splitlines()):
        try:
            return rc, json.loads(line)
        except json.JSONDecodeError:
            continue
    return 1, None


def main():
    for arg in sys.argv[1:]:
        lane = arg.removeprefix("--worker-")
        if arg.startswith("--worker-") and lane in dict(
                DEVICE_LANES + CPU_LANES):
            return globals()[f"worker_{lane}"]()

    merged, rc = {}, 0
    # the CPU lanes never touch the chip: they run concurrently with the
    # device lanes, which own it one after another
    cpu_procs = [(lane, limit, _spawn(lane, cpu=True))
                 for lane, limit in CPU_LANES]
    lanes = [(lane, _finish(_spawn(lane, cpu=False), limit))
             for lane, limit in DEVICE_LANES]
    lanes += [(lane, _finish(proc, limit)) for lane, limit, proc in cpu_procs]
    for lane, (lane_rc, result) in lanes:
        if result is not None:
            merged.update(result)
        else:
            merged[f"{lane}_error"] = f"worker exited {lane_rc}"
            rc = rc or lane_rc
    print(json.dumps(merged))
    return rc


if __name__ == "__main__":
    sys.exit(main())
