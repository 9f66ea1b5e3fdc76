"""Real multi-process CPU collectives and fleet fault tolerance:
OS processes bootstrapped by ``paddle_tpu.distributed.launch`` +
``jax.distributed.initialize``.

Everything else in the suite runs multi-"device" inside ONE process
(the 8 virtual CPU devices conftest forces); these tests are the proof
that the launcher's coordinator bootstrap and the eager multi-host
collective path work across genuine process boundaries (VERDICT item
9): children rendezvous over a local gRPC coordinator, see the true
``process_count()``, and ``all_reduce`` returns the cross-process sum
on every rank.

``test_fleet_sigkill_reconfigure_resume`` is the chaos acceptance
proof for PR 14 (fleet-grade fault tolerance): one of 3 ranks is
SIGKILLed mid-training, the survivors detect it within the configured
timeout budget (no indefinite hang anywhere on the coordination path),
reconfigure to world size 2, reload the quorum checkpoint, and the
resumed loss trajectory is IDENTICAL to a fault-free world-size-2 run
restored from the same checkpoint.  Measured ~10-15s wall for both
phases, inside the whole chaos gate's 480s wall budget
(tools/lint_all.py `_GATE_TIMEOUT_S`, which also covers
test_resilience.py + test_fleet.py).

Kept deliberately small (1 CPU device per child, tiny collectives)
so the wall cost is coordinator startup, not compute; generous
deadlines absorb slow CI boxes, and failure modes (port clash, hung
rendezvous) surface as missing result files with captured child logs.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "_multiprocess_worker.py")
FLEET_WORKER = os.path.join(HERE, "_fleet_worker.py")
SENTINEL_WORKER = os.path.join(HERE, "_sentinel_worker.py")
DEADLINE_S = 120.0
FLEET_DEADLINE_S = 150.0


def _free_port():
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(extra=None):
    env = dict(os.environ)
    # fresh processes: pin the CPU backend explicitly (conftest's env
    # is inherited but make the contract local), ONE device per process
    # so the multi-process world is unmistakably cross-process
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("PADDLE_MASTER", None)
    env.pop("PADDLE_NNODES", None)
    env.pop("PADDLE_TRAINER_ID", None)
    env.pop("PADDLE_LAUNCH_ID", None)
    env.update(extra or {})
    return env


def _spawn(rank, port, out_dir):
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--master", f"127.0.0.1:{port}", "--nnodes", "2",
         "--rank", str(rank), WORKER, out_dir],
        cwd=os.path.dirname(HERE), env=_child_env(),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def test_two_process_all_reduce_via_launch(tmp_path):
    port = _free_port()
    procs = [_spawn(rank, port, str(tmp_path)) for rank in (0, 1)]
    outputs = {}
    try:
        deadline = time.monotonic() + DEADLINE_S
        for rank, p in enumerate(procs):
            remaining = max(1.0, deadline - time.monotonic())
            try:
                out, _ = p.communicate(timeout=remaining)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
                pytest.fail(
                    f"rank {rank} did not finish within {DEADLINE_S}s "
                    f"— coordinator rendezvous hung?\n--- child log "
                    f"---\n{out[-2000:]}")
            outputs[rank] = out
            assert p.returncode == 0, (
                f"rank {rank} exited rc={p.returncode}\n--- child log "
                f"---\n{out[-2000:]}")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    results = {}
    for rank in (0, 1):
        path = tmp_path / f"rank{rank}.json"
        assert path.exists(), (
            f"rank {rank} wrote no result\n--- child log ---\n"
            f"{outputs.get(rank, '')[-2000:]}")
        results[rank] = json.loads(path.read_text())

    for rank, res in results.items():
        assert res["nprocs"] == 2, res
        # SUM over ranks: [1, 10] + [2, 20] on every process
        assert res["reduced"] == [3.0, 30.0], res
        assert res["ranks_seen"] == [0, 1], res
        assert res["broadcast"] == 101.0, res    # rank 1's value
    assert {results[0]["rank"], results[1]["rank"]} == {0, 1}


# ---------------------------------------------------------------------------
# Fleet fault tolerance: SIGKILL -> detect -> reconfigure -> resume
# ---------------------------------------------------------------------------

# tight-but-realistic budgets: heartbeat every 0.4s, SUSPECT at 1.2s,
# DEAD at 2.4s, collective deadline 10s — detection is expected at
# ~2.5-4s via the DEAD-verdict abort, always under the 10s hard budget
FLEET_ENV = {
    "PTPU_FLEET_TIMEOUT_S": "10",
    "PTPU_FLEET_KV_SLICE_S": "0.25",
    "PTPU_FLEET_HB_INTERVAL_S": "0.4",
    "PTPU_FLEET_RENDEZVOUS_TIMEOUT_S": "20",
}
KILL_RANK, KILL_STEP, CKPT_STEP, TOTAL_STEPS = 2, 8, 5, 12


def _spawn_fleet(rank, port, nnodes, out_dir, ckpt_dir, mode,
                 launch_id):
    env = _child_env({**FLEET_ENV, "PADDLE_LAUNCH_ID": launch_id})
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--master", f"127.0.0.1:{port}", "--nnodes", str(nnodes),
         "--rank", str(rank), FLEET_WORKER, out_dir, ckpt_dir, mode,
         str(KILL_RANK), str(KILL_STEP), str(CKPT_STEP),
         str(TOTAL_STEPS)],
        cwd=os.path.dirname(HERE), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _collect(procs, deadline_s, expect_killed=()):
    """Wait for every child under ONE deadline; any overrun is an
    indefinite-hang failure (the thing the fleet layer forbids)."""
    outputs, codes = {}, {}
    deadline = time.monotonic() + deadline_s
    for rank, p in procs.items():
        remaining = max(1.0, deadline - time.monotonic())
        try:
            out, _ = p.communicate(timeout=remaining)
        except subprocess.TimeoutExpired:
            for q in procs.values():
                if q.poll() is None:
                    q.kill()
            out, _ = p.communicate()
            pytest.fail(
                f"rank {rank} still running after {deadline_s}s — a "
                f"coordination-path hang the fleet layer must prevent"
                f"\n--- child log ---\n{out[-2000:]}")
        outputs[rank], codes[rank] = out, p.returncode
    for rank, p in procs.items():
        if rank in expect_killed:
            assert codes[rank] == -signal.SIGKILL, (
                f"rank {rank} should have died by SIGKILL, rc="
                f"{codes[rank]}\n{outputs[rank][-2000:]}")
        else:
            assert codes[rank] == 0, (
                f"rank {rank} rc={codes[rank]}\n--- child log ---\n"
                f"{outputs[rank][-2000:]}")
    return outputs


@pytest.mark.chaos
@pytest.mark.slow
def test_fleet_sigkill_reconfigure_resume(tmp_path):
    # slow: ~12s of two 3-process spawn phases; the chaos marker keeps
    # it in the lint_all chaos gate, which runs slow chaos tests too
    out_dir, ckpt_dir = tmp_path / "out", tmp_path / "ckpt"
    out_dir.mkdir()

    # ---- phase A: 3 ranks, rank 2 SIGKILLed at step 8 ----
    port = _free_port()
    procs = {r: _spawn_fleet(r, port, 3, str(out_dir), str(ckpt_dir),
                             "chaos", "fleetA")
             for r in range(3)}
    try:
        outputs = _collect(procs, FLEET_DEADLINE_S,
                           expect_killed={KILL_RANK})
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()

    chaos = {}
    for r in (0, 1):
        path = out_dir / f"chaos-rank{r}.json"
        assert path.exists(), (
            f"survivor {r} wrote no result\n--- child log ---\n"
            f"{outputs[r][-2000:]}")
        chaos[r] = json.loads(path.read_text())
    assert not (out_dir / f"chaos-rank{KILL_RANK}.json").exists()

    budget = float(FLEET_ENV["PTPU_FLEET_TIMEOUT_S"])
    for r, res in chaos.items():
        det = res["detection"]
        assert det is not None, f"survivor {r} never detected the kill"
        assert det["missing_rank"] == KILL_RANK, det
        # detection within the configured budget (+ one slice of slack)
        assert det["waited_s"] <= budget + 1.0, det
        assert det["verdict"] in ("dead-verdict", "deadline"), det
        nw = res["new_world"]
        assert nw["size"] == 2 and nw["members"] == [0, 1], nw
        assert nw["generation"] == 1, nw
        assert res["reshard_ok"] is True, res
        assert res["final_world"]["size"] == 2, res
        assert len(res["losses_resumed"]) == TOTAL_STEPS - CKPT_STEP
    # the all_reduce'd trajectory is fleet-global: survivors agree
    assert chaos[0]["losses_resumed"] == chaos[1]["losses_resumed"]

    # ---- phase B: fault-free world-size-2 run from the SAME ckpt ----
    port = _free_port()
    procs = {r: _spawn_fleet(r, port, 2, str(out_dir), str(ckpt_dir),
                             "baseline", "fleetB")
             for r in range(2)}
    try:
        outputs = _collect(procs, FLEET_DEADLINE_S)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()

    base = {}
    for r in (0, 1):
        path = out_dir / f"baseline-rank{r}.json"
        assert path.exists(), (
            f"baseline rank {r} wrote no result\n--- child log ---\n"
            f"{outputs[r][-2000:]}")
        base[r] = json.loads(path.read_text())

    # THE acceptance identity: survivors' resumed trajectory is exactly
    # the fault-free world-size-2 trajectory from the same quorum
    # checkpoint — elastic recovery loses nothing and invents nothing
    assert base[0]["losses_resumed"] == base[1]["losses_resumed"]
    assert chaos[0]["losses_resumed"] == base[0]["losses_resumed"], (
        "resumed-after-SIGKILL trajectory diverged from the fault-free "
        "world-size-2 trajectory")


# ---------------------------------------------------------------------------
# Sentinel: SDC digest vote -> quarantine -> reconfigure -> resume
# ---------------------------------------------------------------------------

SDC_RANK, SDC_STEP, SDC_TOTAL = 2, 4, 8


def _spawn_sentinel(rank, port, out_dir):
    env = _child_env({**FLEET_ENV, "PADDLE_LAUNCH_ID": "sentinelA"})
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--master", f"127.0.0.1:{port}", "--nnodes", "3",
         "--rank", str(rank), SENTINEL_WORKER, out_dir,
         str(SDC_RANK), str(SDC_STEP), str(SDC_TOTAL)],
        cwd=os.path.dirname(HERE), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


@pytest.mark.chaos
def test_sentinel_digest_vote_names_sdc_rank(tmp_path):
    """The PR 15 SDC-localization proof on a REAL 3-process fleet: a
    silent (finite, low-bit) bitflip lands in one rank's weight
    replica; the per-step cross-rank digest vote names that rank on
    EVERY process (including the corrupted one), the survivors
    quarantine it (sticky SUSPECT on the watchdog) and
    reconfigure-and-resume at world size 2 with finite, fleet-agreed
    losses — the corruption never reaches a gradient sync."""
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    port = _free_port()
    procs = {r: _spawn_sentinel(r, port, str(out_dir))
             for r in range(3)}
    try:
        outputs = _collect(procs, FLEET_DEADLINE_S)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()

    res = {}
    for r in range(3):
        path = out_dir / f"vote-rank{r}.json"
        assert path.exists(), (
            f"rank {r} wrote no result\n--- child log ---\n"
            f"{outputs[r][-2000:]}")
        res[r] = json.loads(path.read_text())

    # every rank's vote named the injected rank — including itself
    for r in range(3):
        vote = res[r]["vote"]
        assert vote is not None, f"rank {r} never saw a dissent"
        assert vote["suspects"] == [SDC_RANK], (r, vote)
        assert vote["step"] == SDC_STEP, (r, vote)
        assert vote["self_suspect"] == (r == SDC_RANK), (r, vote)

    # the suspect quarantined itself out; survivors reconfigured
    assert res[SDC_RANK]["exited_as_suspect"] is True
    assert res[SDC_RANK]["new_world"] is None
    for r in (0, 1):
        assert res[r]["monitor_suspects"] == [SDC_RANK], res[r]
        nw = res[r]["new_world"]
        assert nw["members"] == [0, 1] and nw["size"] == 2, nw
        assert nw["generation"] == 1, nw
        assert res[r]["final_world"]["size"] == 2, res[r]
        assert len(res[r]["losses_resumed"]) == SDC_TOTAL - SDC_STEP
        assert all(np.isfinite(v) for v in res[r]["losses_resumed"])
    # the all_reduce'd resumed trajectory is fleet-global
    assert res[0]["losses_resumed"] == res[1]["losses_resumed"]


# ---------------------------------------------------------------------------
# Serving fleet: SIGKILL + SIGSTOP-wedge mid-decode -> DEAD verdicts ->
# zero-loss failover -> warm respawn on the spare -> disagg handoff
# ---------------------------------------------------------------------------

FLEETSERVING_WORKER = os.path.join(
    os.path.dirname(HERE), "paddle_tpu", "serving", "fleet", "worker.py")
SRV_KILL_RANK, SRV_WEDGE_RANK, SRV_SPARE_RANK = 2, 3, 4
FLEETSERVING_DEADLINE_S = 240.0


def _fleetserving_scenario(out_dir, cache_dir):
    rng = np.random.default_rng(1234)
    lens = [3, 7, 12, 5, 9, 2, 11, 6, 4]
    prompts = [[int(t) for t in rng.integers(1, 256, ln)]
               for ln in lens]
    sampling = [{"max_new_tokens": 10,
                 "temperature": 0.7 if i % 2 else 0.0,
                 "top_k": 20 if i % 3 else 0, "seed": i}
                for i in range(len(prompts))]
    dlens = [4, 8, 6]
    dprompts = [[int(t) for t in rng.integers(1, 256, ln)]
                for ln in dlens]
    dsampling = [{"max_new_tokens": 8, "temperature": 0.5,
                  "top_k": 16, "seed": 50 + i}
                 for i in range(len(dprompts))]
    return {
        "seed": 0,
        "model": {"vocab_size": 256, "hidden_size": 64,
                  "num_layers": 2, "num_heads": 4, "max_seq_len": 128,
                  "dropout": 0.0, "attention_dropout": 0.0},
        "engine": {"max_num_seqs": 4, "page_size": 4,
                   "max_model_len": 48,
                   "prefill_buckets": [8, 16, 32]},
        "cache_dir": cache_dir,
        "out_dir": out_dir,
        "controller_rank": 0,
        "worker_ranks": [1, 2, 3],
        "spare_ranks": [SRV_SPARE_RANK],
        "prompts": prompts,
        "sampling": sampling,
        "disagg_prompts": dprompts,
        "disagg_sampling": dsampling,
        # both faults fire MID-DECODE (each replica owns ~3 requests x
        # 10 tokens, so its step counter runs well past both indices):
        # rank 2 dies outright, rank 3 freezes whole-process (its
        # heartbeat thread too) — only the watchdog can unblock that
        "faults": {
            str(SRV_KILL_RANK): [{"site": "serving.fleet.step",
                                  "kind": "rank_kill", "at": 5}],
            str(SRV_WEDGE_RANK): [{"site": "serving.fleet.step",
                                   "kind": "wedge", "at": 7}],
        },
        "serve_budget_s": 120.0,
        "finalize_s": 6.0,
    }


def _spawn_fleetserving(rank, port, scenario_path, extra_env=None):
    env = _child_env({**FLEET_ENV, "PADDLE_LAUNCH_ID": "fleetsrvA",
                      **(extra_env or {})})
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--master", f"127.0.0.1:{port}", "--nnodes", "5",
         "--rank", str(rank), FLEETSERVING_WORKER, scenario_path],
        cwd=os.path.dirname(HERE), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


@pytest.mark.chaos
@pytest.mark.slow
def test_serving_fleet_sigkill_wedge_failover(tmp_path):
    """The ISSUE 16 acceptance proof on a REAL 5-process fleet
    (controller + 3 replicas + 1 spare).  Slow-marked (~30s of 5-way
    process spawn + wedge deadlines); the chaos marker keeps it in the
    lint_all chaos gate, so every standalone `python tools/lint_all.py`
    still runs it.  One replica SIGKILLed and one
    SIGSTOP-wedged mid-decode, both drawn DEAD verdicts within the
    configured budget, every affected request migrated with zero token
    loss (streams exactly-once), the fleet output token-identical to
    the fault-free monolithic reference, the respawn landing on the
    spare rank booting WARM from the shared AOT cache, and the
    disaggregated prefill/decode handoff token-identical — with every
    live replica's lifetime compile count inside the bound."""
    out_dir, cache_dir = tmp_path / "out", tmp_path / "cache"
    out_dir.mkdir()
    cache_dir.mkdir()
    spool_dir = tmp_path / "spool"            # PR 20: fleet tracing ON
    spool_dir.mkdir()
    scenario = _fleetserving_scenario(str(out_dir), str(cache_dir))
    scenario_path = tmp_path / "scenario.json"
    scenario_path.write_text(json.dumps(scenario))

    port = _free_port()
    procs = {r: _spawn_fleetserving(
                 r, port, str(scenario_path),
                 extra_env={"PTPU_OBS_SPOOL_DIR": str(spool_dir)})
             for r in range(5)}
    ctl_path = out_dir / "controller.json"
    try:
        # the wedged rank is frozen by a real SIGSTOP — it can never
        # exit on its own.  Wait for the controller's verdict file,
        # then put it down so _collect can reap everyone.
        deadline = time.monotonic() + FLEETSERVING_DEADLINE_S
        while not ctl_path.exists():
            if procs[0].poll() is not None:
                out, _ = procs[0].communicate()
                for p in procs.values():
                    if p.poll() is None:
                        p.kill()
                pytest.fail(
                    f"controller exited rc={procs[0].returncode} "
                    f"without a result\n--- controller log ---\n"
                    f"{out[-3000:]}")
            if time.monotonic() > deadline:
                for p in procs.values():
                    if p.poll() is None:
                        p.kill()
                out, _ = procs[0].communicate()
                pytest.fail(
                    f"controller wrote no result within "
                    f"{FLEETSERVING_DEADLINE_S}s\n--- controller log "
                    f"---\n{out[-3000:]}")
            time.sleep(0.2)
        if procs[SRV_WEDGE_RANK].poll() is None:
            procs[SRV_WEDGE_RANK].kill()
        outputs = _collect(procs, 60.0,
                           expect_killed={SRV_KILL_RANK,
                                          SRV_WEDGE_RANK})
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()

    res = json.loads(ctl_path.read_text())

    # ---- zero token loss + token identity with the fault-free
    # monolithic reference, despite one SIGKILL and one wedge
    ref, flt = res["ref"], res["fleet"]
    assert len(flt) == len(ref) == 9
    for i, (want, got) in enumerate(zip(ref, flt)):
        assert got["tokens"] == want["tokens"], (
            f"request {i} diverged after failover: {got} != {want}")
        assert got["finish_reason"] == want["finish_reason"], (i, got)
        # exactly-once streams: the streamed prefix IS the history
        assert got["stream_tokens"] == got["tokens"], (i, got)
        assert got["stream_fins"] == 1, (i, got)
    assert sum(r["migrations"] for r in flt) >= 1
    assert res["snapshot"]["failovers"] >= 2, res["snapshot"]

    # ---- both faults drew bounded-time watchdog verdicts
    budget = float(FLEET_ENV["PTPU_FLEET_TIMEOUT_S"])
    dets = res["detections"]
    assert {d["rank"] for d in dets} == {SRV_KILL_RANK,
                                         SRV_WEDGE_RANK}, dets
    for d in dets:
        assert d["verdict"] in ("dead-verdict", "deadline"), d
        assert d["detect_s"] <= budget + 1.0, d

    # ---- respawn-elsewhere: the SIGKILLed slot reboots on the spare
    # rank, WARM from the shared AOT cache (the 38x path); the wedged
    # slot found the pool empty and stays parked (graceful degradation)
    assert res["assigned"]["0"] == 1, res["assigned"]
    assert res["assigned"]["1"] == SRV_SPARE_RANK, res["assigned"]
    assert res["assigned"]["2"] == SRV_WEDGE_RANK, res["assigned"]
    assert res["respawn_ms"] and res["respawn_ms"][0] > 0.0, res
    boots = res["boots"]
    assert boots[1].get("warm") is True, (
        f"respawn on the spare was a cold boot: {boots[1]}")

    # ---- disaggregated prefill/decode across two live replicas:
    # token-identical to the monolithic reference
    assert res["disagg_ranks"], "disagg phase never ran"
    assert [d["tokens"] for d in res["disagg"]] == \
        [d["tokens"] for d in res["disagg_ref"]]
    assert res["handoffs"] >= 1 and res["handoff_bytes"] > 0

    # ---- bounded-compile contract audited over the wire on every
    # live replica (respawned spare included)
    assert res["audits"], res
    for rank, audit in res["audits"].items():
        assert "error" not in audit, (rank, audit)
        assert audit["compiled"] <= audit["bound"], (rank, audit)
        assert audit["cache_loads"] > 0, (rank, audit)

    # ---- surviving replicas checked out cleanly with their own audit
    for r in (1, SRV_SPARE_RANK):
        path = out_dir / f"replica-rank{r}.json"
        assert path.exists(), (
            f"replica {r} wrote no result\n--- child log ---\n"
            f"{outputs[r][-2000:]}")
        rep = json.loads(path.read_text())
        assert rep["compiled"] <= rep["bound"], rep
        assert rep["steps"] > 0, rep
    assert not (out_dir / f"replica-rank{SRV_KILL_RANK}.json").exists()
    assert not (out_dir
                / f"replica-rank{SRV_WEDGE_RANK}.json").exists()

    # ================================================= PR 20 fleettrace
    # the same chaos run, with telemetry spooling armed in every
    # process, must yield the three observability acceptance artifacts
    from paddle_tpu.observability import fleettrace

    tel = fleettrace.merge_spools(str(spool_dir))
    summary = tel.summary()

    # ---- (a) merged chrome trace with spans from ALL 5 processes on
    # aligned clocks: every rank spooled (the SIGKILLed and wedged
    # spools survive as flushed prefixes), every non-ref rank completed
    # the KV clock handshake (a real offset, not the wall fallback)
    assert summary["processes"] == 5, summary
    assert sorted(summary["ranks"]) == [0, 1, 2, 3, 4], summary
    for p in tel.processes:
        assert p.spans, f"rank {p.rank} spooled no spans"
        assert p.clock is not None, f"rank {p.rank} has no clock anchor"
        if p.rank != 0:
            assert p.clock.get("offset_ns") is not None, (
                f"rank {p.rank} never completed the clock handshake")
    chrome = tel.chrome_trace()
    span_pids = {e["pid"] for e in chrome["traceEvents"]
                 if e.get("cat") == "span"}
    assert span_pids == {0, 1, 2, 3, 4}, span_pids

    # ---- (b) a COMPLETE per-request timeline for a request migrated
    # across the dead rank: admission -> prefill -> failover adoption
    # -> finish, exactly-once, spanning >= 2 processes
    tls = [tel.timeline(t) for t in tel.traces()]
    migrated = [t for t in tls
                if t and t["complete"] and t["migrations"] >= 1]
    assert migrated, (
        f"no complete migrated-request timeline among "
        f"{[(t['request'], t['complete'], t['migrations']) for t in tls if t]}")
    mt = migrated[0]
    assert mt["admissions"] == 1 and mt["finishes"] == 1, mt
    assert len(mt["processes"]) >= 2, mt
    span_names = {e["name"] for e in mt["spans"]}
    assert {"serving.router.admit", "serving.prefill", "serving.adopt",
            "serving.finish"} <= span_names, span_names
    assert mt["stages"].get("total_s", 0) > 0, mt["stages"]
    assert "adoption_s" in mt["stages"], mt["stages"]

    # ---- (c) the crash flight recorder: the controller's DEAD-verdict
    # hook wrote a post-mortem for the SIGKILLed rank naming the
    # requests in flight on it at death
    pms = res.get("postmortems", {})
    assert str(SRV_KILL_RANK) in pms, (
        f"controller recorded no post-mortem for the SIGKILLed rank: "
        f"{sorted(pms)}")
    pm = pms[str(SRV_KILL_RANK)]
    assert pm["in_flight_requests"], pm
    assert pm["spans_total"] > 0, pm
    pm_path = spool_dir / f"postmortem-r{SRV_KILL_RANK}.json"
    assert pm_path.exists(), "post-mortem file missing next to spools"
    on_disk = json.loads(pm_path.read_text())
    assert on_disk["in_flight_requests"] == pm["in_flight_requests"]
    assert on_disk["last_spans"], on_disk.keys()
