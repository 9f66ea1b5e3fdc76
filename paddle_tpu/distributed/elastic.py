"""Failure detection / elastic supervision.

Reference parity: python/paddle/distributed/elastic (+ fleet elastic
manager): etcd-backed node watchdogs that detect dead trainers and
trigger job restart. TPU-native design: JAX is single-controller per host,
so in-process failure detection is (a) a step-progress watchdog (training
stall = hung collective / hung device — the moral equivalent of a NCCL
timeout) and (b) multi-host liveness via the jax.distributed coordination
service, which already evicts dead hosts at barrier timeout. The watchdog
runs as a daemon thread; on stall it snapshots live stacks (for the bug
report) and invokes the user callback (default: log + optional abort).
"""
from __future__ import annotations

import faulthandler
import os
import sys
import threading
import time


class Watchdog:
    """Step-progress heartbeat. Call beat() every train step; if no beat
    arrives within `timeout` seconds the stall callback fires (once per
    stall episode).

    Usage:
        wd = Watchdog(timeout=300, abort=True)
        for batch in loader:
            train_step(batch)
            wd.beat(step)
        wd.stop()
    """

    def __init__(self, timeout=300.0, on_stall=None, abort=False,
                 poll_interval=None):
        self.timeout = float(timeout)
        self.on_stall = on_stall
        self.abort = abort
        self._poll = poll_interval or min(self.timeout / 4, 10.0)
        self._last_beat = time.monotonic()
        self._last_step = None
        self._stalled = False
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="paddle_tpu-watchdog")
        self._thread.start()

    def beat(self, step=None):
        self._last_beat = time.monotonic()
        self._last_step = step
        self._stalled = False

    def _run(self):
        while not self._stop.wait(self._poll):
            idle = time.monotonic() - self._last_beat
            if idle > self.timeout and not self._stalled:
                self._stalled = True
                self._fire(idle)

    def _fire(self, idle):
        msg = (f"[paddle_tpu.elastic] WATCHDOG: no training progress for "
               f"{idle:.0f}s (last step {self._last_step}); likely a hung "
               f"collective or hung device")
        print(msg, file=sys.stderr, flush=True)
        try:
            faulthandler.dump_traceback(file=sys.stderr)  # live stacks
        except Exception:
            pass
        if self.on_stall is not None:
            try:
                self.on_stall(idle, self._last_step)
            except Exception:
                pass
        if self.abort:
            os._exit(43)  # distinct exit code: watchdog kill -> relaunch

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def _hb_prefix():
    """Heartbeat keys live under the run's coordination namespace
    (protolint PL101): un-namespaced ``ptpu/hb/*`` keys survive the
    end-of-run namespace reap on a long-lived coordinator, so the
    NEXT launch's rank 0 reads this run's final beats as fresh-enough
    liveness and delays dead-host detection by a full grace period."""
    from paddle_tpu.resilience import fleet
    return f"{fleet.coord_namespace()}/hb"


class HeartbeatServer:
    """Multi-host liveness over the jax.distributed KV store: every host
    publishes a timestamp; rank 0 flags hosts whose heartbeat is stale.
    Degrades to a no-op in single-process runs.

    Keys are run-namespaced (:func:`_hb_prefix`) and each host reaps
    its own key in :meth:`stop`, so a clean shutdown leaves nothing in
    the store and a SIGKILLed host's key still dies with the
    namespace reap."""

    def __init__(self, interval=30.0, stale_after=120.0, on_dead=None,
                 client=None):
        self.interval = interval
        self.stale_after = stale_after
        self.on_dead = on_dead
        self._client = client
        self._stop = threading.Event()
        self._start_time = time.time()
        self._pid = None
        if self._client is None:
            try:
                from jax._src.distributed import global_state
                self._client = global_state.client
            except Exception:
                self._client = None
        self._thread = None
        if self._client is not None:
            # publish-then-spawn: the beat loop and stop() both read
            # _pid, so it must be set before the thread starts
            import jax
            self._pid = jax.process_index()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()

    def _run(self):
        import jax
        pid = self._pid
        nproc = jax.process_count()
        consecutive_failures = 0
        while not self._stop.wait(self.interval):
            now = str(time.time())
            try:
                prefix = _hb_prefix()
                # fixed key per rank (overwritten each beat) — O(nranks)
                # store size, not O(beats)
                try:
                    self._client.key_value_set(f"{prefix}/{pid}", now,
                                               allow_overwrite=True)
                except TypeError:  # older client without the kwarg
                    self._client.key_value_delete(f"{prefix}/{pid}")
                    self._client.key_value_set(f"{prefix}/{pid}", now)
                if pid == 0:
                    dirs = self._client.key_value_dir_get(
                        f"{_hb_prefix()}/")
                    latest = {}
                    for k, v in dirs:
                        r = int(k.rsplit("/", 1)[-1])
                        latest[r] = max(latest.get(r, 0.0), float(v))
                    cutoff = time.time() - self.stale_after
                    # a rank with NO heartbeat yet is only "dead" after the
                    # startup grace period — else slow-starting hosts get
                    # flagged (and possibly restarted) on rank 0's first poll
                    grace_over = time.time() - self._start_time > \
                        self.stale_after
                    dead = [r for r in range(nproc)
                            if (latest[r] < cutoff if r in latest
                                else grace_over)]
                    if dead and self.on_dead is not None:
                        self.on_dead(dead)
                consecutive_failures = 0
            except Exception as e:
                # a silently-dead heartbeat loop would disable dead-host
                # detection with no trace; log (rate-limited) and give up
                # loudly after repeated failures so operators can see it
                consecutive_failures += 1
                if consecutive_failures <= 3 or \
                        consecutive_failures % 20 == 0:
                    print(f"[paddle_tpu.elastic] heartbeat poll failed "
                          f"({consecutive_failures}x): {type(e).__name__}: "
                          f"{e}", file=sys.stderr, flush=True)
                if consecutive_failures >= 60:
                    print("[paddle_tpu.elastic] heartbeat DISABLED after "
                          "60 consecutive failures — liveness monitoring "
                          "is NOT functioning", file=sys.stderr, flush=True)
                    return

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            # a beat in flight after the delete below would resurrect
            # the key; wait the loop out first
            self._thread.join(timeout=5)
        if self._client is not None and self._pid is not None:
            try:
                self._client.key_value_delete(f"{_hb_prefix()}/{self._pid}")
            except Exception:
                pass


class ElasticManager:
    """Reference: fleet elastic manager — here a thin supervisor combining
    the step watchdog with host heartbeats, and (optionally) a
    resilience PreemptionHandler so drains and heartbeats compose: the
    handler's drain calls :func:`notify_progress` around its final
    checkpoint write, which beats THIS manager's watchdog — a slow
    final save is progress, not a stall."""

    def __init__(self, timeout=300.0, abort_on_stall=True,
                 preemption=None):
        self.watchdog = Watchdog(timeout=timeout, abort=abort_on_stall)
        self.heartbeats = HeartbeatServer()
        self.preemption = preemption
        if preemption is not None:
            from paddle_tpu.resilience import preemption as _pre
            _pre.install(preemption)
            preemption.install_signal_handlers()

    def beat(self, step=None):
        self.watchdog.beat(step)

    def stop(self):
        self.watchdog.stop()
        self.heartbeats.stop()
        if self.preemption is not None:
            self.preemption.uninstall_signal_handlers()
            # and the process-global registration (symmetric with
            # __init__): a stopped manager's handler must not swallow
            # later request_preemption() calls — no loop polls it
            from paddle_tpu.resilience import preemption as _pre
            _pre.uninstall(self.preemption)


# ---- global progress hook ------------------------------------------------
# The launch CLI installs a manager here; Optimizer.step() calls
# notify_progress() so a watchdog started by the launcher sees heartbeats
# WITHOUT the training script knowing about it (otherwise a CLI-configured
# watchdog would fire on perfectly healthy runs).
_active_manager = None
_step_counter = [0]


def install_manager(manager):
    global _active_manager
    _active_manager = manager
    return manager


def get_manager():
    return _active_manager


def notify_progress():
    if _active_manager is not None:
        _step_counter[0] += 1
        _active_manager.beat(_step_counter[0])
    # every watchdog beat is ALSO fleet progress: the rank heartbeat
    # publisher's progress counter advances per microbatch (e.g. each
    # GradientMergeOptimizer accumulate step), so a slow k-step
    # accumulate window — where Optimizer.step never fires — cannot be
    # misclassified SUSPECT by a progress-aware FleetMonitor
    from paddle_tpu.resilience import fleet
    fleet.notify_fleet_progress()


class Command:
    """Elastic scale control (reference distributed/elastic.py:19): the
    reference stores the target world size np in etcd. Zero external
    services here — the KV is a local JSON file shared by node-local
    processes (cross-host coordination is jax.distributed's job)."""

    def __init__(self, server=None, name="default"):
        import json
        import os
        import tempfile
        self._json = json
        self.path = os.path.join(tempfile.gettempdir(),
                                 f"ptpu_elastic_{name}.json")

    def _read(self):
        import os
        if not os.path.exists(self.path):
            return {}
        try:
            with open(self.path) as fh:
                return self._json.load(fh)
        except Exception:
            return {}

    def set_np(self, np):
        state = self._read()
        state["np"] = int(np)
        with open(self.path, "w") as fh:
            self._json.dump(state, fh)

    def scale_np(self, np):
        if self._read().get("np") is not None:
            self.set_np(np)
            return True
        return False

    def clean(self):
        import os
        if os.path.exists(self.path):
            os.remove(self.path)
