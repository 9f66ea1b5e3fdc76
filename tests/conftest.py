"""Test env: 8 virtual CPU devices (multi-chip sharding tests run here).

Must set the env BEFORE jax initializes its backends (backend selection is
lazy — first jax.devices() call wins).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "backend_optimization_level" not in flags:
    # tests are compile-bound, not run-bound: XLA:CPU at -O0 halves the
    # compile time of the deep-model tests with no semantic change
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(items):
    # nightly implies slow: a `-m "not slow"` on the command line (the
    # tier-1 gate uses one) REPLACES the addopts' `-m "not nightly"`
    # (pytest keeps only the last -m), which silently pulled the whole
    # compile-heavy nightly sweep into the gate budget.  Dual-marking
    # here keeps the two selections aligned without touching every test.
    for item in items:
        if "nightly" in item.keywords:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True, scope="module")
def _global_mesh_stays_in_its_file():
    # a file that installs a global mesh (`init_mesh`, or code that calls
    # `ensure_mesh`) and leaves it keeps it to itself: under xdist the
    # next file on the same worker would otherwise trace every model
    # under it — which file that is depends on the placement
    from paddle_tpu.distributed import mesh
    found = mesh.get_mesh()
    yield
    mesh.set_mesh(found)


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as P
    P.seed(0)
    np.random.seed(0)
    yield


@pytest.fixture(autouse=True)
def _lock_order_sanitizer(request):
    # Every chaos-marked test runs under the racelint lock-order
    # tracer: the fault-injection suite doubles as a concurrency
    # stress run, and ANY lock pair observed in both orders fails the
    # gate (a real inversion — the next unlucky schedule deadlocks).
    # PADDLE_TPU_LOCK_TRACE=0 opts out (e.g. when bisecting an
    # unrelated failure).
    if "chaos" not in request.keywords \
            or os.environ.get("PADDLE_TPU_LOCK_TRACE") == "0":
        yield
        return
    from paddle_tpu.analysis.lock_tracer import LockOrderTracer
    with LockOrderTracer() as tracer:
        yield
    snap = tracer.snapshot()
    assert not snap["violations"], (
        f"lock-order inversion observed during chaos run: {snap}")


@pytest.fixture(autouse=True)
def _kv_lifecycle_sanitizer(request, tmp_path_factory):
    # Every chaos-marked test ALSO runs under protolint's KV event
    # tracer: the in-process half patches LocalKVClient (rank-per-
    # thread fleets), and PTPU_KV_TRACE_DIR makes the multiprocess
    # workers (which inherit os.environ through _child_env) append
    # their real-coordination-client streams as kill-safe JSONL the
    # parent collects here.  Any key-lifecycle violation — a get after
    # this process deleted the key, or a double-consume on an
    # exactly-once lane — fails the gate: that is the dynamic
    # double-delivery/stale-read evidence PL101/PL102 police
    # statically.  PADDLE_TPU_KV_TRACE=0 opts out.
    if "chaos" not in request.keywords \
            or os.environ.get("PADDLE_TPU_KV_TRACE") == "0":
        yield
        return
    from paddle_tpu.analysis import kv_tracer
    trace_dir = str(tmp_path_factory.mktemp("kvtrace"))
    prev = os.environ.get("PTPU_KV_TRACE_DIR")
    os.environ["PTPU_KV_TRACE_DIR"] = trace_dir
    try:
        with kv_tracer.KVEventTracer() as tracer:
            yield
        events = tracer.events + kv_tracer.read_trace_dir(trace_dir)
        violations = kv_tracer.lifecycle_violations(events)
        assert not violations, (
            f"KV lifecycle violation observed during chaos run "
            f"({len(events)} events): {violations}")
    finally:
        if prev is None:
            os.environ.pop("PTPU_KV_TRACE_DIR", None)
        else:
            os.environ["PTPU_KV_TRACE_DIR"] = prev
