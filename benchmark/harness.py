"""What every runner needs from the harness: files found by name, host
spans, the compile counter, device facts and the profiler capture."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` — found by the name in the manifest
    or the cell's file, never listed in code."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def with_rehearsal(obj: dict, rehearse: bool) -> dict:
    """The file as it is run; under ``--rehearse-cpu`` its ``rehearsal``
    block (tiny sizes) is laid over it."""
    out = {k: v for k, v in obj.items() if k != "rehearsal"}
    if rehearse:
        out.update(obj.get("rehearsal", {}))
    return out


class Spans:
    """Host spans of the benchmark's own loop, kept in memory.  Under a
    profiler capture each is also a ``TraceAnnotation``, so the device
    trace carries them on its own clock."""

    def __init__(self):
        self.durations = {}
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.durations.setdefault(name, []).append(
                time.perf_counter() - t0)
            if ann is not None:
                ann.__exit__(None, None, None)


class XlaCompileCounter:
    """Counts XLA compile requests and persistent-cache hits (jax's own
    monitoring events); the difference is what was compiled anew.  Copied
    from ``chip_smoke.py``."""

    def __init__(self):
        import jax
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def new_compiles(self):
        return self.requests - self.hits


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(chips, len(devs))}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Capture:
    """A profiler capture into a fixed directory inside the checkout."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = os.path.join(ROOT, ".cache", "benchmark_trace")
        self.t0 = self.t1 = None

    def start(self):
        self.t0 = time.perf_counter()
        if self.on:
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # device ops and the spans only:
            opts.host_tracer_level = 1       # a python trace is tens of MB/s
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        """Ends the capture; the caller has already waited for the device."""
        self.t1 = time.perf_counter()
        if self.on:
            import jax
            jax.profiler.stop_trace()

    def xplane_path(self):
        for base, _dirs, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(base, f)
        return None

    def discard(self):
        shutil.rmtree(self.dir, ignore_errors=True)
