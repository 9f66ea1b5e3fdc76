"""paddle_tpu.observability — spans, metrics registry, recompile
attribution, exporters, and the profiler satellites that ride along.

Everything here is CPU-only; the recompile-attribution tests compile a
tiny to_static signature pair (a handful of scalar-ish programs), never
a model.  The process-wide singletons (span recorder, recompile log,
metrics registry) are shared with the rest of the suite, so tests that
read them assert on DELTAS or use private instances — `registry().reset()`
is never called (it would drop the builtin sources and every live
engine's snapshot source).
"""
import json
import os
import time
import types

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.observability import export as obs_export
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.observability.recompile import diff_keys
from paddle_tpu.observability.spans import SpanRecord, SpanRecorder

pytestmark = pytest.mark.obs


# ===================================================================== spans
class TestSpans:
    @pytest.mark.smoke
    def test_nesting_depth_and_order(self):
        rec = obs.recorder()
        before = rec.total_recorded
        with obs.span("outer"):
            with obs.span("inner"):
                pass
        spans = rec.spans()[-2:]
        assert rec.total_recorded == before + 2
        # spans close inner-first
        by_name = {s.name: s for s in spans}
        assert by_name["inner"].depth == 1
        assert by_name["outer"].depth == 0
        # inner is contained in outer's window
        assert by_name["inner"].start_ns >= by_name["outer"].start_ns
        assert (by_name["inner"].start_ns + by_name["inner"].dur_ns
                <= by_name["outer"].start_ns + by_name["outer"].dur_ns)

    def test_attrs_recorded(self):
        with obs.span("attrs-span", step=3, phase="decode"):
            pass
        s = obs.recorder().spans()[-1]
        assert s.name == "attrs-span"
        assert s.attrs == {"step": 3, "phase": "decode"}

    def test_ring_buffer_bounds_and_aggregates(self):
        rec = SpanRecorder(cap=8)
        for i in range(20):
            rec.record(SpanRecord("tick", i, 1_000_000, 0, 0, None))
        assert len(rec.spans()) == 8                 # bounded
        assert rec.total_recorded == 20
        assert rec.dropped == 12
        # aggregates survive ring eviction: all 20 counted
        agg = rec.aggregates()
        assert agg["tick"]["count"] == 20
        assert agg["tick"]["total_ms"] == pytest.approx(20.0)
        # oldest-first snapshot, newest retained
        assert [s.start_ns for s in rec.spans()] == list(range(12, 20))

    def test_set_capacity_preserves_recent(self):
        rec = SpanRecorder(cap=16)
        for i in range(10):
            rec.record(SpanRecord("s", i, 1, 0, 0, None))
        rec.set_capacity(4)
        assert rec.capacity == 4
        assert [s.start_ns for s in rec.spans()] == [6, 7, 8, 9]

    def test_disabled_records_nothing(self):
        rec = obs.recorder()
        prev = obs.set_enabled(False)
        try:
            before = rec.total_recorded
            with obs.span("invisible"):
                pass
            assert rec.total_recorded == before
        finally:
            obs.set_enabled(prev)

    def test_exception_still_closes_span(self):
        rec = obs.recorder()
        before = rec.total_recorded
        with pytest.raises(RuntimeError):
            with obs.span("raises"):
                raise RuntimeError("boom")
        assert rec.total_recorded == before + 1
        assert rec.spans()[-1].name == "raises"

    def test_clear(self):
        rec = SpanRecorder(cap=4)
        rec.record(SpanRecord("a", 0, 1, 0, 0, None))
        rec.clear()
        assert rec.spans() == [] and rec.total_recorded == 0
        assert rec.aggregates() == {}


# ================================================================== metrics
class TestMetricsRegistry:
    def test_get_or_create_is_idempotent(self):
        reg = MetricsRegistry()
        c1 = reg.counter("hits", help="h")
        c2 = reg.counter("hits")
        assert c1 is c2
        c1.inc(); c1.inc(2)
        assert c2.value == 3

    def test_labels_key_distinct_instruments(self):
        reg = MetricsRegistry()
        a = reg.counter("reqs", labels={"engine": "a"})
        b = reg.counter("reqs", labels={"engine": "b"})
        assert a is not b
        a.inc(5)
        assert b.value == 0

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")
        # same name, different labels, different kind: still a conflict
        with pytest.raises(ValueError, match="already registered"):
            reg.histogram("x", labels={"l": "1"})

    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("mono")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_up_down(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        g.set(4); g.inc(); g.dec(2)
        assert g.value == 3.0

    def test_histogram_summary_contract(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", cap=4)
        assert h.summary() == {"count": 0, "mean": None, "p50": None,
                               "p99": None}
        for v in (0.010, 0.020, 0.030, 0.040, 0.050):
            h.observe(v)
        s = h.summary()                 # seconds -> ms by default
        assert s["count"] == 5          # exact count survives eviction
        assert s["p50"] == pytest.approx(40.0)  # reservoir kept last 4
        assert h.sum == pytest.approx(0.150)

    def test_snapshot_and_report(self):
        reg = MetricsRegistry()
        reg.counter("c").inc(2)
        reg.gauge("g", labels={"k": "v"}).set(1.5)
        snap = reg.snapshot()
        assert snap == {"c": 2, "g{k=v}": 1.5}
        reg.register_source("src", lambda: {"ok": 1})
        reg.register_source("bad", lambda: 1 / 0)
        rep = reg.report()
        assert rep["src"] == {"ok": 1}
        assert "ZeroDivisionError" in rep["bad"]["error"]
        assert rep["observability"]["metrics"]["c"] == 2

    def test_register_source_requires_callable(self):
        reg = MetricsRegistry()
        with pytest.raises(TypeError):
            reg.register_source("nope", 42)

    def test_drop_labeled_releases_an_owner(self):
        reg = MetricsRegistry()
        reg.counter("c", labels={"engine": "dead"}).inc()
        reg.histogram("h", labels={"engine": "dead", "k": "v"})
        reg.counter("c", labels={"engine": "alive"}).inc(2)
        assert reg.drop_labeled({"engine": "dead"}) == 2
        snap = reg.snapshot()
        assert snap == {"c{engine=alive}": 2}
        # the name's kind survives while other owners still use it,
        # and frees up once the last one is gone
        assert reg.drop_labeled({"engine": "alive"}) == 1
        reg.gauge("c")                      # no stale kind conflict
        with pytest.raises(ValueError):
            reg.drop_labeled({})

    def test_unregister_source_expected_guard(self):
        reg = MetricsRegistry()
        def first():
            return {"v": 1}
        def second():
            return {"v": 2}
        reg.register_source("rolling", first)
        reg.register_source("rolling", second)      # successor took over
        reg.unregister_source("rolling", expected=first)   # stale owner
        assert reg.report()["rolling"] == {"v": 2}
        reg.unregister_source("rolling", expected=second)
        assert "rolling" not in reg.report()

    def test_reset_keeps_builtin_sources(self):
        # builtin sources register once (at package import for the
        # global registry); reset() must not lose them forever
        reg = MetricsRegistry()
        reg.register_source("builtin-src", lambda: {"b": 1}, builtin=True)
        reg.register_source("ephemeral", lambda: {})
        reg.counter("c").inc()
        reg.reset()
        rep = reg.report()
        assert rep["builtin-src"] == {"b": 1}
        assert "ephemeral" not in rep
        assert rep["observability"]["metrics"] == {}
        # the package's span/recompile sources ARE builtins, so a
        # global reset() cannot silently empty metrics_report()
        assert {"spans", "recompile"} <= set(obs.registry()._builtins)


# ============================================================= profiler shim
class TestProfilerShim:
    def test_metrics_report_routes_through_registry(self):
        profiler.register_metrics_source("obs-shim-test",
                                         lambda: {"answer": 42})
        try:
            rep = profiler.metrics_report()
            assert rep["obs-shim-test"] == {"answer": 42}
            # builtin sources ride along in the SAME report
            assert "spans" in rep and "recompile" in rep
            assert "observability" in rep
        finally:
            profiler.unregister_metrics_source("obs-shim-test")
        assert "obs-shim-test" not in profiler.metrics_report()


# ================================================================ prometheus
class TestPrometheusExposition:
    def test_golden_text(self):
        reg = MetricsRegistry()
        reg.counter("requests_total", help="requests served").inc(3)
        reg.gauge("queue_depth").set(2)
        h = reg.histogram("latency_seconds", labels={"engine": "e0"})
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        assert obs_export.prometheus_text(reg) == (
            '# TYPE latency_seconds summary\n'
            'latency_seconds{engine="e0",quantile="0.5"} 3\n'
            'latency_seconds{engine="e0",quantile="0.9"} 4\n'
            'latency_seconds{engine="e0",quantile="0.99"} 4\n'
            'latency_seconds_sum{engine="e0"} 10\n'
            'latency_seconds_count{engine="e0"} 4\n'
            '# TYPE queue_depth gauge\n'
            'queue_depth 2\n'
            '# HELP requests_total requests served\n'
            '# TYPE requests_total counter\n'
            'requests_total 3\n')

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("c", labels={"p": 'a"b\\c\nd'}).inc()
        text = obs_export.prometheus_text(reg)
        assert r'p="a\"b\\c\nd"' in text

    def test_empty_histogram_renders_nan(self):
        reg = MetricsRegistry()
        reg.histogram("empty_seconds")
        text = obs_export.prometheus_text(reg)
        assert 'empty_seconds{quantile="0.5"} NaN' in text
        assert "empty_seconds_count 0" in text


# ================================================================ recompile
def _clear_log():
    obs.recompile_log().clear()


class TestRecompileAttribution:
    def test_shape_change_names_the_perturbed_arg(self):
        _clear_log()

        @P.jit.to_static
        def f(x, y):
            return x * 2.0 + y

        a = P.to_tensor(np.ones((2, 8), np.float32))
        b = P.to_tensor(np.ones((2, 8), np.float32))
        f(a, b)                                     # first compile
        f(a, b)                                     # cache hit: no event
        events = obs.recompile_log().events()
        assert len(events) == 1
        assert events[0].cause == "first compile of this function"
        assert events[0].changes == []
        assert events[0].trace_ms is not None
        assert events[0].compile_ms is not None

        wide = P.to_tensor(np.ones((2, 16), np.float32))
        f(wide, P.to_tensor(np.ones((2, 16), np.float32)))  # forced retrace
        ev = obs.recompile_log().events()[-1]
        assert ev.kind == "jit"
        changed = {c["arg"]: c for c in ev.changes}
        assert "x" in changed and changed["x"]["kind"] == "shape"
        assert changed["x"]["before"] == [2, 8]
        assert changed["x"]["after"] == [2, 16]
        assert "shape change" in ev.cause
        assert ev.cache_size == 2

    def test_single_arg_perturbation_names_only_that_arg(self):
        _clear_log()

        @P.jit.to_static
        def g(x, y):
            return x.sum() + y.sum()

        x8 = P.to_tensor(np.ones((8,), np.float32))
        y8 = P.to_tensor(np.ones((8,), np.float32))
        g(x8, y8)
        g(x8, P.to_tensor(np.ones((12,), np.float32)))   # only y changed
        ev = obs.recompile_log().events()[-1]
        assert ev.changed_args() == ["y"]
        assert ev.changes[0]["kind"] == "shape"

    def test_static_leaf_change_names_the_leaf(self):
        _clear_log()

        @P.jit.to_static
        def h(x, scale):
            return x * scale

        x = P.to_tensor(np.ones((4,), np.float32))
        h(x, 2.0)
        h(x, 3.0)                                   # static-leaf retrace
        ev = obs.recompile_log().events()[-1]
        assert ev.changed_args() == ["scale"]
        c = ev.changes[0]
        assert c["kind"] == "static"
        assert c["before"] == "2.0" and c["after"] == "3.0"

    def test_dtype_change_names_the_arg(self):
        _clear_log()

        @P.jit.to_static
        def k(x):
            return x + 1

        k(P.to_tensor(np.ones((4,), np.float32)))
        k(P.to_tensor(np.ones((4,), np.int32)))
        ev = obs.recompile_log().events()[-1]
        assert ev.changed_args() == ["x"]
        assert ev.changes[0]["kind"] == "dtype"

    def test_visible_in_metrics_report(self):
        _clear_log()

        @P.jit.to_static
        def m(x):
            return x * x

        m(P.to_tensor(np.ones((3,), np.float32)))
        m(P.to_tensor(np.ones((5,), np.float32)))
        rep = profiler.metrics_report()
        assert rep["recompile"]["count"] == 2
        recent = rep["recompile"]["recent"]
        assert recent[-1]["changes"][0]["arg"] == "x"
        assert rep["observability"]["metrics"]["obs_recompile_total"] >= 2

    def test_diff_keys_unit(self):
        # pure-unit coverage of the traced<->static and state-registry
        # branches the jit tests above don't exercise
        sentinel = object()
        tree = "TREE"                       # treedefs compare by identity
        old = (tree, (((2, 8), "float32"),), (sentinel, 5), 0)
        new_traced = (tree, (((2, 8), "float32"), ((1,), "int32")),
                      (sentinel, sentinel), 0)
        ch = diff_keys(new_traced, old, ["x", "flag"], sentinel)
        assert ch == [{"arg": "flag", "kind": "traced",
                       "before": "static", "after": "array"}]
        new_state = (tree, (((2, 8), "float32"),), (sentinel, 5), 3)
        ch = diff_keys(new_state, old, ["x", "flag"], sentinel)
        assert ch == [{"arg": "<state-registry>", "kind": "state",
                       "before": 0, "after": 3}]

    def test_log_is_bounded(self):
        from paddle_tpu.observability.recompile import RecompileLog
        log = RecompileLog(cap=4)
        for i in range(10):
            log.record(f"f{i}", "jit", "test", [])
        assert len(log.events()) == 4
        assert log.count == 10                  # seq keeps counting
        assert log.snapshot(last=2)["count"] == 10
        assert len(log.snapshot(last=2)["recent"]) == 2

    def test_aot_event_attrs(self):
        _clear_log()
        ev = obs.note_aot_compile("decode/b128", compile_ms=12.5,
                                  cache_size=3, bound=7, engine="e-test")
        assert ev.kind == "serving-aot"
        assert ev.attrs == {"compile_bound": 7, "engine": "e-test"}
        assert "decode/b128" in ev.format()


# ================================================================ serving
class TestServingUnification:
    def test_note_compile_bumps_shared_registry(self):
        from paddle_tpu.serving.metrics import EngineMetrics
        m = EngineMetrics(name="pytest-unify")
        c = obs.registry().counter("serving_compile_total",
                                   labels={"engine": "pytest-unify"})
        before = c.value
        m.note_compile()
        assert c.value == before + 1
        assert m.compile_count == 1             # snapshot contract intact

    def test_histograms_are_registry_backed(self):
        from paddle_tpu.serving.metrics import EngineMetrics, Histogram
        from paddle_tpu.observability.metrics import Histogram as ObsHist
        assert Histogram is ObsHist             # one class, not a copy
        m = EngineMetrics(name="pytest-unify2")
        m.ttft.observe(0.5)
        text = obs_export.prometheus_text()
        assert ('serving_ttft_seconds{engine="pytest-unify2",'
                'quantile="0.5"} 0.5') in text
        # and the engine-facing summary sees the same observation
        assert m.ttft.summary()["count"] == 1

    def test_unnamed_instances_never_share(self):
        from paddle_tpu.serving.metrics import EngineMetrics
        a, b = EngineMetrics(), EngineMetrics()
        a.ttft.observe(0.1)
        assert b.ttft.count == 0

    def test_release_drops_registry_instruments(self):
        from paddle_tpu.serving.metrics import EngineMetrics
        m = EngineMetrics(name="pytest-release")
        m.note_compile()
        assert 'engine="pytest-release"' in obs_export.prometheus_text()
        m.release()
        assert 'engine="pytest-release"' not in obs_export.prometheus_text()

    def test_shared_name_release_refcounts(self):
        # rolling restart: two engines share a stable metrics name —
        # the first shutdown must NOT delete the survivor's instruments
        from paddle_tpu.serving.metrics import EngineMetrics
        a = EngineMetrics(name="pytest-shared")
        b = EngineMetrics(name="pytest-shared")
        assert a.ttft is b.ttft                  # shared registry key
        a.release()
        b.ttft.observe(0.1)
        text = obs_export.prometheus_text()
        assert 'serving_ttft_seconds{engine="pytest-shared"' in text
        b.release()
        assert 'engine="pytest-shared"' not in obs_export.prometheus_text()

    def test_release_is_idempotent(self):
        from paddle_tpu.serving.metrics import EngineMetrics
        a = EngineMetrics(name="pytest-idem")
        b = EngineMetrics(name="pytest-idem")
        a.release()
        a.release()                              # double release = one claim
        assert 'engine="pytest-idem"' in obs_export.prometheus_text()
        b.release()
        assert 'engine="pytest-idem"' not in obs_export.prometheus_text()

    def test_collected_instance_releases_its_claim(self):
        import gc
        from paddle_tpu.serving.metrics import EngineMetrics
        a = EngineMetrics(name="pytest-gcref")
        b = EngineMetrics(name="pytest-gcref")
        del a
        gc.collect()
        assert 'engine="pytest-gcref"' in obs_export.prometheus_text()
        del b
        gc.collect()
        assert 'engine="pytest-gcref"' not in obs_export.prometheus_text()


# ================================================================ exporters
class TestExporters:
    def test_jsonl_round_trip(self, tmp_path):
        spans = [SpanRecord("a", 10, 20, 0, 1, {"k": "v"}),
                 SpanRecord("b", 15, 5, 1, 1, None)]
        _clear_log()
        obs.recompile_log().record("fn", "jit", "test", [
            {"arg": "x", "kind": "shape", "before": [2], "after": [4]}])
        path = str(tmp_path / "obs.jsonl")
        obs_export.dump_jsonl(path, spans=spans,
                              recompiles=obs.recompile_log().events())
        doc = obs_export.load_jsonl(path)
        assert doc["meta"]["version"] == 1
        assert "UTC" in doc["meta"]["capture_utc"]
        assert [s["name"] for s in doc["spans"]] == ["a", "b"]
        assert doc["spans"][0]["attrs"] == {"k": "v"}
        assert doc["recompiles"][0]["changes"][0]["arg"] == "x"
        # the process-wide registry rode along as metric rows
        assert any(m["name"] == "obs_recompile_total"
                   for m in doc["metrics"])

    def test_chrome_trace_shape(self):
        spans = [SpanRecord("step", 2_000, 1_000, 0, 7, {"i": 1})]
        doc = obs_export.chrome_trace(spans)
        assert doc["displayTimeUnit"] == "ms"
        ev = doc["traceEvents"][0]
        assert ev == {"name": "step", "ph": "X", "pid": 0, "tid": 0,
                      "ts": 2.0, "dur": 1.0, "args": {"i": 1}}

    def test_write_chrome_trace(self, tmp_path):
        path = str(tmp_path / "trace.json")
        obs_export.write_chrome_trace(
            path, [SpanRecord("s", 0, 1, 0, 0, None)])
        with open(path) as fh:
            assert json.load(fh)["traceEvents"][0]["name"] == "s"


# ============================================================== obs_report
class TestObsReportCLI:
    def test_renders_dump(self, tmp_path, capsys):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "obs_report", os.path.join(os.path.dirname(__file__),
                                       os.pardir, "tools", "obs_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _clear_log()
        obs.recompile_log().record("train_step", "jit", "shape change in x", [
            {"arg": "x", "kind": "shape", "before": [2, 8],
             "after": [2, 16]}])
        path = str(tmp_path / "obs.jsonl")
        obs_export.dump_jsonl(
            path, spans=[SpanRecord("train", 0, 5_000_000, 0, 0, None)])
        assert mod.main([path]) == 0
        out = capsys.readouterr().out
        assert "shape change in x" in out
        assert "x: shape [2, 8] -> [2, 16]" in out
        assert "train" in out
        assert "obs_recompile_total" in out


# ================================================================= overhead
class TestOverhead:
    def test_per_span_cost_bounded(self):
        # the production contract is "cheap enough to leave on": two
        # clock reads + a deque append.  100 us/span is ~30x the
        # observed cost — a regression tripwire, not a benchmark.
        n = 5_000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("overhead-probe"):
                pass
        per_span_us = (time.perf_counter() - t0) / n * 1e6
        assert per_span_us < 100.0, f"{per_span_us:.1f} us/span"

    def test_disabled_span_is_near_free(self):
        prev = obs.set_enabled(False)
        try:
            n = 20_000
            t0 = time.perf_counter()
            for _ in range(n):
                with obs.span("off-probe"):
                    pass
            per_span_us = (time.perf_counter() - t0) / n * 1e6
        finally:
            obs.set_enabled(prev)
        assert per_span_us < 25.0, f"{per_span_us:.1f} us/span disabled"

    def test_jit_step_overhead_pct(self):
        # the bench.py --worker-obs lane asserts < 2% on the full gpt
        # hybrid step; this is the same measurement on a smaller step
        # with a looser bound so it stays robust under CI noise
        import statistics

        @P.jit.to_static
        def step(x):
            return (x @ x).sum()

        x = P.to_tensor(np.ones((192, 192), np.float32))
        step(x)                                     # compile once

        def loop(iters=30):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = step(x)
            out._value.block_until_ready()
            return time.perf_counter() - t0

        loop()                                      # warm
        overhead = None
        for _ in range(4):
            obs.set_enabled(False)
            off = statistics.median(loop() for _ in range(3))
            obs.set_enabled(True)
            on = statistics.median(loop() for _ in range(3))
            pct = max(0.0, (on - off) / off * 100.0)
            overhead = pct if overhead is None else min(overhead, pct)
            if overhead < 2.0:
                break
        obs.set_enabled(True)
        assert overhead < 15.0, f"span overhead {overhead:.2f}%"


# ====================================================== profiler satellites
class TestChromeTracingManifest:
    def test_manifest_written_and_returned(self, tmp_path):
        trace_dir = str(tmp_path / "trace")
        handler = profiler.export_chrome_tracing(trace_dir,
                                                 worker_name="w0")
        assert handler.last_manifest_path is None
        prof = types.SimpleNamespace(step_num=7, _window_start_step=3)
        path = handler(prof)
        assert path == handler.last_manifest_path
        assert os.path.basename(path) == "ptpu_trace_manifest.json"
        with open(path) as fh:
            manifest = json.load(fh)
        assert manifest["trace_dir"] == os.path.abspath(trace_dir)
        assert manifest["worker_name"] == "w0"
        assert manifest["step_window"] == [3, 7]
        assert "UTC" in manifest["capture_utc"]

    def test_manifest_without_window_attrs(self, tmp_path):
        # a handler invoked by code that never opened a window (or a
        # foreign profiler object) still writes a valid manifest
        handler = profiler.export_chrome_tracing(str(tmp_path / "t"))
        path = handler(types.SimpleNamespace())
        with open(path) as fh:
            assert json.load(fh)["step_window"] == [0, 0]

    def test_manifest_keeps_window_history(self, tmp_path):
        # a repeating scheduler fires the handler once per recorded
        # window; every window's step range must survive in "windows"
        # while the top-level keys mirror the most recent one
        handler = profiler.export_chrome_tracing(str(tmp_path / "t"))
        handler(types.SimpleNamespace(step_num=5, _window_start_step=2))
        path = handler(
            types.SimpleNamespace(step_num=15, _window_start_step=12))
        with open(path) as fh:
            manifest = json.load(fh)
        assert manifest["step_window"] == [12, 15]
        assert [w["step_window"] for w in manifest["windows"]] == \
            [[2, 5], [12, 15]]


class TestSchedulerContract:
    def test_repeat0_skip_first_no_reskip_at_wraparound(self):
        S = profiler.ProfilerState
        sched = profiler.make_scheduler(closed=1, ready=1, record=2,
                                        repeat=0, skip_first=3)
        # skip_first consumed once, up front
        assert [sched(s) for s in range(3)] == [S.CLOSED] * 3
        cycle = [S.CLOSED, S.READY, S.RECORD, S.RECORD_AND_RETURN]
        # then a plain total-step modulus, forever — NO re-skip after
        # wraparound (the pinned contract)
        assert [sched(3 + s) for s in range(8)] == cycle + cycle
        assert sched(3 + 40 * 4 + 1) == S.READY

    def test_repeat_n_closes_after_n_cycles(self):
        S = profiler.ProfilerState
        sched = profiler.make_scheduler(closed=0, ready=1, record=1,
                                        repeat=2, skip_first=1)
        assert sched(0) == S.CLOSED                  # skipped
        assert [sched(s) for s in range(1, 5)] == [
            S.READY, S.RECORD_AND_RETURN, S.READY, S.RECORD_AND_RETURN]
        # after repeat cycles: closed forever
        assert all(sched(s) == S.CLOSED for s in range(5, 12))

    def test_profiler_empty_tuple_window_never_records(self):
        # (n, n) / inverted windows have always meant "never record" —
        # they must not trip make_scheduler's record >= 1 validation
        S = profiler.ProfilerState
        for window in ((3, 3), (5, 2)):
            prof = profiler.Profiler(timer_only=True, scheduler=window)
            assert all(prof.scheduler(s) == S.CLOSED for s in range(10))

    def test_invalid_phases_raise(self):
        with pytest.raises(ValueError, match="record"):
            profiler.make_scheduler(closed=1, ready=1, record=0)
        with pytest.raises(ValueError, match="negative"):
            profiler.make_scheduler(closed=-1, ready=0, record=1)
        with pytest.raises(ValueError, match="negative"):
            profiler.make_scheduler(closed=0, ready=0, record=1,
                                    skip_first=-2)


# ======================================================= telemetry isolation
class TestTelemetryIsolation:
    def test_poisoned_telemetry_never_fail_caches_a_transform(
            self, monkeypatch):
        # a telemetry error (e.g. the counter's name registered as a
        # different kind, raising on lookup) must not discard a
        # successful AST transform or fail-cache the function — that
        # would silently run tensor-dependent control flow unconverted
        # under to_static
        from paddle_tpu.jit import dy2static
        from paddle_tpu.observability import metrics as obs_metrics

        def poisoned_registry():
            raise ValueError("metric kind conflict")

        monkeypatch.setattr(obs_metrics, "registry", poisoned_registry)
        monkeypatch.setattr(
            obs, "span",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))

        def f(x):
            if x.sum() > 0:
                return x + 1
            return x - 1

        out = dy2static.transform_func(f)
        assert f not in dy2static._fail_cache
        assert getattr(f, "_ptd2s_variant", None) is not None
        assert out is f._ptd2s_variant


# ============================================== spans on a capture's clock
def _capture_events(tmp_path, body):
    """Host events {name: [stats dict, ...]} of a BARE jax capture round
    ``body`` — nothing of ``paddle_tpu.profiler`` is called."""
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(
                    {k: v for k, v in ev.stats})
    return events


class TestSpansReachAnyCapture:
    def test_bare_jax_capture_holds_the_span_and_its_attrs(self, tmp_path):
        assert not profiler.in_profiler_mode()

        def body():
            with obs.span("capture.probe", request="req-9", bucket=128,
                          tokens=77) as s:
                s.set(hit=True)

        events = _capture_events(tmp_path, body)
        assert len(events["capture.probe"]) == 1
        stats = events["capture.probe"][0]
        assert str(stats["request"]) == "req-9"
        assert int(stats["bucket"]) == 128 and int(stats["tokens"]) == 77
        assert obs.recorder().spans()[-1].attrs == {
            "request": "req-9", "bucket": 128, "tokens": 77, "hit": True}

    def test_attributes_set_at_exit_reach_the_capture(self, tmp_path):
        def body():
            with obs.span("capture.exit-probe", bucket=8) as s:
                s.set(admitted=2)
                s.set(tokens=5, admitted=3)      # the last set wins
            with obs.span("capture.after-exit") as s:
                pass
            s.set(late=1)                        # closed: the record's

        events = _capture_events(tmp_path, body)
        (stats,) = events["capture.exit-probe"]
        assert int(stats["bucket"]) == 8 and int(stats["tokens"]) == 5
        assert int(stats["admitted"]) == 3
        (after,) = events["capture.after-exit"]
        assert "late" not in after

    def test_an_engines_exit_attributes_reach_the_capture(self, tmp_path):
        """serving.step's admitted / tokens, serving.decode's experts_hit
        and serving.deliver's tokens, all known only at exit."""
        from paddle_tpu import serving
        engine = _tiny_block_engine()
        try:
            events = _capture_events(tmp_path, lambda: engine.generate(
                [list(range(1, 15))],
                serving.SamplingParams(max_new_tokens=6, temperature=0.0)))
        finally:
            engine.shutdown()
        steps = events["serving.step"]
        assert sum(int(s["admitted"]) for s in steps) == 1
        assert sum(int(s["tokens"]) for s in steps) == 6
        assert sum(int(d["tokens"]) for d in events["serving.deliver"]) == 6
        assert all(int(d["experts_hit"]) > 0
                   for d in events["serving.decode"])

    def test_disabled_spans_write_no_event(self, tmp_path):
        def body():
            prev = obs.set_enabled(False)
            try:
                with obs.span("capture.off-probe", k=1):
                    pass
            finally:
                obs.set_enabled(prev)
            with obs.span("capture.on-probe"):
                pass

        events = _capture_events(tmp_path, body)
        assert "capture.off-probe" not in events
        assert len(events["capture.on-probe"]) == 1

    def test_discard_leaves_no_record_and_restores_depth(self):
        rec = obs.recorder()
        before = rec.total_recorded
        with obs.span("discard.outer"):
            with obs.span("discard.probe") as s:
                s.discard()
            with obs.span("discard.kept"):
                pass
        names = [r.name for r in rec.spans()[-2:]]
        assert rec.total_recorded == before + 2
        assert names == ["discard.kept", "discard.outer"]
        assert rec.spans()[-2].depth == 1


def _inside(child, parent):
    return (child.depth > parent.depth
            and child.thread_id == parent.thread_id
            and parent.start_ns <= child.start_ns
            and child.start_ns + child.dur_ns
            <= parent.start_ns + parent.dur_ns)


def _tiny_engine():
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    P.seed(0)
    mcfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                     num_heads=2, max_seq_len=32, dropout=0.0,
                     attention_dropout=0.0)
    return serving.LLMEngine(
        GPTForCausalLM(mcfg),
        serving.EngineConfig(max_num_seqs=2, page_size=4, max_model_len=16,
                             prefill_buckets=(8,)))


@pytest.fixture(scope="module")
def engine_step_records():
    """One tiny CPU engine serving three prompts through two slots: the
    ring buffer's records of that run and the engine's own counts."""
    from paddle_tpu import serving
    engine = _tiny_engine()
    rec = obs.recorder()
    prev_cap = rec.capacity
    rec.set_capacity(1 << 16)
    before = rec.total_recorded
    try:
        ctx = obs.TraceContext.new("ambient")
        with obs.use_context(ctx):     # as under a fleet server's verb
            results = engine.generate(
                [[1, 2, 3], [4, 5, 6, 7], [8, 9]],
                serving.SamplingParams(max_new_tokens=4, temperature=0.0))
        records = rec.spans()[-(rec.total_recorded - before):]
        counts = (engine.metrics.prefill_steps, engine.metrics.decode_steps)
        delivered = sum(len(r.output_token_ids) for r in results)
    finally:
        rec.set_capacity(prev_cap)
        engine.shutdown()
    return records, counts, delivered, ctx


class TestEngineStepSpans:
    @pytest.mark.parametrize("name", ["serving.prefill", "serving.decode",
                                      "serving.sample", "serving.admit"])
    def test_every_child_lies_inside_a_step(self, engine_step_records, name):
        records = engine_step_records[0]
        steps = [r for r in records if r.name == "serving.step"]
        children = [r for r in records if r.name == name]
        assert steps and children
        for c in children:
            assert sum(_inside(c, s) for s in steps) == 1, c

    def test_counts_equal_the_engines_own(self, engine_step_records):
        records, (prefills, decodes), delivered, _ = engine_step_records
        by = {}
        for r in records:
            by.setdefault(r.name, []).append(r)
        assert len(by["serving.prefill"]) == prefills == 3
        assert len(by["serving.decode"]) == decodes
        # the one blocking fetch of every prefill and every decode step
        assert len(by["serving.sample"]) == prefills + decodes
        assert {r.attrs["width"] for r in by["serving.sample"]} == {1, 2}
        steps = by["serving.step"]
        assert sum(s.attrs["admitted"] for s in steps) == prefills
        assert sum(s.attrs["tokens"] for s in steps) == delivered == 12
        assert all(d.attrs["live"] in (1, 2) for d in by["serving.decode"])
        assert steps[0].attrs["waiting"] == 3
        assert steps[0].attrs["running"] == 0

    def test_sample_is_the_child_of_prefill_and_decode(
            self, engine_step_records):
        records = engine_step_records[0]
        parents = [r for r in records
                   if r.name in ("serving.prefill", "serving.decode")]
        for s in (r for r in records if r.name == "serving.sample"):
            holders = [p for p in parents if _inside(s, p)]
            assert len(holders) == 1 and s.depth == holders[0].depth + 1

    @pytest.mark.parametrize("knobs,path", [
        ([dict(temperature=0.0)] * 2, "greedy"),
        # the benchmark cells' mix: half greedy, half 0.8 / top-p 0.95
        ([dict(temperature=0.0), dict(temperature=0.8, top_p=0.95)],
         "top_p"),
        ([dict(temperature=0.8), dict(temperature=0.0, top_k=5)], "draw"),
        ([dict(temperature=0.0, top_p=0.5), dict(temperature=0.8, top_k=5)],
         "top_k"),
        ([dict(temperature=0.0), dict(temperature=0.8, top_k=5, top_p=0.9)],
         "top_k+top_p"),
    ])
    def test_sample_span_and_counter_say_what_the_batch_asked(self, knobs,
                                                              path):
        """`path` names the searches the step's sampler program runs: a
        greedy row's knobs ask for none, and a greedy request's prefill
        sample (width 1) is `greedy` whatever its neighbours ask."""
        from paddle_tpu import serving
        engine = _tiny_engine()
        rec = obs.recorder()
        before = rec.total_recorded
        try:
            engine.generate([[1, 2, 3], [4, 5, 6, 7]], [
                serving.SamplingParams(max_new_tokens=3, seed=i, **kw)
                for i, kw in enumerate(knobs)])
            samples = [r for r in rec.spans()[-(rec.total_recorded - before):]
                       if r.name == "serving.sample"]
            counted = engine.metrics.snapshot()["sampler_paths"]
        finally:
            engine.shutdown()
        decodes = [r.attrs["path"] for r in samples if r.attrs["width"] == 2]
        assert decodes == [path] * 2
        prefills = [r.attrs["path"] for r in samples
                    if r.attrs["width"] == 1]
        assert len(prefills) == 2
        assert prefills.count("greedy") == sum(
            kw["temperature"] == 0.0 for kw in knobs)
        assert sum(counted.values()) == len(samples) == 4
        assert counted[path] >= 2

    def test_step_never_parents_a_requests_trace(self, engine_step_records):
        # serving.step belongs to no request: untraced requests under an
        # ambient context keep it as the thread's parent, but a request's
        # own trace (ctx=req.trace) never hangs below a step
        from paddle_tpu import serving
        engine = _tiny_engine()
        rec = obs.recorder()
        before = rec.total_recorded
        try:
            birth = obs.TraceContext("req-trace", "router.1")
            with obs.use_context(birth):
                rid = engine.add_request(
                    [1, 2, 3], serving.SamplingParams(max_new_tokens=2,
                                                      temperature=0.0))
            with obs.use_context(obs.TraceContext("other-trace", "verb.7")):
                while engine.has_unfinished():
                    engine.step()
        finally:
            engine.shutdown()
        records = rec.spans()[-(rec.total_recorded - before):]
        step_ids = {r.span_id for r in records if r.name == "serving.step"}
        mine = [r for r in records if r.trace_id == "req-trace"]
        assert {"serving.prefill", "serving.finish"} <= {r.name for r in mine}
        for r in mine:
            if r.name in ("serving.prefill", "serving.finish"):
                assert r.parent_id == "router.1"
                assert r.attrs["request"] == rid
            assert r.parent_id not in step_ids


# ============================================ the host's phases of a step
PHASES = ("serving.capacity", "serving.launch", "serving.fetch",
          "serving.deliver", "serving.gauges")


def _tiny_block_engine():
    """The tiny block-diffusion engine of test_serving_block_generation."""
    import jax.numpy as jnp

    from paddle_tpu import serving
    from tests.test_sdar_moe_model import build, tiny_weights
    return serving.LLMEngine(
        build(tiny_weights()),
        serving.EngineConfig(max_num_seqs=3, page_size=8, max_model_len=64,
                             dtype=jnp.float32))


@pytest.fixture(scope="module", params=["next_token", "block_diffusion"])
def phase_records(request):
    """One tiny CPU engine of each generation kind serving three prompts:
    its ring-buffer records, its own counters and the tokens it
    delivered."""
    from paddle_tpu import serving
    if request.param == "next_token":
        engine, prompts = _tiny_engine(), [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
        new = 4
    else:
        engine = _tiny_block_engine()
        prompts = [list(range(1, 15)), list(range(20, 29)), [5, 6, 7]]
        new = 6
    rec = obs.recorder()
    prev_cap = rec.capacity
    rec.set_capacity(1 << 16)
    before = rec.total_recorded
    try:
        results = engine.generate(
            prompts, serving.SamplingParams(max_new_tokens=new,
                                            temperature=0.0))
        records = rec.spans()[-(rec.total_recorded - before):]
        m = engine.metrics
        counts = {"prefill": m.prefill_steps, "decode": m.decode_steps,
                  "sample": sum(m.sampler_paths.values())}
        delivered = sum(len(r.output_token_ids) for r in results)
    finally:
        rec.set_capacity(prev_cap)
        engine.shutdown()
    return records, counts, delivered


def _children(records, parent):
    """Direct children by start; a first call's compile is set-up, not a
    phase."""
    return sorted((r for r in records if _inside(r, parent)
                   and r.depth == parent.depth + 1
                   and r.name != "serving.compile"),
                  key=lambda r: r.start_ns)


class TestEngineStepPhases:
    @pytest.mark.parametrize("name", PHASES)
    def test_every_phase_lies_inside_one_step(self, phase_records, name):
        records = phase_records[0]
        steps = [r for r in records if r.name == "serving.step"]
        found = [r for r in records if r.name == name]
        assert found
        for r in found:
            assert sum(_inside(r, s) for s in steps) == 1, r

    def test_a_decode_steps_phases_come_in_order(self, phase_records):
        """A pass launched: capacity < launch(decode) < sample{launch
        (sample)}, each a direct child of the step's ``serving.decode``.
        A block engine then fetches inside the sample span and delivers:
        one launch a span.  A next-token engine launches the pass ahead
        (two in a span where none was in flight, none where the pass in
        flight is its last) before the fetch of the pass it delivers, a
        direct child."""
        records = phase_records[0]
        passes = [r for r in records if r.name == "serving.decode"]
        assert passes
        launch = ["serving.capacity", "serving.launch", "serving.sample"]
        for p in passes:
            kids = _children(records, p)
            names = [k.name for k in kids]
            if "serving.fetch" in names:
                n = (len(names) - 2) // 3
                assert names == launch * n + ["serving.fetch",
                                              "serving.deliver"], p
                assert 0 <= n <= 2
                if n != 1:          # one launch: ahead, or the last pass
                    assert p.attrs["ahead"] == n // 2
                fetched = []
            else:
                n = 1
                assert names == launch + ["serving.deliver"], p
                assert p.attrs["ahead"] == 0
                fetched = [("serving.fetch", None)]
            for i in range(n):
                assert kids[3 * i].attrs["grown"] >= 0
                assert kids[3 * i + 1].attrs["program"] == "decode"
                inner = _children(records, kids[3 * i + 2])
                assert [(k.name, (k.attrs or {}).get("program")) for k in
                        inner] == [("serving.launch", "sample"), *fetched]

    def test_launches_count_what_the_engine_counts(self, phase_records):
        records, counts, _ = phase_records
        launched = {}
        for r in records:
            if r.name == "serving.launch":
                launched[r.attrs["program"]] = launched.get(
                    r.attrs["program"], 0) + 1
        assert counts["prefill"] > 0 and counts["decode"] > 0
        assert launched == counts

    def test_delivery_and_gauges_cover_every_step(self, phase_records):
        records, _, delivered = phase_records
        steps = [r for r in records if r.name == "serving.step"]
        # expiry before admission and the gauges after: two a step
        assert sum(r.name == "serving.gauges" for r in records) == \
            2 * len(steps)
        deliveries = [r for r in records if r.name == "serving.deliver"]
        assert sum(r.attrs["tokens"] for r in deliveries) == delivered


class _TenRows(P.io.Dataset):
    def __len__(self):
        return 10

    def __getitem__(self, i):
        return np.full((3,), i, np.float32), np.int64(i)


class TestLoaderAndJitSpans:
    @pytest.mark.parametrize("workers,processes", [
        (0, None), (2, None), (2, False)],
        ids=["inline", "forked-workers", "threads"])
    def test_io_next_once_per_batch_delivered(self, workers, processes):
        rec = obs.recorder()
        before = rec.total_recorded
        loader = P.io.DataLoader(_TenRows(), batch_size=3,
                                 num_workers=workers,
                                 use_process_workers=processes)
        batches = list(loader)
        waits = [r for r in rec.spans()[-(rec.total_recorded - before):]
                 if r.name == "io.next"]
        assert len(batches) == 4 and len(waits) == 4
        assert {r.depth for r in waits} == {0}

    def test_io_next_on_the_native_path_and_an_early_close(self):
        rec = obs.recorder()
        before = rec.total_recorded
        ds = P.io.TensorDataset(
            [P.to_tensor(np.arange(20, dtype=np.float32).reshape(10, 2))])
        assert len(list(P.io.DataLoader(ds, batch_size=5))) == 2
        feed = iter(P.io.DataLoader(_TenRows(), batch_size=2, num_workers=2))
        next(feed)
        feed.close()                  # the workers' clean-up still runs
        waits = [r for r in rec.spans()[-(rec.total_recorded - before):]
                 if r.name == "io.next"]
        assert len(waits) == 3

    def test_jit_span_says_whether_the_program_was_cached(self):
        @P.jit.to_static
        def hit_probe(x):
            return (x * 2).sum()

        rec = obs.recorder()
        before = rec.total_recorded
        x = P.to_tensor(np.ones((4,), np.float32))
        for _ in range(3):
            hit_probe(x)
        hits = [r.attrs["hit"]
                for r in rec.spans()[-(rec.total_recorded - before):]
                if r.name == "jit.hit_probe"]
        assert hits == [False, True, True]
