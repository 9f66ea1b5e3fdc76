"""Host spans over device gaps: nesting, self time, the clock offset's
bracket and the gap table — on hand-made captures, on the recorded
``small_trace.xplane.pb`` (three jitted calls, 2 ms sleeps between) and on
``span_trace.xplane.pb`` (``tools/record_span_trace.py`` on the chip: jitted
calls in ``jit.a``, known sleeps in ``io.b``, in the parent ``serving.step``
alone and outside every span)."""
import json
import os

import pytest

from benchmark import hostspans as hs

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "small_trace.xplane.pb")
SPANS = os.path.join(HERE, "span_trace.xplane.pb")


# ------------------------------------------------------ a hand-made capture
class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _ev(name, start_us, dur_us, **stats):
    return _Obj(name=name, start_ns=1e3 * start_us, duration_ns=1e3 * dur_us,
                stats=list(stats.items()))


def _profile(host_lines, modules, ops):
    """host_lines: {line name: [events]}; times in microseconds."""
    return _Obj(planes=[
        _Obj(name="/host:CPU", lines=[_Obj(name=n, events=evs)
                                      for n, evs in host_lines]),
        _Obj(name="/device:TPU:0", lines=[
            _Obj(name="XLA Modules", events=modules),
            _Obj(name="XLA Ops", events=ops)])])


def _hand_made(offset_us=1000.0):
    """Two engine steps on the host from t=10000 us; the device's clock
    leads by ``offset_us``.  Step 1: admit+prefill (device busy 2 ms), then
    decode (busy 5 ms) with a 0.6 ms host gap before the launch and a 0.8 ms
    one inside ``serving.sample``; 1.5 ms outside every span; step 2: decode
    only, launched 0.4 ms in."""
    host = [
        _ev("serve.step", 10000, 9000),
        _ev("serving.step", 10050, 8900, running=1, waiting=1),
        _ev("serving.admit", 10100, 2300),
        _ev("serving.prefill", 10150, 2200, request="req-0", bucket=128),
        _ev("DoEnqueueProgram", 10200, 40, run_id=1),
        _ev("serving.sample", 12250, 80, width=1),
        _ev("serving.decode", 12450, 6400, live=2, pages_live=40),
        _ev("DoEnqueueProgram", 12800, 40, run_id=2),
        _ev("serving.sample", 17850, 900, width=2),
        _ev("DoEnqueueProgram", 18600, 40, run_id=3),
        # outside: 19000 .. 20500
        _ev("serve.step", 20500, 6000),
        _ev("serving.step", 20520, 5900, running=2, waiting=0),
        _ev("serving.admit", 20540, 20),
        _ev("serving.decode", 20600, 5700, live=2, pages_live=41),
        _ev("DoEnqueueProgram", 20900, 40, run_id=4),
        _ev("serving.sample", 25950, 300, width=2),
    ]
    done = [_ev("tpu::System::Execute=>Done", t, 10)
            for t in (12300, 17900, 18900, 26100)]
    o = offset_us

    def mod(run_id, start, dur):
        return _ev(f"jit_p({run_id})", start - o, dur, run_id=run_id)

    modules = [mod(1, 10210, 2000), mod(2, 12810, 5000),
               mod(3, 18610, 100), mod(4, 20910, 5000)]
    ops = [_ev("%fusion.1 = f32[8]{0} fusion(", m.start_ns / 1e3,
               m.duration_ns / 1e3) for m in modules]
    return _profile([("python", host), ("futex", done),
                     ("python", [_ev("io.next", 10000, 50)])], modules, ops)


def test_spans_nest_by_containment_on_their_own_line():
    spans = hs.host_spans(_hand_made())
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert len(by["serving.step"]) == 2 and len(by["serve.step"]) == 2
    step = by["serving.step"][0]
    assert step.parent.name == "serve.step"
    assert [c.name for c in step.children] == ["serving.admit",
                                               "serving.decode"]
    prefill = by["serving.prefill"][0]
    assert prefill.parent.name == "serving.admit"
    assert [c.name for c in prefill.children] == ["serving.sample"]
    assert prefill.stats == {"request": "req-0", "bucket": 128}
    # the other thread's span shares the line NAME and not the line
    (other,) = by["io.next"]
    assert other.parent is None and other.line != step.line
    assert {s.name for s in step.descendants()} == {
        "serving.admit", "serving.prefill", "serving.sample",
        "serving.decode"}


def test_self_time_adds_up_to_the_parents_duration():
    spans = hs.host_spans(_hand_made())
    for s in spans:
        assert hs.self_time(s) >= 0
        assert hs.self_time(s) + sum(c.seconds for c in s.children) == \
            pytest.approx(s.seconds)
    decode = next(s for s in spans if s.name == "serving.decode")
    assert hs.self_time(decode) == pytest.approx((6400 - 900) / 1e6)


def test_offset_bracket_from_launches_and_dones():
    prof = _hand_made(offset_us=1000.0)
    lo, hi = hs.clock_offset(prof)
    # tightest launch: every module starts 10 us after its enqueue began
    assert lo == pytest.approx(1e6 - 10e3)
    # tightest completion: module 2 ends at 17810 (host), Done at 17900
    assert hi == pytest.approx(1e6 + 90e3)
    assert hs.offset_point(prof) == lo


def test_an_empty_bracket_reads_nothing(capsys):
    prof = _hand_made()
    # a =>Done BEFORE its module could have ended under any shift lo allows
    done_line = prof.planes[0].lines[1]
    done_line.events[1] = _ev("tpu::System::Execute=>Done", 15000, 10)
    lo, hi = hs.clock_offset(prof)
    assert lo > hi
    assert hs.offset_point(prof) is None
    assert hs.idle_gaps(prof) is None
    assert "bracket is empty" in capsys.readouterr().err


def test_gaps_go_to_the_innermost_span_open_for_most_of_them():
    prof = _hand_made(offset_us=1000.0)
    rows = hs.idle_gaps(prof, min_ms=0.25)
    table = {name: (secs, n, longest) for name, secs, n, longest in rows}
    # prefill's module ends 12210 -> decode's starts 12810: 0.6 ms, of which
    # 0.35 under serving.decode itself, 0.08 serving.sample, the rest admit,
    # prefill and step; the sampler's module 17810 -> 18610 lies in
    # serving.sample
    assert table["serving.decode"] == pytest.approx((0.6e-3, 1, 0.6))
    assert table["serving.sample"] == pytest.approx((0.8e-3, 1, 0.8))
    # 18710 -> 20910: 2.2 ms, 1.5 of them outside every span
    assert table["outside"] == pytest.approx((2.2e-3, 1, 2.2))
    idle = sum(r[1] for r in rows)
    busy = hs.device_busy(prof)
    window = (busy[-1][1] - busy[0][0]) / 1e9
    assert idle == pytest.approx(window - sum(e - s for s, e in busy) / 1e9)
    assert [r[1] for r in rows] == sorted((r[1] for r in rows), reverse=True)


def test_uncorrected_the_same_gaps_go_to_the_wrong_span(monkeypatch):
    prof = _hand_made(offset_us=1000.0)
    monkeypatch.setattr(hs, "offset_point", lambda profile: 0.0)
    names = {name for name, *_ in hs.idle_gaps(prof, min_ms=0.25)}
    # a millisecond early, the gap before decode's launch falls into the
    # prefill and the sampler's wait into the decode span
    assert names == {"serving.prefill", "serving.decode", "outside"}


def test_short_gaps_are_summed_and_put_down_to_nothing():
    prof = _hand_made()
    rows = hs.idle_gaps(prof, min_ms=0.7)
    table = {r[0]: r for r in rows}
    assert table["short"][2] == 1 and table["short"][1] == pytest.approx(
        0.6e-3)
    assert set(table) == {"short", "serving.sample", "outside"}


def test_busy_time_inside_a_span_is_taken_on_the_corrected_clock():
    prof = _hand_made(offset_us=1000.0)
    steps = [s for s in hs.host_spans(prof) if s.name == "serving.step"]
    busy = hs.busy_seconds_inside(prof, steps)
    # at lo (10 us under the truth) module 4 (5 ms from 20910) lies inside
    # step 2 whole; step 1 holds modules 1-3 whole
    assert busy[1] == pytest.approx(5.0e-3)
    assert busy[0] == pytest.approx(7.1e-3)
    host_exposed = [s.seconds - b for s, b in zip(steps, busy)]
    assert host_exposed[1] == pytest.approx(0.9e-3)


def test_no_device_no_spans_nothing_read():
    empty = _Obj(planes=[_Obj(name="/host:CPU", lines=[])])
    assert hs.host_spans(empty) == []
    assert hs.clock_offset(empty) is None
    assert hs.idle_gaps(empty) is None
    assert hs.durations_ms("serving.decode", empty) == []
    assert hs.load(None) is None


# ---------------------------------------------------- the recorded captures
@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded capture")
def test_small_trace_offset_bracket():
    prof = hs.load(SMALL)
    lo, hi = hs.clock_offset(prof)
    assert 1.35e6 <= lo <= 1.41e6
    assert 1.80e6 <= hi <= 1.87e6
    # uncorrected, the first module "starts" before its launch
    first_module = min(hs._modules(prof))[0]
    first_launch = hs._events(prof, hs.LAUNCH)[0][0]
    assert first_module < first_launch
    assert first_module + lo >= first_launch
    rows = hs.idle_gaps(prof)
    assert [r[0] for r in rows] == ["outside", "short"]    # no span in it
    assert rows[0][2] == 2 and 3.0 < rows[0][3] < 3.5      # the two sleeps


@pytest.mark.skipif(not os.path.exists(SPANS),
                    reason="no recorded span capture beside the test")
def test_span_trace_puts_each_sleep_down_to_its_span():
    prof = hs.load(SPANS)
    with open(os.path.join(HERE, "span_trace.json")) as f:
        asked = json.load(f)["sleeps"]
    lo, hi = hs.clock_offset(prof)
    assert lo <= hi
    gaps = [g for g in hs.placed_gaps(prof) if g[2] != "short"]
    assert [name for _a, _b, name in gaps] == [name for _s, name in asked]
    for (a, b, name), (seconds, _n) in zip(gaps, asked):
        # the device idles for the sleep and the host's way to the next
        # launch and back from the last completion, never for less
        assert 1e9 * seconds <= b - a <= 1e9 * seconds + 2.5e6
    # each io.b span lies inside its gap on the corrected clock, within the
    # bracket's width (0.5 ms); uncorrected it sticks out at the far end
    sleeps = [s for s in hs.host_spans(prof) if s.name == "io.b"]
    in_b = [g for g in gaps if g[2] == "io.b"]
    assert len(sleeps) == len(in_b) == 2
    for s, (a, b, _name) in zip(sleeps, in_b):
        assert a - 0.5e6 <= s.start and s.end <= b + 0.5e6
        assert s.end > b - lo
    parent = next(s for s in hs.host_spans(prof) if s.name == "serving.step")
    assert len(parent.children) == 6               # four jit.a, two io.b
    assert hs.self_time(parent) + sum(c.seconds for c in parent.children) \
        == pytest.approx(parent.seconds)
    assert hs.self_time(parent) >= 0.004           # the sleep in it alone


def test_the_result_lines_breakdown_holds_the_gap_table():
    from benchmark import run, xplane
    got = run.breakdown_of(xplane.summarize(SPANS), hs.load(SPANS))
    rows = got["idle_gaps"]
    assert rows and len(rows) <= 10 and json.loads(json.dumps(rows)) == rows
    assert all(isinstance(n, str) and isinstance(s, float) and s > 0
               for n, s in rows)
    # the two sleeps in io.b (3 + 5 ms), the one in serving.step alone, the
    # one outside every span, and the gaps too short to place
    table = dict(rows)
    assert set(table) >= {"io.b", "serving.step", "outside"}
    assert 0.008 <= table["io.b"] <= 0.013
    full = hs.idle_gaps(hs.load(SPANS))
    assert rows == [r[:2] for r in full]
    assert got["device_ops"] and len(got["device_ops"]) <= 10


# ------------------------------------------- paged_decode_roofline.serve
def _paged_run(profile_ops):
    from benchmark import peaks
    cfg = {"num_attention_heads": 16, "head_dim": 64, "num_hidden_layers": 24}
    traffic = {"engine": {"page_size": 16, "dtype": "bfloat16"}}
    return {"trace": {"op_seconds": profile_ops}, "chips": 1, "cfg": cfg,
            "peak": peaks.peak_for("TPU v5 lite"), "traffic": traffic}


def test_paged_decode_roofline_reads_pages_live_over_the_kernels_time(
        monkeypatch):
    from benchmark import harness
    reader = harness.load_module("layer_metrics", "paged_decode_roofline.serve")
    monkeypatch.setattr(hs, "load_current", _hand_made)
    # the two decode spans read 40 + 41 pages of 16 rows: K and V rows of
    # 1024 bf16 values in 24 layers = 81 * 16 * 24 * 2 * 1024 * 2 bytes
    need = 81 * 16 * 24 * 2 * 1024 * 2
    least = need / 819e9
    ops = {"%paged_decode.3 = bf16[32,1,1024]{2,1,0} custom-call(...)":
           4 * least,
           "%paged_decode.7 = bf16[32,1,1024]{2,1,0} custom-call(...)":
           4 * least,
           "%fusion.1 = bf16[32,1024]{1,0} fusion(%paged_decode.3)": 1.0}
    assert reader.read(_paged_run(ops)) == pytest.approx(100.0 / 8)


def test_paged_decode_roofline_reads_nothing_without_the_kernel(monkeypatch):
    from benchmark import harness
    reader = harness.load_module("layer_metrics", "paged_decode_roofline.serve")
    monkeypatch.setattr(hs, "load_current", _hand_made)
    no_kernel = {"%fusion.1 = bf16[32,1024]{1,0} fusion(%paged_decode.3)": 1.0}
    assert reader.read(_paged_run(no_kernel)) is None
    assert reader.read(dict(_paged_run(no_kernel), trace=None)) is None
    # the kernel ran, but the capture holds no serving.decode span
    monkeypatch.setattr(hs, "load_current", lambda: hs.load(SMALL))
    some = {"%paged_decode.3 = bf16[32,1,1024]{2,1,0} custom-call(...)": 1.0}
    assert reader.read(_paged_run(some)) is None


def test_occupancy_reads_how_full_the_engine_ran_from_the_spans():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "idle_gaps_tool", os.path.join(os.path.dirname(HERE), "tools",
                                       "idle_gaps.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    occ = tool.occupancy(hs.host_spans(_hand_made()))
    assert occ["decode_spans"] == 2 and occ["widest_live"] == 2
    assert occ["share_at_widest"] == 1.0 and occ["mean_pages_live"] == 40.5
    # step 1 (waiting 1) starts the capture, step 2 (waiting 0) starts in
    # its second third; no step starts in the last
    assert occ["waiting_by_thirds"] == [1.0, 0.0, None]
    assert tool.occupancy(hs.host_spans(hs.load(SMALL))) is None
    rep = tool.report(_hand_made())
    assert rep["occupancy"] == occ and rep["idle_gaps"]
