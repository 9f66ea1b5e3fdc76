"""launches.py: every device program tied to the span that launched it and
every idle gap laid on the host's clock at the launch of the program that
ended it — on hand-made engine loops whose clocks drift apart or whose
``=>Done`` events are miscounted (``hostspans.offset_point`` reads nothing
on either), on the recorded ``span_trace.xplane.pb`` against
``hostspans.placed_gaps``, and on ``launch_trace.xplane.pb``
(``tools/record_launch_trace.py`` on the chip: a two-program engine loop
with known sleeps in ``serving.capacity`` and ``serving.deliver``)."""
import importlib.util
import json
import os
import statistics

import pytest

from benchmark import harness, launches
from benchmark import hostspans as hs
from benchmark.tests.test_hostspans import SMALL, SPANS, _ev, _hand_made, \
    _Obj, _profile

HERE = os.path.dirname(os.path.abspath(__file__))
LAUNCH_TRACE = os.path.join(HERE, "launch_trace.xplane.pb")
CAP, DELIVER = "serving.capacity", "serving.deliver"
LONG, SHORT = 1500.0, 100.0          # us the host sleeps in a phase


def _loop(steps=20, drift_us=0.0, shifted_dones=False, split=3):
    """``steps`` decode-only engine steps back to back on the host (times in
    us): a capacity pass, the decode launched (3 ms on the device), the
    sampler launched behind it (0.2 ms, queued), the fetch, delivery.  An
    even step sleeps LONG in its delivery, an odd one LONG in the capacity
    pass of the next step's turn — so the gap after an even step is the
    delivery's and the gap after an odd step the next capacity pass's.  The
    device's clock lags the host's by 1 ms at the start and by 1 ms +
    ``drift_us`` at the end; step ``split``'s decode runs as two operations
    with 50 us between them.  ``=>Done`` events come 0.3 ms after each
    module ends, or (``shifted_dones``) one early and one missing."""
    host, runtime, dones, mods = [], [], [], []
    t = 10000.0
    for k in range(steps):
        cap = LONG if k % 2 == 0 and k else SHORT
        dlv = LONG if k % 2 == 0 else SHORT
        c0 = t + 20
        l0 = c0 + cap + 10                    # serving.launch (decode)
        enq_d = l0 + 20
        dec = (enq_d + 10, enq_d + 3010)
        s0 = l0 + 110                         # serving.sample
        enq_s = s0 + 15
        smp = (dec[1] + 2, dec[1] + 202)
        f0, f1 = s0 + 50, smp[1] + 60         # serving.fetch
        d0 = f1 + 10
        end = d0 + dlv + 30
        host += [_ev("serving.step", t, end - t, running=2, waiting=0),
                 _ev("serving.decode", t + 10, d0 + dlv + 10 - (t + 10)),
                 _ev(CAP, c0, cap),
                 _ev("serving.launch", l0, 100, program="decode"),
                 _ev("serving.sample", s0, f1 + 5 - s0, width=2),
                 _ev("serving.launch", s0 + 5, 40, program="sample"),
                 _ev("serving.fetch", f0, f1 - f0),
                 _ev(DELIVER, d0, dlv, tokens=2)]
        runtime += [_ev("DoEnqueueProgram", enq_d, 30, run_id=2 * k),
                    _ev("DoEnqueueProgram", enq_s, 20, run_id=2 * k + 1)]
        dones += [dec[1] + 300, smp[1] + 300]
        mods += [(2 * k, dec), (2 * k + 1, smp)]
        t = end + 30
    t0, t1 = 10000.0, t

    def dev(x):
        return x - 1000.0 - drift_us * (x - t0) / (t1 - t0)

    modules, ops = [], []
    for run_id, (a, b) in mods:
        modules.append(_ev(f"jit_p({run_id})", dev(a), dev(b) - dev(a),
                           run_id=run_id))
        if run_id == 2 * split:
            ops += [_ev("%fusion.1 = f32[8]{0} fusion(", dev(a),
                        dev(a + 1000) - dev(a)),
                    _ev("%fusion.2 = f32[8]{0} fusion(", dev(a + 1050),
                        dev(b) - dev(a + 1050))]
        else:
            ops.append(_ev("%fusion.1 = f32[8]{0} fusion(", dev(a),
                           dev(b) - dev(a)))
    if shifted_dones:
        dones = [dones[0] - 5000.0] + dones[:-1]
    done_evs = [_ev("tpu::System::Execute=>Done", d, 10) for d in dones]
    return _profile([("python", host), ("main", runtime),
                     ("futex", done_evs)], modules, ops)


def _expected_names(steps=20):
    # gap after step k, before step k + 1's decode, then the queued one
    # between step k + 1's decode and its sampler
    out = ["queued"]
    for k in range(steps - 1):
        out += [DELIVER if k % 2 == 0 else CAP, "queued"]
    return out


def _named(prof):
    return [g.name for g in launches.gaps(prof) if g.kind != "in_program"]


@pytest.mark.parametrize("case", ["drift", "shifted_dones"])
def test_every_gap_goes_to_the_span_that_slept_where_no_offset_reads(
        case, capsys):
    prof = (_loop(drift_us=2000.0) if case == "drift"
            else _loop(shifted_dones=True))
    lo, hi = hs.clock_offset(prof)
    assert lo > hi and hs.offset_point(prof) is None
    assert "bracket is empty" in capsys.readouterr().err
    assert _named(prof) == _expected_names()
    for g in launches.gaps(prof):
        if g.name in (CAP, DELIVER):
            assert LONG <= (g.end - g.start) / 1e3 <= LONG + 400
    link = launches.linkage(prof)
    assert link["unlinked_share"] == 0.0 and link["by_run_id"] == 40
    p5, p95 = link["anchor_ms"]
    assert p95 - p5 <= (0.15 if case == "drift" else 1e-9)


def test_the_rows_add_up_to_the_windows_idle_and_name_device_side_gaps():
    prof = _loop(drift_us=2000.0)
    rows = launches.idle_table(prof)
    busy = hs.device_busy(prof)
    window = (busy[-1][1] - busy[0][0]) / 1e9
    idle = window - sum(e - s for s, e in busy) / 1e9
    assert sum(r[1] for r in rows) == pytest.approx(idle, rel=1e-12)
    table = {r[0]: r for r in rows}
    assert set(table) == {CAP, DELIVER, "queued", "in_program"}
    assert table["in_program"][2] == 1
    assert table["in_program"][3] == pytest.approx(0.05, rel=0.03)
    assert table["queued"][2] == 20 and table[DELIVER][2] == 10
    assert [r[1] for r in rows] == sorted((r[1] for r in rows), reverse=True)


def test_each_module_is_owned_by_the_launch_span_it_was_enqueued_in():
    mods = launches.modules(_loop(drift_us=2000.0))
    assert len(mods) == 40
    assert [m.owner.name for m in mods] == ["serving.launch"] * 40
    assert [m.owner.stats["program"] for m in mods] == \
        ["decode", "sample"] * 20
    assert [m.owner.parent.name for m in mods] == \
        ["serving.decode", "serving.sample"] * 20


def test_a_module_without_run_id_is_tied_by_its_flow():
    prof = _loop()
    plane_host, plane_dev = prof.planes
    launch_line = plane_host.lines[1]
    for i, ev in enumerate(launch_line.events):
        ev.stats = [("_p", 1000 + i)]
    for i, ev in enumerate(plane_dev.lines[0].events):
        ev.stats = [("_c", 1000 + i)]
    mods = launches.modules(prof)
    assert {m.by for m in mods} == {"flow"}
    assert _named(prof) == _expected_names()
    # one launch lost: its module is unlinked and so is its gap
    launch_line.events[4].stats = []
    launches.modules.cache_clear()
    launches.gaps.cache_clear()
    link = launches.linkage(prof)
    assert link["unlinked_share"] == pytest.approx(1 / 40)
    assert "unlinked" in _named(prof)


def _reader(name):
    return harness.load_module("layer_metrics", name)


def _exposed_us(steps=20, split=3):
    """Device idle inside each step on the host's clock: from its start to
    its decode's launch (50 us + the capacity pass), the 2 us turnaround,
    and from the sampler's end, laid 10 us early, to the step's end (110 us
    + the delivery); the window opens at the first step's decode and closes
    at the last step's sampler.  Step ``split`` holds 50 us more."""
    out = []
    for k in range(steps):
        cap = LONG if k % 2 == 0 and k else SHORT
        dlv = LONG if k % 2 == 0 else SHORT
        out.append((50 + cap if k else 0) + 2 + 50 * (k == split)
                   + (110 + dlv if k < steps - 1 else 0))
    return out


@pytest.mark.parametrize("drift_us", [0.0, 2000.0])
def test_the_three_readers(monkeypatch, drift_us):
    prof = _loop(drift_us=drift_us)
    monkeypatch.setattr(hs, "load_current", lambda: prof)
    exposed = _reader("host_exposed_ms.serve").read({})
    # a clock that drifts 2 ms in ~100 ms runs 2% slow: device intervals
    # read 2% short
    rel = 0.05 if drift_us else 1e-6
    assert exposed == pytest.approx(statistics.median(_exposed_us()) / 1e3,
                                    rel=rel)
    assert _reader("pass_device_ms.serve").read({}) == pytest.approx(
        3.2, rel=rel)
    assert _reader("launch_ms.serve").read({}) == pytest.approx(0.14)
    steps = [s for s in hs.host_spans(prof) if s.name == "serving.step"]
    idle = launches.idle_seconds_inside(prof, steps)
    # and a gap drifts by 2% of its length between its two ends
    assert [1e6 * x for x in idle] == pytest.approx(
        _exposed_us(), abs=50 if drift_us else 1e-6)
    if drift_us == 0.0:
        # where the offset reads, a step the window holds whole reads the
        # same on both clocks
        busy = hs.busy_seconds_inside(prof, steps)
        old = [s.seconds - b for s, b in zip(steps, busy)]
        assert idle[1:-1] == pytest.approx(old[1:-1], abs=1e-9)


@pytest.mark.parametrize("profile", ["hostspans_hand_made", "small"])
def test_the_readers_read_nothing_without_launch_spans(monkeypatch, profile):
    prof = _hand_made() if profile == "hostspans_hand_made" else hs.load(
        SMALL)
    monkeypatch.setattr(hs, "load_current", lambda: prof)
    for name in ("host_exposed_ms.serve", "pass_device_ms.serve",
                 "launch_ms.serve"):
        assert _reader(name).read({}) is None
    monkeypatch.setattr(hs, "load_current", lambda: None)
    for name in ("host_exposed_ms.serve", "pass_device_ms.serve",
                 "launch_ms.serve"):
        assert _reader(name).read({}) is None
    empty = _Obj(planes=[_Obj(name="/host:CPU", lines=[])])
    assert launches.gaps(empty) is None and launches.modules(empty) == []


@pytest.mark.skipif(not os.path.exists(SPANS), reason="no recorded capture")
def test_span_trace_gaps_land_where_placed_gaps_put_them():
    prof = hs.load(SPANS)
    old = [name for _a, _b, name in hs.placed_gaps(prof) if name != "short"]
    new = [g.name for g in launches.gaps(prof) if g.kind == "host"]
    assert new == old == ["io.b", "io.b", "serving.step", "outside"]
    assert {g.kind for g in launches.gaps(prof)} == {"host", "in_program"}
    mods = launches.modules(prof)
    assert [m.by for m in mods] == ["run_id"] * 5
    assert {m.owner.name for m in mods} == {"jit.a"}
    # the profiler's flow ties the same launch to every module
    flows = {st["_p"]: s for s, _e, st in hs._events(prof, hs.LAUNCH)}
    assert [flows[st["_c"]] for _s, _e, st in hs._modules(prof)] == \
        [m.launch for m in mods]
    p5, p95 = launches.linkage(prof)["anchor_ms"]
    assert p95 - p5 < 0.1


@pytest.mark.skipif(not os.path.exists(LAUNCH_TRACE),
                    reason="no recorded launch capture beside the test")
def test_launch_trace_puts_each_sleep_on_its_phase():
    prof = hs.load(LAUNCH_TRACE)
    with open(os.path.join(HERE, "launch_trace.json")) as f:
        asked = json.load(f)["sleeps"]
    found = launches.gaps(prof)
    placed = [g for g in found if g.kind == "host"]
    assert [g.name for g in placed] == [name for name, _s in asked]
    for g, (_name, seconds) in zip(placed, asked):
        assert 1e9 * seconds <= g.end - g.start <= 1e9 * seconds + 2.5e6
    mods = launches.modules(prof)
    assert all(m.by == "run_id" for m in mods)
    assert [m.owner.stats["program"] for m in mods] == \
        ["decode", "sample"] * (len(asked) + 1)
    link = launches.linkage(prof)
    assert link["unlinked_share"] == 0.0
    p5, p95 = link["anchor_ms"]
    assert p95 - p5 < 0.1


def test_the_tool_reports_phases_gaps_and_why_the_offset_refused():
    spec = importlib.util.spec_from_file_location(
        "host_phases_tool", os.path.join(os.path.dirname(HERE), "tools",
                                         "host_phases.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    rep = tool.report(_loop(drift_us=2000.0))
    decode = rep["phases"]["decode"]
    assert decode["steps"] == 20 and "prefill" not in rep["phases"]
    assert decode["self_ms"][DELIVER] == pytest.approx(
        (LONG + SHORT) / 2e3)
    assert rep["offset"]["offset_point"] == "refused: lo > hi"
    assert rep["offset"]["run_id_matched"] == 1.0
    first, last = rep["offset"]["first_third"], rep["offset"]["last_third"]
    assert last[0] - first[0] > 1.0          # lo drifts with the clock
    assert sum(r[1] for r in rep["linked_gaps"]) == pytest.approx(
        rep["idle_s"])
    assert rep["readings"]["pass_device_ms.serve"] == pytest.approx(
        3.2, rel=0.03)
    assert "offset: engine_host_ms" not in rep["readings"]
