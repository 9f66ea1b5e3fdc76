"""The program's own spans in a profiler capture, laid over the device's
idle gaps on ONE clock.

``paddle_tpu.observability.span`` opens a ``TraceAnnotation`` under any jax
capture, so ``serving.step``, ``jit.train_step``, ``io.next`` ... are events
of the capture's ``/host:CPU`` plane beside the runtime's own
(``DoEnqueueProgram``, ``tpu::System::Execute=>Done``), and the device's
``XLA Modules`` / ``XLA Ops`` lines lie in the same file.  Host and device
lines are NOT on one clock as written: the device's lead the host's by a
shift that is constant within a capture (1.36-1.82 ms in
``tests/small_trace.xplane.pb``), of the size of the gaps to be explained.
``clock_offset`` brackets the shift from causality, and everything here that
lays host spans over device time corrects by one point of that bracket.

Read with ``jax.profiler.ProfileData`` alone, like ``xplane.py``; one device
(the first ``/device:TPU:<n>`` plane): the accepted cells hold one chip.
"""
from __future__ import annotations

import bisect
import functools
import sys
from dataclasses import dataclass, field

from benchmark import xplane

HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
LAUNCH = "DoEnqueueProgram"                 # host: the runtime hands a program
#                                             to the chip (stat run_id)
DONE = "tpu::System::Execute=>Done"         # host: told that it has finished
PROGRAM_PREFIXES = ("serving.", "jit.", "io.", "optimizer.")
BENCHMARK_SPANS = ("serve.step", "serve.add_request", "train.step",
                   "data.next")
MIN_GAP_MS = 0.25


@dataclass
class Span:
    """One host span, times in ns on the HOST lines' clock."""
    name: str
    start: float
    end: float
    line: int                    # index of its line in the host plane (two
    stats: dict                  # threads' lines may share the name "python")
    parent: "Span | None" = None
    children: list = field(default_factory=list)

    @property
    def seconds(self):
        return (self.end - self.start) / 1e9

    def descendants(self):
        for c in self.children:
            yield c
            yield from c.descendants()


def _is_span(name):
    return name.startswith(PROGRAM_PREFIXES) or name in BENCHMARK_SPANS


@functools.lru_cache(maxsize=2)
def load(path):
    """The capture at ``path``, parsed once for every reader of one run;
    ``None`` where there is none."""
    if not path:
        return None
    from jax.profiler import ProfileData
    try:
        return ProfileData.from_file(path)
    except (OSError, RuntimeError) as e:     # a reader reads nothing then
        print(f"[hostspans] cannot read {path}: {e}", file=sys.stderr)
        return None


def load_current():
    """The capture of the run in progress (``harness.Capture``'s fixed
    directory): it still exists while the per-layer readers run."""
    from benchmark import harness
    return load(harness.Capture(False).xplane_path())


def _host_lines(profile):
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            return list(enumerate(plane.lines))
    return []


def _device_plane(profile):
    planes = sorted((p for p in profile.planes
                     if p.name.startswith(xplane.DEVICE_PREFIX)),
                    key=lambda p: p.name)
    return planes[0] if planes else None


@functools.lru_cache(maxsize=2)
def host_spans(profile):
    """Every span of the program (``serving.*``, ``jit.*``, ``io.*``,
    ``optimizer.*``) and of the benchmark's loop in the capture, by start,
    nested by containment on its own line."""
    out = []
    for idx, line in _host_lines(profile):
        spans = [Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                      idx, dict(ev.stats))
                 for ev in line.events if _is_span(ev.name)]
        spans.sort(key=lambda s: (s.start, -s.end))
        stack = []
        for s in spans:
            while stack and s.start >= stack[-1].end:
                stack.pop()
            if stack:
                s.parent = stack[-1]
                stack[-1].children.append(s)
            stack.append(s)
        out.extend(spans)
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def self_time(span) -> float:
    """Seconds of ``span`` that none of its children on its line cover."""
    return span.seconds - sum(c.seconds for c in span.children)


def _events(profile, name):
    return sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                   for _i, line in _host_lines(profile)
                   for ev in line.events if ev.name == name),
                  key=lambda e: e[0])


def _modules(profile):
    plane = _device_plane(profile)
    if plane is None:
        return []
    return sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                   for line in plane.lines if line.name == MODULES_LINE
                   for ev in line.events), key=lambda e: e[0])


@functools.lru_cache(maxsize=2)
def clock_offset(profile):
    """``(lo_ns, hi_ns)``: the bracket of the shift that, ADDED to a device
    line's time, gives the host lines' time.

    ``lo`` is the least shift after which no module starts before the host
    began to enqueue it (launch and module share a ``run_id``; without one,
    k-th launch and k-th module).  ``hi`` is the largest shift after which
    no module ends after the host was told so (k-th ``=>Done`` and k-th
    module where the capture holds as many of one as of the other; else each
    module takes the first ``=>Done`` that ``lo`` allows, which can only
    widen the bracket).  ``None`` where the capture lacks the events.
    """
    modules = _modules(profile)
    launches = _events(profile, LAUNCH)
    dones = _events(profile, DONE)
    if not modules or not launches or not dones:
        return None
    by_run = {st.get("run_id"): s for s, _e, st in launches
              if st.get("run_id") is not None}
    pairs = [(by_run[st["run_id"]], s) for s, _e, st in modules
             if st.get("run_id") in by_run]
    if not pairs and len(launches) == len(modules):
        pairs = [(l[0], m[0]) for l, m in zip(launches, modules)]
    if not pairs:
        return None
    lo = max(launch - start for launch, start in pairs)
    if len(dones) == len(modules):
        hi = min(d[0] - m[1] for d, m in zip(dones, modules))
    else:
        starts = [d[0] for d in dones]
        took = [bisect.bisect_left(starts, m[1] + lo) for m in modules]
        slack = [starts[k] - m[1] for k, m in zip(took, modules)
                 if k < len(starts)]
        if not slack:
            return None
        hi = min(slack)
    return lo, hi


def offset_point(profile):
    """The one point of the bracket every reduction here corrects by:
    ``lo``.  A program launched onto an idle device starts within tens of
    microseconds of its enqueue, so the tightest launch pins the truth from
    below closely; ``hi`` carries the completion's way back to a host
    thread (~0.4 ms in the recorded captures).  At ``lo`` a device interval
    lies at most ``hi - lo`` too early on the host's clock.  Refuses
    (``None``, why on stderr) where the bracket is empty or cannot be had.
    """
    bracket = clock_offset(profile)
    if bracket is None:
        print("[hostspans] no launch / module / =>Done events to take the "
              "clock offset from: nothing read", file=sys.stderr)
        return None
    lo, hi = bracket
    if lo > hi:
        print(f"[hostspans] clock offset bracket is empty (lo {lo / 1e6:.3f} "
              f"ms > hi {hi / 1e6:.3f} ms): the launch / =>Done events do "
              f"not mean what this reduction takes them for; nothing read",
              file=sys.stderr)
        return None
    return lo


@functools.lru_cache(maxsize=2)
def device_busy(profile):
    """Merged ``[(start_ns, end_ns), ...]`` in which an operation ran on
    the device (the union ``xplane.union_seconds`` measures), device clock."""
    plane = _device_plane(profile)
    if plane is None:
        return []
    merged = []
    for _name, s, e in sorted(
            xplane.device_events(profile).get(plane.name, []),
            key=lambda ev: ev[1]):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [tuple(iv) for iv in merged]


class _Busy:
    """Busy nanoseconds inside any interval, from prefix sums."""

    def __init__(self, intervals):
        self.starts = [s for s, _ in intervals]
        self.ends = [e for _, e in intervals]
        self.before = [0.0]
        for s, e in intervals:
            self.before.append(self.before[-1] + (e - s))

    def _upto(self, t):
        k = bisect.bisect_right(self.starts, t)
        if k == 0:
            return 0.0
        return self.before[k] - max(0.0, self.ends[k - 1] - t)

    def inside(self, a, b):
        return self._upto(b) - self._upto(a)


def busy_seconds_inside(profile, spans):
    """For each span: seconds the device was busy between its start and its
    end, on the corrected clock; ``None`` where the offset cannot be had."""
    off = offset_point(profile)
    if off is None:
        return None
    busy = _Busy(device_busy(profile))
    return [busy.inside(s.start - off, s.end - off) / 1e9 for s in spans]


def _innermost_cover(span, a, b, cover):
    """Adds to ``cover[name]`` the ns of ``[a, b]`` during which ``span``
    is the innermost span open on its line."""
    lo, hi = max(a, span.start), min(b, span.end)
    if hi <= lo:
        return
    own = hi - lo
    for c in span.children:
        own -= max(0.0, min(hi, c.end) - max(lo, c.start))
        _innermost_cover(c, a, b, cover)
    cover[span.name] = cover.get(span.name, 0.0) + own


def placed_gaps(profile, min_ms=None):
    """Every idle interval of the device inside the traced window as
    ``(start_ns, end_ns, name)`` on the HOST's clock, in order.

    A gap of at least ``min_ms`` (default: the offset bracket's width, never
    under 0.25 ms — a shorter gap cannot be placed) is put down to the span
    that was the INNERMOST one open for the largest part of it on the
    thread that runs the engine / the train loop (the line holding the most
    spans): a program span, else the benchmark's span round it, else
    ``outside``.  A shorter gap is named ``short``.  ``None`` where no
    operation ran or the clocks cannot be laid over each other.
    """
    busy = device_busy(profile)
    off = offset_point(profile)
    if not busy or off is None:
        return None
    if min_ms is None:
        lo, hi = clock_offset(profile)
        min_ms = max(MIN_GAP_MS, (hi - lo) / 1e6)
    spans = host_spans(profile)
    per_line = {}
    for s in spans:
        per_line[s.line] = per_line.get(s.line, 0) + 1
    main = max(per_line, key=per_line.get) if per_line else None
    roots = [s for s in spans if s.line == main and s.parent is None]
    root_starts = [s.start for s in roots]
    out = []
    for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
        a, b = e0 + off, s1 + off
        if b - a < min_ms * 1e6:
            out.append((a, b, "short"))
            continue
        cover = {}
        k = max(0, bisect.bisect_right(root_starts, a) - 1)
        while k < len(roots) and roots[k].start < b:
            _innermost_cover(roots[k], a, b, cover)
            k += 1
        cover["outside"] = (b - a) - sum(cover.values())
        out.append((a, b, max(cover, key=cover.get)))
    return out


def idle_gaps(profile, min_ms=None):
    """``placed_gaps`` added up by name: ``[[name, seconds, count,
    longest_ms], ...]`` by time, the rows adding up to the window's idle
    seconds — the shape ``breakdown.idle_gaps`` wants."""
    gaps = placed_gaps(profile, min_ms)
    if gaps is None:
        return None
    rows = {}
    for a, b, name in gaps:
        row = rows.setdefault(name, [0.0, 0, 0.0])
        row[0] += (b - a) / 1e9
        row[1] += 1
        row[2] = max(row[2], (b - a) / 1e6)
    return sorted(([name, secs, n, longest]
                   for name, (secs, n, longest) in rows.items()),
                  key=lambda r: -r[1])


def durations_ms(name, profile=None):
    """Durations (ms) of every span called ``name`` in the capture (the
    run's own where none is given); empty where there is no capture or no
    such span — the parent's program annotates none."""
    profile = profile or load_current()
    if profile is None:
        return []
    return [1e3 * s.seconds for s in host_spans(profile) if s.name == name]
