"""Device programs tied to the host span that launched them, and the
device's idle gaps laid on the host's clock program by program.

Each ``XLA Modules`` event of the device plane is one run of one program;
the host handed it over in a ``DoEnqueueProgram`` event of the host plane.
The two carry one ``run_id``; where a capture lacks it, the profiler's own
flow ties them (the launch's ``_p`` is the module's ``_c``).  The launch is
an event on the host's clock, so the span open round it on the thread that
runs the engine -- a ``serving.launch`` -- OWNS the module exactly: no shift
between the two planes' clocks enters.

A device gap (from the end of one busy interval to the start of the next,
device clock) is laid on the host's clock at the launch of the module that
ends it, shifted by that module's own ``start - launch``.  Where the device
was idle when the launch came, that shift is the clocks' offset at that
moment plus the runtime's hand-over (tens of microseconds), so neither a
drift between the clocks over the window nor ``=>Done`` events that do not
pair with the modules can move a gap.  A module whose shift exceeds the last
idle-launched module's by more than ``QUEUED_MS`` was enqueued behind
another program (a sampler behind its decode pass): the gap before it is
the device's own turnaround, ``queued``.  A gap between two operations of
one program is ``in_program``; one before a module with no launch,
``unlinked``.  Those three are laid on the host's clock at the last
idle-launched module's shift.  A gap ended by an idle launch goes to the
span innermost for most of it on the engine's thread
(``hostspans._innermost_cover``), else ``outside``.

No global offset and no ``=>Done`` pairing are used here; ``hostspans``
keeps both for the readers that still use them.
"""
from __future__ import annotations

import bisect
import functools
from dataclasses import dataclass

from benchmark import hostspans, stats

FLOW_OUT = "_p"                 # a launch's flow id
FLOW_IN = "_c"                  # the module's end of the same flow
LAUNCH_SPAN = "serving.launch"
QUEUED_MS = 1.0                 # a module enqueued behind another waits a
#                                 whole program (3-30 ms in the serving
#                                 cells); a hand-over onto an idle device
#                                 varies by tens of microseconds


@dataclass
class Module:
    """One run of a program: device-clock times, the host-clock start of
    the launch that enqueued it (``None``: none found), how it was found
    (``run_id`` / ``flow``) and the innermost engine span open then."""
    start: float
    end: float
    launch: float | None
    by: str | None
    owner: hostspans.Span | None

    @property
    def shift(self):
        return self.start - self.launch


@dataclass
class Gap:
    """One idle interval of the device (device clock), what ended it, the
    shift that lays it on the host's clock, and what it is put down to."""
    start: float
    end: float
    kind: str                   # host | queued | in_program | unlinked
    shift: float
    name: str
    excess: float | None = None  # host gaps: shift over the last idle launch's

    @property
    def host(self):
        return self.start - self.shift, self.end - self.shift


def _engine_roots(profile):
    """Top-level spans of the host line holding the most spans (the thread
    that runs the engine / the benchmark's loop), by start."""
    spans = hostspans.host_spans(profile)
    per_line = {}
    for s in spans:
        per_line[s.line] = per_line.get(s.line, 0) + 1
    if not per_line:
        return []
    main = max(per_line, key=per_line.get)
    return [s for s in spans if s.line == main and s.parent is None]


def _innermost_at(roots, starts, t):
    k = bisect.bisect_right(starts, t) - 1
    if k < 0 or t >= roots[k].end:
        return None
    span = roots[k]
    while True:
        inner = next((c for c in span.children if c.start <= t < c.end),
                     None)
        if inner is None:
            return span
        span = inner


@functools.lru_cache(maxsize=2)
def modules(profile):
    """Every module of the device plane by start, each tied to its launch;
    ``[]`` where the capture holds none."""
    by_run, by_flow = {}, {}
    for start, _end, st in hostspans._events(profile, hostspans.LAUNCH):
        if st.get("run_id") is not None:
            by_run.setdefault(st["run_id"], start)
        if st.get(FLOW_OUT) is not None:
            by_flow.setdefault(st[FLOW_OUT], start)
    roots = _engine_roots(profile)
    starts = [r.start for r in roots]
    out = []
    for start, end, st in hostspans._modules(profile):
        launch, by = by_run.get(st.get("run_id")), "run_id"
        if launch is None:
            launch, by = by_flow.get(st.get(FLOW_IN)), "flow"
        if launch is None:
            out.append(Module(start, end, None, None, None))
        else:
            out.append(Module(start, end, launch, by,
                              _innermost_at(roots, starts, launch)))
    return out


@functools.lru_cache(maxsize=2)
def gaps(profile):
    """Every idle interval of the device inside the traced window, in
    order (they add up to the window's idle); ``None`` where no operation
    ran or no module has a launch."""
    busy = hostspans.device_busy(profile)
    mods = modules(profile)
    if not busy or not any(m.launch is not None for m in mods):
        return None
    # the shift of the last module launched onto an idle device, at each
    # module (before the first such one: the first linked module's)
    ref, refs, queued = None, [], []
    for m in mods:
        late = (m.launch is not None and ref is not None
                and m.shift - ref > QUEUED_MS * 1e6)
        queued.append(late)
        refs.append(ref)
        if m.launch is not None and not late:
            ref = m.shift
    first = next(m.shift for m in mods if m.launch is not None)
    refs = [first if r is None else r for r in refs]
    mod_starts = [m.start for m in mods]
    roots = _engine_roots(profile)
    root_starts = [r.start for r in roots]
    out = []
    for (_s0, a), (b, _e1) in zip(busy, busy[1:]):
        k = bisect.bisect_right(mod_starts, b) - 1
        if k < 0 or mods[k].end < b or mods[k].launch is None:
            kind = "unlinked"
        elif mods[k].start <= a:
            kind = "in_program"
        elif queued[k]:
            kind = "queued"
        else:
            kind = "host"
        ref = refs[max(k, 0)]
        if kind != "host":
            out.append(Gap(a, b, kind, ref, kind))
            continue
        m = mods[k]
        ha, hb = a - m.shift, b - m.shift
        cover = {}
        r = max(0, bisect.bisect_right(root_starts, ha) - 1)
        while r < len(roots) and roots[r].start < hb:
            hostspans._innermost_cover(roots[r], ha, hb, cover)
            r += 1
        cover["outside"] = (hb - ha) - sum(cover.values())
        out.append(Gap(a, b, kind, m.shift, max(cover, key=cover.get),
                       m.shift - ref))
    return out


def idle_table(profile):
    """``gaps`` added up by name: ``[[name, seconds, count, longest_ms],
    ...]`` by time — the shape of ``breakdown.idle_gaps``."""
    found = gaps(profile)
    if found is None:
        return None
    rows = {}
    for g in found:
        row = rows.setdefault(g.name, [0.0, 0, 0.0])
        row[0] += (g.end - g.start) / 1e9
        row[1] += 1
        row[2] = max(row[2], (g.end - g.start) / 1e6)
    return sorted(([name, secs, n, longest]
                   for name, (secs, n, longest) in rows.items()),
                  key=lambda r: -r[1])


def idle_seconds_inside(profile, spans):
    """For each span: seconds of device idle that fall inside it, every
    gap laid on the host's clock as ``gaps`` lays it; ``None`` where
    ``gaps`` is."""
    found = gaps(profile)
    if found is None:
        return None
    # two gaps laid by two launches' shifts can overlap by the shifts'
    # difference: the later one starts where the earlier ends
    laid, last = [], float("-inf")
    for a, b in sorted(g.host for g in found):
        a = max(a, last)
        if b > a:
            laid.append((a, b))
            last = b
    idle = hostspans._Busy(laid)
    return [idle.inside(s.start, s.end) / 1e9 for s in spans]


def device_seconds_launched(profile, spans):
    """For each span: summed device duration of the modules whose launch
    lies inside it."""
    linked = sorted((m for m in modules(profile) if m.launch is not None),
                    key=lambda m: m.launch)
    at = [m.launch for m in linked]
    out = []
    for s in spans:
        lo, hi = bisect.bisect_left(at, s.start), bisect.bisect_left(at, s.end)
        out.append(sum(m.end - m.start for m in linked[lo:hi]) / 1e9)
    return out


def has_launch_spans(profile):
    """Whether the program annotates its launches (the parent's does not:
    every reader built on them then reads nothing)."""
    return any(s.name == LAUNCH_SPAN for s in hostspans.host_spans(profile))


def linkage(profile):
    """How the capture's modules were tied and how tight the anchors are:
    counts, the unlinked share, and the spread (p5, p95 in ms) of the
    host gaps' shifts over the last idle launch's."""
    mods = modules(profile)
    found = gaps(profile) or []
    excess = [g.excess / 1e6 for g in found if g.kind == "host"]
    n = len(mods)
    return {"launches": len(hostspans._events(profile, hostspans.LAUNCH)),
            "modules": n,
            "by_run_id": sum(m.by == "run_id" for m in mods),
            "by_flow": sum(m.by == "flow" for m in mods),
            "unlinked_share": (sum(m.launch is None for m in mods) / n
                               if n else None),
            "host_gaps": len(excess),
            "anchor_ms": ([stats.percentile(excess, 5),
                           stats.percentile(excess, 95)] if excess else None)}
