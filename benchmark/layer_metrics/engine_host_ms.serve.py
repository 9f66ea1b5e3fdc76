"""Host time a decode-only engine step exposes: per ``serving.step`` span
of the traced window that admitted nothing (it holds no ``serving.prefill``),
its duration minus the time the device was busy inside it on the corrected
clock (``hostspans.offset_point``); the median over those steps."""
from benchmark import hostspans, stats


def read(run):
    profile = hostspans.load_current()
    if profile is None:
        return None
    steps = [s for s in hostspans.host_spans(profile)
             if s.name == "serving.step"
             and not any(c.name == "serving.prefill"
                         for c in s.descendants())]
    busy = hostspans.busy_seconds_inside(profile, steps) if steps else None
    if not busy:
        return None
    return 1e3 * stats.median([s.seconds - b for s, b in zip(steps, busy)])
