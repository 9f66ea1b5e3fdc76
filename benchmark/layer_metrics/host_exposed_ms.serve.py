"""Host time a decode-only engine step exposes, on no estimated clock: per
``serving.step`` span of the traced window that admitted nothing (it holds
no ``serving.prefill``), the device idle that falls inside it, each gap laid
on the host's clock at the launch of the program that ended it
(``launches.gaps``); the median over those steps.  Nothing to read where
the program annotates no ``serving.launch`` (the parent's)."""
from benchmark import hostspans, launches, stats


def read(run):
    profile = hostspans.load_current()
    if profile is None or not launches.has_launch_spans(profile):
        return None
    steps = [s for s in hostspans.host_spans(profile)
             if s.name == "serving.step"
             and not any(c.name == "serving.prefill"
                         for c in s.descendants())]
    idle = launches.idle_seconds_inside(profile, steps) if steps else None
    if not idle:
        return None
    return 1e3 * stats.median(idle)
