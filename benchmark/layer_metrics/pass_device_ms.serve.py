"""Device time of an engine pass: per ``serving.decode`` span of the traced
window, the summed device duration of the programs launched inside it (the
decode pass or commit, the sampler), each tied to its launch by ``run_id``
(``launches.modules``); the median.  The denominator a pass's roofline
wants.  Nothing to read where the program annotates no ``serving.launch``
(the parent's)."""
from benchmark import hostspans, launches, stats


def read(run):
    profile = hostspans.load_current()
    if profile is None or not launches.has_launch_spans(profile):
        return None
    passes = [s for s in hostspans.host_spans(profile)
              if s.name == "serving.decode"]
    busy = [b for b in launches.device_seconds_launched(profile, passes)
            if b > 0.0]
    return 1e3 * stats.median(busy) if busy else None
