"""The host's dispatch cost of an engine pass: per ``serving.decode`` span
of the traced window, the summed duration of the ``serving.launch`` spans
inside it (operands placed and each program called: the pass, the
sampler); the median.  Nothing to read where the program annotates no
``serving.launch`` (the parent's)."""
from benchmark import hostspans, launches, stats


def read(run):
    profile = hostspans.load_current()
    if profile is None:
        return None
    per_pass = [sum(c.seconds for c in s.descendants()
                    if c.name == launches.LAUNCH_SPAN)
                for s in hostspans.host_spans(profile)
                if s.name == "serving.decode"]
    per_pass = [t for t in per_pass if t > 0.0]
    return 1e3 * stats.median(per_pass) if per_pass else None
