"""Median duration of the program's own ``serving.decode`` spans in the
traced window (capacity pass, decode program, sample, post-token): the
inside twin of ``decode_step_ms``, which also holds expiry, the empty admit
pass and the gauges."""
from benchmark import hostspans, stats


def read(run):
    spans = hostspans.durations_ms("serving.decode")
    return stats.median(spans) if spans else None
