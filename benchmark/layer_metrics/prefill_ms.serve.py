"""Median duration of the program's own ``serving.prefill`` spans in the
traced window: one request's prefill, during which every running stream
waits."""
from benchmark import hostspans, stats


def read(run):
    spans = hostspans.durations_ms("serving.prefill")
    return stats.median(spans) if spans else None
