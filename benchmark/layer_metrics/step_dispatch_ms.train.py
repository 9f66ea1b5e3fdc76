"""Median duration of the program's own ``jit.train_step`` spans in the
traced window: what ``StaticFunction.__call__`` costs the host a step
(walk the state, build the key, dispatch, apply) — with steps in flight it
is hidden behind the device until the device's step comes near it."""
from benchmark import hostspans, stats


def read(run):
    spans = hostspans.durations_ms("jit.train_step")
    return stats.median(spans) if spans else None
