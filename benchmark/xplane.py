"""Reduction of a profiler capture (``.xplane.pb``) to what the metrics
read: the traced window, the seconds in which an operation ran on each
device (the union of its intervals), and the summed time per operation
name.  Read with ``jax.profiler.ProfileData`` alone.

A device is a plane named ``/device:TPU:<n>``; its operations are the
events of the line ``XLA Ops``.  The window is the span from the first
operation's start to the last operation's end over all devices — the
capture is started and stopped around the measured loop, and the host's
own start/stop overhead is not device time.
"""
from __future__ import annotations

import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def union_seconds(intervals) -> float:
    """Total length covered by ``[(start_ns, end_ns), ...]``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def device_events(profile):
    """{plane name: [(op name, start_ns, end_ns), ...]} of device planes."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            out[plane.name] = [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in line.events]
    return out


_SUFFIX = re.compile(r"(\.\d+|\.remat\d*|\.clone)+$")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_kind(name: str) -> str:
    """A device operation's kind, so that the copies of one operation in
    every layer add up: an HLO instruction ``%fusion.12 = f32[8,4]{..}
    fusion(...)`` reads ``fusion f32[8,4] fusion``; another name is kept."""
    if not name.startswith("%") or " = " not in name:
        return name[:80]
    ident, rest = name[1:].split(" = ", 1)
    if rest.startswith("("):
        depth, end = 0, len(rest)
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                end = i + 1
                break
    else:
        end = rest.find(" ") if " " in rest else len(rest)
    out_type = _LAYOUT.sub("", rest[:end])
    opcode = rest[end:].strip().split("(", 1)[0]
    return f"{_SUFFIX.sub('', ident)} {out_type} {opcode}"[:120]


def reduce_events(events_by_plane: dict):
    """The summary every reader gets; ``None`` where no operation ran."""
    planes = {k: v for k, v in events_by_plane.items() if v}
    if not planes:
        return None
    first = min(s for evs in planes.values() for _, s, _ in evs)
    last = max(e for evs in planes.values() for _, _, e in evs)
    busy = [union_seconds([(s, e) for _, s, e in evs])
            for evs in planes.values()]
    op_seconds, op_counts = {}, {}
    for evs in planes.values():
        for name, s, e in evs:
            op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / 1e9
            op_counts[name] = op_counts.get(name, 0) + 1
    n = len(planes)
    kinds = {}
    for name, secs in op_seconds.items():
        kinds[op_kind(name)] = kinds.get(op_kind(name), 0.0) + secs
    top = sorted(kinds.items(), key=lambda kv: -kv[1])
    return {"window_s": (last - first) / 1e9,
            "busy_s": sum(busy) / n,
            "devices": n,
            "op_seconds": {k: v / n for k, v in op_seconds.items()},
            "op_counts": op_counts,
            "top_ops": [[k, v / n] for k, v in top[:10]]}


def idle_pct(summary):
    """1 - busy over the traced window, in percent; nothing where no
    operation ran."""
    if summary is None:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])


def summarize(path: str):
    from jax.profiler import ProfileData
    return reduce_events(device_events(ProfileData.from_file(path)))
