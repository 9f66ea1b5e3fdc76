"""The decode passes' share of the HBM roofline in a model with window
layers, over the traced window: the bytes the window's decode passes NEED
(``smallthinker_work.decode_pass_needed``: every resident matrix and the
head once; each expert that got a token once — ``experts_hit`` of the
step's ``serving.experts`` marker; every live K/V row once a layer —
``pages_live x page_size`` rows in a full layer, ``window_rows_live`` rows
in a window layer, both of the step's ``serving.decode`` span) over the HBM
peak, over the time the device was busy inside those ``serving.decode``
spans on the corrected clock, whatever implements the read.  A pass of 32
rows is bound by bytes.  A program whose decode spans carry no
``window_rows_live`` (no window layers, or the parent's) gives nothing to
read."""
from benchmark import hostspans, smallthinker_work


def decode_spans(profile):
    """[(serving.decode span with ``window_rows_live``, its
    serving.experts marker's stats)]."""
    out = []
    for s in hostspans.host_spans(profile):
        if s.name != "serving.decode" or "window_rows_live" not in s.stats:
            continue
        marks = [c for c in s.descendants() if c.name == "serving.experts"]
        if marks:
            out.append((s, marks[-1].stats))
    return out


def read(run):
    profile = hostspans.load_current()
    if profile is None or run["peak"] is None:
        return None
    if run["cfg"].get("family") != "smallthinker":
        return None
    steps = decode_spans(profile)
    busy = (hostspans.busy_seconds_inside(profile, [s for s, _ in steps])
            if steps else None)
    if not busy or sum(busy) <= 0.0:
        return None
    import jax.numpy as jnp
    eng = run["traffic"]["engine"]
    itemsize = jnp.dtype(eng["dtype"]).itemsize
    need = sum(smallthinker_work.decode_pass_needed(
        run["cfg"], int(mark["experts_hit"]),
        int(s.stats.get("pages_live", 0)) * eng["page_size"],
        int(s.stats["window_rows_live"]), itemsize)
        for s, mark in steps)
    return 100.0 * need / run["peak"].hbm_bytes_s / (
        sum(busy) * run["chips"])
