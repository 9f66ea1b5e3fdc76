"""The decode step's share of the HBM roofline over the traced window: the
bytes the window's plain decode steps NEED (``latent_work.decode_step_bytes``:
every resident matrix once, each routed expert that got a token once —
``experts_hit`` of the step's ``serving.experts`` marker — and each live
latent row once a layer — ``pages_live`` of its ``serving.decode`` span)
over the HBM peak, over the time the device was busy inside those
``serving.decode`` spans on the corrected clock.  A decode step of 32 rows
is bound by bytes (a weight byte meets 32 multiply-adds, the ridge is 240).
A program without the marker (no expert layers, or the parent's) gives
nothing to read."""
from benchmark import hostspans, latent_work


def decode_spans(profile):
    """[(serving.decode span, its serving.experts marker's stats)]."""
    out = []
    for s in hostspans.host_spans(profile):
        if s.name != "serving.decode":
            continue
        marks = [c for c in s.descendants() if c.name == "serving.experts"]
        if marks:
            out.append((s, marks[-1].stats))
    return out


def read(run):
    profile = hostspans.load_current()
    if profile is None or run["peak"] is None:
        return None
    if run["cfg"].get("family") != "deepseek_v3":
        return None
    steps = decode_spans(profile)
    busy = (hostspans.busy_seconds_inside(profile, [s for s, _ in steps])
            if steps else None)
    if not busy or sum(busy) <= 0.0:
        return None
    import jax.numpy as jnp
    eng = run["traffic"]["engine"]
    itemsize = jnp.dtype(eng["dtype"]).itemsize
    need = sum(latent_work.decode_step_bytes(
        run["cfg"], int(mark["experts_hit"]),
        int(s.stats.get("pages_live", 0)) * eng["page_size"], itemsize)
        for s, mark in steps)
    return 100.0 * need / run["peak"].hbm_bytes_s / (
        sum(busy) * run["chips"])
