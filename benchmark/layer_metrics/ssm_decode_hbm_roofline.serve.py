"""A granitemoehybrid decode step's share of the HBM roofline over the
traced window: the bytes the window's plain decode steps NEED
(``ssm_work.decode_step_bytes``: every resident matrix once; each HELD
expert that got a token once — ``experts_hit`` of the step's
``serving.experts`` marker; each live slot's recurrent state once read and
once written a mamba layer — ``state_rows`` of its ``serving.decode`` span;
each live K/V row of the attention layers once — its ``pages_live``) over
the HBM peak, over the time the device was busy inside those
``serving.decode`` spans on the corrected clock, whatever implements the
update.  A decode step of 48 rows is bound by bytes.  A program whose
decode spans carry no ``state_rows`` (no state layers, or the parent's)
gives nothing to read."""
from benchmark import hostspans, ssm_work


def decode_spans(profile):
    """[(serving.decode span with ``state_rows``, its serving.experts
    marker's stats)]."""
    out = []
    for s in hostspans.host_spans(profile):
        if s.name != "serving.decode" or "state_rows" not in s.stats:
            continue
        marks = [c for c in s.descendants() if c.name == "serving.experts"]
        if marks:
            out.append((s, marks[-1].stats))
    return out


def read(run):
    profile = hostspans.load_current()
    if profile is None or run["peak"] is None:
        return None
    if run["cfg"].get("family") != "granitemoehybrid":
        return None
    steps = decode_spans(profile)
    busy = (hostspans.busy_seconds_inside(profile, [s for s, _ in steps])
            if steps else None)
    if not busy or sum(busy) <= 0.0:
        return None
    import jax.numpy as jnp
    eng = run["traffic"]["engine"]
    itemsize = jnp.dtype(eng["dtype"]).itemsize
    need = sum(ssm_work.decode_step_bytes(
        run["cfg"], int(mark["experts_hit"]), int(s.stats["state_rows"]),
        int(s.stats.get("pages_live", 0)) * eng["page_size"], itemsize)
        for s, mark in steps)
    return 100.0 * need / run["peak"].hbm_bytes_s / (
        sum(busy) * run["chips"])
