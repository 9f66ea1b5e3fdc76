"""A qwen3_next decode pass's share of the HBM roofline over the traced
window: the bytes the window's decode passes NEED
(``qwen3next_work.decode_pass_needed``: every resident matrix and the head
once; each HELD expert that got a token once — ``experts_hit`` of the
pass's ``serving.experts`` marker; each live slot's delta-rule state and
convolution rows once read and once written a Gated DeltaNet layer —
``state_rows`` of its ``serving.decode`` span; each live K/V row of the
full layer once — its ``pages_live`` x page size, 2,048 bytes a row) over
the HBM peak, over the time the device was busy inside those
``serving.decode`` spans on the corrected clock, whatever implements the
update.  A decode pass of 64 rows is bound by bytes.  A program whose
decode spans carry no ``state_rows`` (the parent's, which has no such
model) gives nothing to read."""
from benchmark import hostspans, qwen3next_work


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
    if run["cfg"].get("family") != "qwen3_next":
        return None
    passes = decode_spans(profile)
    busy = (hostspans.busy_seconds_inside(profile, [s for s, _ in passes])
            if passes else None)
    if not busy or sum(busy) <= 0.0:
        return None
    import jax.numpy as jnp
    eng = run["traffic"]["engine"]
    itemsize = jnp.dtype(eng["dtype"]).itemsize
    need = sum(qwen3next_work.decode_pass_needed(
        run["cfg"], int(mark["experts_hit"]), int(s.stats["state_rows"]),
        int(s.stats.get("pages_live", 0)) * eng["page_size"], itemsize)
        for s, mark in passes)
    return 100.0 * need / run["peak"].hbm_bytes_s / (
        sum(busy) * run["chips"])
