"""``grouped_paged_decode``'s share of its roofline in the decode passes of
a model whose ``kv`` layers cache grouped-query K/V beside another kind
(``granitemoehybrid``: attention layers beside per-slot state;
``smallthinker``: full layers beside window rings), over the traced window.

Numerator: the least time the chip could take for what those passes NEED
of the kernel — the K and the V row of ``pages_live`` x page size positions
(the ``serving.decode`` span's pages: what the kernel reads) in every
``kv`` layer, once, against the HBM peak; the ``4 H_q d`` FLOPs a position
and layer against the bf16 peak lose (~7 FLOPs a byte).  Denominator: the
device time of the operations named ``grouped_paged_decode`` inside the
programs LAUNCHED within those spans, each program tied to its launch by
``run_id`` (``launches.modules``).  A span counts only if a program it
launched is in the capture, so a capture cut short of the host's spans
drops a span's rows and its time together.  Nothing to read where no such
operation ran (the parent's program, another family)."""
import bisect

from benchmark import hostspans, launches, smallthinker_work, ssm_work
from benchmark import xplane

KERNEL = "grouped_paged_decode"


def kv_geometry(cfg, itemsize):
    """(``kv`` layers, bytes of a position's K and V rows in one, query
    width ``H_q x d``) of a family that caches grouped K/V by page; None
    for another."""
    if cfg.get("family") == "smallthinker":
        return (smallthinker_work._layers(cfg)[0],
                smallthinker_work.kv_row_bytes(cfg, itemsize),
                cfg["num_attention_heads"] * cfg["head_dim"])
    if cfg.get("family") == "granitemoehybrid":
        return (ssm_work._layers(cfg)[1], ssm_work.kv_row_bytes(cfg, itemsize),
                cfg["hidden_size"])
    return None


def kernel_seconds(profile, spans):
    """For each span (by start, not overlapping): device seconds of the
    kernel's operations inside the programs launched within it, and
    whether any program it launched is in the capture."""
    plane = hostspans._device_plane(profile)
    events = xplane.device_events(profile).get(plane.name, []) if plane else []
    ops = sorted((s, e) for name, s, e in events
                 if KERNEL in name.split(" = ", 1)[0])
    op_starts = [s for s, _ in ops]
    starts = [s.start for s in spans]
    spent, seen = [0.0] * len(spans), [False] * len(spans)
    for m in launches.modules(profile):
        if m.launch is None:
            continue
        k = bisect.bisect_right(starts, m.launch) - 1
        if k < 0 or m.launch >= spans[k].end:
            continue
        seen[k] = True
        i = max(0, bisect.bisect_left(op_starts, m.start) - 1)
        while i < len(ops) and ops[i][0] < m.end:
            s, e = ops[i]
            spent[k] += max(0.0, min(e, m.end) - max(s, m.start))
            i += 1
    return [t / 1e9 for t in spent], seen


def read(run):
    profile = hostspans.load_current()
    if profile is None or run["peak"] is None:
        return None
    import jax.numpy as jnp
    eng = run["traffic"]["engine"]
    geometry = kv_geometry(run["cfg"], jnp.dtype(eng["dtype"]).itemsize)
    if geometry is None:
        return None
    layers, row_bytes, q_width = geometry
    spans = sorted((s for s in hostspans.host_spans(profile)
                    if s.name == "serving.decode"
                    and s.stats.get("pages_live")), key=lambda s: s.start)
    spent, seen = kernel_seconds(profile, spans)
    counted = [(s, t) for s, t, ok in zip(spans, spent, seen) if ok]
    busy = sum(t for _, t in counted)
    if busy <= 0.0:
        return None
    positions = layers * eng["page_size"] * sum(
        int(s.stats["pages_live"]) for s, _ in counted)
    least = max(positions * row_bytes / run["peak"].hbm_bytes_s,
                4.0 * q_width * positions / run["peak"].bf16_flops)
    return 100.0 * least / (busy * run["chips"])
