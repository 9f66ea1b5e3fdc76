"""``paged_decode``'s share of its roofline over the traced window: the
least time the chip could take to read the K and V rows that the window's
decode steps NEED (``flops.paged_decode_work`` over the ``pages_live`` of
every ``serving.decode`` span x the page size: the pages a step's attention
has to read, from the host's lengths) over the summed device time of the
operations named ``paged_decode``.  Bound by bytes at every size (1 FLOP
a byte in bf16).  A step that decodes through XLA has no such operation,
and this reader then reads nothing."""
from benchmark import flops, hostspans

KERNEL = "paged_decode"


def kernel_seconds(op_seconds):
    return sum(s for name, s in op_seconds.items()
               if KERNEL in name.split(" = ", 1)[0])


def read(run):
    trace, peak = run["trace"], run["peak"]
    if trace is None or peak is None:
        return None
    spent = kernel_seconds(trace["op_seconds"]) * run["chips"]
    profile = hostspans.load_current()
    if spent <= 0.0 or profile is None:
        return None
    pages = sum(int(s.stats.get("pages_live", 0))
                for s in hostspans.host_spans(profile)
                if s.name == "serving.decode")
    if not pages:
        return None
    import jax.numpy as jnp
    eng = run["traffic"]["engine"]
    need_flops, need_bytes = flops.paged_decode_work(
        run["cfg"], pages * eng["page_size"],
        jnp.dtype(eng["dtype"]).itemsize)
    least = max(need_flops / peak.bf16_flops, need_bytes / peak.hbm_bytes_s)
    return 100.0 * least / spent
