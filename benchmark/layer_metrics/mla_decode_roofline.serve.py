"""``mla_paged_decode``'s share of its roofline over the traced window: the
least time the chip could take to read the latent rows that the window's
decode steps NEED (``latent_work.mla_decode_work`` over the ``pages_live``
of every ``serving.decode`` span x the page size; a row's ``rank + rope``
values once, keys and values being the same row) over the summed device
time of the operations named ``mla_paged_decode``.  Bound by bytes (60
FLOPs a byte in bf16, the ridge is 240).  The pool stores a row padded to
whole lane tiles (576 -> 640), so a kernel that reads nothing but live
pages reads 10/9 of the bytes counted.  A step that decodes through XLA
has no such operation, and this reader then reads nothing."""
from benchmark import hostspans, latent_work

KERNEL = "mla_paged_decode"


def kernel_seconds(op_seconds):
    return sum(s for name, s in op_seconds.items()
               if KERNEL in name.split(" = ", 1)[0])


def read(run):
    trace, peak = run["trace"], run["peak"]
    if trace is None or peak is None or "kv_lora_rank" not in run["cfg"]:
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
    need_flops, need_bytes = latent_work.mla_decode_work(
        run["cfg"], pages * eng["page_size"],
        jnp.dtype(eng["dtype"]).itemsize)
    least = max(need_flops / peak.bf16_flops, need_bytes / peak.hbm_bytes_s)
    return 100.0 * least / spent
