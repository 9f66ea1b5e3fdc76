"""Attention kernels' share of their roofline over the traced steps: the
least time the chip could take for the FLOPs and bytes that attention
NEEDS in those steps (from shapes, whatever kernel runs them; a recomputed
forward is not needed work) over the summed device time of the attention
kernels' events (every call, the recomputed ones too).

The events are the device's ``custom-call`` operations whose result has the
attention layout ``[batch * heads, seq, head_dim]`` — the Mosaic calls of
``ops/pallas/flash_attention.py`` (forward, dq, dkv), which the trace names
``jvp__.<n>`` and not by kernel.  A step that runs attention through XLA
has no such event, and this reader then reads nothing."""
from benchmark import flops


def attention_events(cfg, traffic, op_seconds):
    shape = "[%d,%d,%d]" % (traffic["batch"] * cfg["num_attention_heads"],
                            traffic["seq_len"], cfg["head_dim"])
    return {name: s for name, s in op_seconds.items()
            if " custom-call(" in name
            and shape in name.split(" custom-call(")[0]}


def read(run):
    trace, peak = run["trace"], run["peak"]
    if trace is None or peak is None or not run["steps"]:
        return None
    tr = run["traffic"]
    spent = sum(attention_events(run["cfg"], tr, trace["op_seconds"]).values())
    if spent <= 0.0:
        return None          # the step runs no attention kernel
    need_flops, need_bytes = flops.flash_step_work(
        run["cfg"], tr["batch"], tr["seq_len"])
    least = run["steps"] * max(need_flops / peak.bf16_flops,
                               need_bytes / peak.hbm_bytes_s)
    return 100.0 * least / spent
