"""A granitemoehybrid prefill's share of the bf16 peak over the traced
window: the FLOPs that prefilling the REAL prompt tokens needs
(``ssm_work.prefill_flops`` of each ``serving.prefill`` span's
``scan_tokens``: the matrices a token multiplies here, the recurrence's own
terms — not the chunked form's extra —, causal attention, the head once)
over the bf16 peak, over the time the device was busy inside those
``serving.prefill`` spans on the corrected clock.  Padding up to the bucket
and the chunked form's quadratic part are work the program adds, so they
lower this share.  A program whose prefill spans carry no ``scan_tokens``
gives nothing to read."""
from benchmark import hostspans, ssm_work


def read(run):
    profile = hostspans.load_current()
    if profile is None or run["peak"] is None:
        return None
    if run["cfg"].get("family") != "granitemoehybrid":
        return None
    spans = [s for s in hostspans.host_spans(profile)
             if s.name == "serving.prefill" and "scan_tokens" in s.stats]
    busy = hostspans.busy_seconds_inside(profile, spans) if spans else None
    if not busy or sum(busy) <= 0.0:
        return None
    need = sum(ssm_work.prefill_flops(run["cfg"], int(s.stats["scan_tokens"]))
               for s in spans)
    return 100.0 * need / run["peak"].bf16_flops / (sum(busy) * run["chips"])
