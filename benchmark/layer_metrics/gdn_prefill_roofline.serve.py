"""A qwen3_next prefill's share of its roofline over the traced window.
Per ``serving.prefill`` span that carries ``scan_tokens`` (the REAL prompt
tokens), the least time the chip could take: the larger of its FLOPs
(``qwen3next_work.prefill_flops``: the matrices a token multiplies here,
the delta rule's own terms — not the chunked form's extra —, causal
attention, the head once) over the bf16 peak and its needed bytes
(``qwen3next_work.prefill_bytes``: every resident matrix and the head
once, every held expert once, the K/V rows and the state it writes) over
the HBM peak.  The metric is the sum of those times over the summed device
time of the programs those spans launched, each tied to its launch by
``run_id`` (``launches.device_seconds_launched``: the prefill and its
first token's sampler), so no clock offset enters.  Bucket padding and
the chunked form's extra lower the share; a span none of whose programs
the capture holds counts neither its work nor its time.  A program whose
prefill spans carry no ``scan_tokens``, or that annotates no launches,
gives nothing to read."""
from benchmark import hostspans, launches, qwen3next_work


def read(run):
    profile = hostspans.load_current()
    if profile is None or run["peak"] is None:
        return None
    cfg = run["cfg"]
    if cfg.get("family") != "qwen3_next":
        return None
    spans = [s for s in hostspans.host_spans(profile)
             if s.name == "serving.prefill" and "scan_tokens" in s.stats]
    if not spans:
        return None
    import jax.numpy as jnp
    itemsize = jnp.dtype(run["traffic"]["engine"]["dtype"]).itemsize
    peak = run["peak"]
    least = busy = 0.0
    for s, spent in zip(spans, launches.device_seconds_launched(profile,
                                                                spans)):
        if spent <= 0.0:
            continue
        tokens = int(s.stats["scan_tokens"])
        least += max(qwen3next_work.prefill_flops(cfg, tokens)
                     / peak.bf16_flops,
                     qwen3next_work.prefill_bytes(cfg, tokens, itemsize)
                     / peak.hbm_bytes_s)
        busy += spent
    if busy <= 0.0:
        return None
    return 100.0 * least / (busy * run["chips"])
