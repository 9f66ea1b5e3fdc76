"""The flash kernel's share of the bf16 peak in the prefills of a model
with window layers, over the traced window: the attention FLOPs those
prefills NEED at each prompt's own length (``smallthinker_work
.attention_flops`` of each ``serving.prefill`` span's ``tokens``: ``4 H
d_h`` a (query, key) pair, every earlier key in a full layer, the window's
in a window layer) over the bf16 peak, over the device time of the flash
forward kernels that ran in the programs those spans launched.

A flash forward is a device ``custom-call`` whose result has the layout
``[H, s, d_h]`` (one prompt a prefill, so batch x heads is ``H``); the
programs are tied to the span that launched them by ``run_id``
(``launches.modules``), so no clock offset enters.  Padding up to the
bucket and the tiles the masks half use are work the kernel adds, so they
lower this share.  A program whose prefill spans carry no
``window_layers``, or whose prefills run no such kernel, gives nothing to
read."""
import re

from benchmark import hostspans, launches, smallthinker_work, xplane


def flash_seconds(profile, spans, heads, head_dim):
    """Device seconds of the flash forward kernels inside the programs
    launched within ``spans``."""
    shape = re.compile(r"\[%d,\d+,%d\]" % (heads, head_dim))
    plane = hostspans._device_plane(profile)
    if plane is None:
        return 0.0
    ops = sorted((s, e) for name, s, e in
                 xplane.device_events(profile).get(plane.name, [])
                 if " custom-call(" in name
                 and shape.search(name.split(" custom-call(")[0]))
    bounds = sorted((s.start, s.end) for s in spans)
    spent = 0.0
    for m in launches.modules(profile):
        if m.launch is None or not any(a <= m.launch < b
                                       for a, b in bounds):
            continue
        spent += sum(min(e, m.end) - max(s, m.start) for s, e in ops
                     if s < m.end and e > m.start)
    return spent / 1e9


def read(run):
    profile = hostspans.load_current()
    if profile is None or run["peak"] is None:
        return None
    cfg = run["cfg"]
    if cfg.get("family") != "smallthinker":
        return None
    spans = [s for s in hostspans.host_spans(profile)
             if s.name == "serving.prefill" and "window_layers" in s.stats]
    if not spans:
        return None
    spent = flash_seconds(profile, spans, cfg["num_attention_heads"],
                          cfg["head_dim"])
    if spent <= 0.0:
        return None
    need = sum(smallthinker_work.attention_flops(cfg, int(s.stats["tokens"]))
               for s in spans)
    return 100.0 * need / run["peak"].bf16_flops / (spent * run["chips"])
