"""Tokens a forward of a model that generates by diffusion over blocks, over
the traced window: per ``serving.decode`` span, the blocks its pass commits
(``commits`` live slots, each storing ``block_length`` final positions) over
the slots it runs (``live``: one forward of a block each); summed over the
window.  As the family decodes — ``denoising_steps`` passes and one commit a
block — it reads ``block_length / (denoising_steps + 1)``: 0.8 for four
passes over blocks of four; a commit fused into the next block's first pass
would read 1.  The acceptance rate's twin.  A program whose decode spans
carry no ``commits`` (a next-token model, or the parent's) gives nothing to
read."""
from benchmark import hostspans


def read(run):
    profile = hostspans.load_current()
    if profile is None or "block_length" not in run["cfg"]:
        return None
    stored = forwards = 0
    for s in hostspans.host_spans(profile):
        if s.name == "serving.decode" and "commits" in s.stats:
            stored += int(s.stats["commits"]) * run["cfg"]["block_length"]
            forwards += int(s.stats["live"])
    return stored / forwards if forwards else None
