"""How unevenly a block pass's rows fall on the experts: per
``serving.decode`` span of the traced window that ran blocks
(``block_rows``), ``expert_tokens_max`` (the heaviest expert's rows, worst
layer) of its ``serving.experts`` marker over the mean load of an expert in
a layer (``rows x experts a token / experts``, ``rows`` = slots x block
length as the program routed them); the median over those passes.  1 would
be a perfectly even pass; the heaviest expert's rows are the longest group
of the grouped product.  Nothing to read where the decode spans carry no
``block_rows``."""
from benchmark import hostspans, stats


def read(run):
    profile = hostspans.load_current()
    cfg = run["cfg"]
    if profile is None or "num_experts" not in cfg:
        return None
    ratios = []
    for s in hostspans.host_spans(profile):
        if s.name != "serving.decode" or "block_rows" not in s.stats:
            continue
        marks = [c for c in s.descendants() if c.name == "serving.experts"]
        if not marks:
            continue
        mark = marks[-1].stats
        mean = (int(mark["rows"]) * cfg["num_experts_per_tok"]
                / cfg["num_experts"])
        ratios.append(int(mark["expert_tokens_max"]) / mean)
    return stats.median(ratios) if ratios else None
