"""How unevenly a decode step's tokens fall on the experts: per
``serving.decode`` span of the traced window, ``expert_tokens_max`` (the
heaviest expert's tokens, worst layer) of its ``serving.experts`` marker
over the mean load of an expert in a layer (``rows x experts a token /
experts``); the median over those steps.  1 would be a perfectly even
step; the heaviest expert's rows are the longest group of the grouped
product.  Nothing to read where the program has no such marker."""
from benchmark import hostspans, stats


def read(run):
    profile = hostspans.load_current()
    cfg = run["cfg"]
    if profile is None or "n_routed_experts" not in cfg:
        return None
    ratios = []
    for s in hostspans.host_spans(profile):
        if s.name != "serving.experts" or s.parent is None:
            continue
        if not any(p.name == "serving.decode" for p in _ancestors(s)):
            continue
        mean = (int(s.stats["rows"]) * cfg["num_experts_per_tok"]
                / cfg["n_routed_experts"])
        ratios.append(int(s.stats["expert_tokens_max"]) / mean)
    return stats.median(ratios) if ratios else None


def _ancestors(span):
    while span.parent is not None:
        span = span.parent
        yield span
