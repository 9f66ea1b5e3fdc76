"""How unevenly a decode step's tokens fall on the experts HELD here: per
``serving.decode`` span of the traced window, ``expert_tokens_max`` (the
heaviest held expert's tokens, worst layer) of its ``serving.experts``
marker over the mean load of an expert in a layer (``rows x experts a
token / the router's width``: the router chooses among all experts, held
here or not); the median over those steps.  1 would be a perfectly even
step; the heaviest expert's rows are the longest group of the grouped
product.  Nothing to read where the configuration holds no share of a
wider router or the program has no such marker."""
from benchmark import hostspans, stats


def read(run):
    profile = hostspans.load_current()
    cfg = run["cfg"]
    if profile is None or "num_local_experts" not in cfg:
        return None
    width = cfg.get("published", {}).get("num_local_experts",
                                         cfg["num_local_experts"])
    ratios = []
    for s in hostspans.host_spans(profile):
        if s.name != "serving.decode":
            continue
        marks = [c for c in s.descendants() if c.name == "serving.experts"]
        if not marks:
            continue
        mark = marks[-1].stats
        mean = int(mark["rows"]) * cfg["num_experts_per_tok"] / width
        ratios.append(int(mark["expert_tokens_max"]) / mean)
    return stats.median(ratios) if ratios else None
