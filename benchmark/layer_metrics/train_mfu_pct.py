"""Whole step's share of the chip's bf16 peak: analytic model FLOPs per
token (forward + backward, recompute not counted) x tokens/s of the traced
window over chips x peak."""
from benchmark import flops


def read(run):
    if run["peak"] is None or not run["tokens"]:
        return None
    per_token = flops.train_flops_per_token(run["cfg"],
                                            run["traffic"]["seq_len"])
    rate = run["tokens"] / run["window_s"]
    return 100.0 * per_token * rate / (run["chips"] * run["peak"].bf16_flops)
