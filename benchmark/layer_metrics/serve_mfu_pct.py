"""Whole serving step's share of the chip's bf16 peak: analytic forward
FLOPs of every prompt and output token processed for tokens delivered in
the window over window x chips x peak."""


def read(run):
    if run["peak"] is None or not run.get("flops_done"):
        return None
    return 100.0 * run["flops_done"] / (
        run["window_s"] * run["chips"] * run["peak"].bf16_flops)
