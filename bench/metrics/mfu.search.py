"""Whole search job's share of the chip's bf16 peak: the algorithm's
operations for the window's jobs (masking, the training kernel's forward,
truncated BP, the Gram, Cholesky factors, solves and predictions of each
evaluation; counted once, real time steps only,
``harness.search_costs.job``) over the window and the chips; %."""
from harness import costs


def read(ctx):
    if not ctx.get("search_ops"):
        return None
    peak = costs.peaks(ctx["device_kind"])["flops_bf16"]
    return 100.0 * ctx["search_ops"] / ctx["window_s"] / (ctx["chips"] * peak)
