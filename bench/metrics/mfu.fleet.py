"""Whole fleet step's share of the chip's bf16 peak: the algorithm's
operations for the samples served in the window (forward, readout,
truncated BP or the (A, B) fold, and each eligible refresh; counted once,
real time steps only, ``harness.costs.fleet_sample_ops``) over the window
and the chips; %."""
from harness import costs


def read(ctx):
    if not ctx.get("fleet_ops"):
        return None
    peak = costs.peaks(ctx["device_kind"])["flops_bf16"]
    return 100.0 * ctx["fleet_ops"] / ctx["window_s"] / (ctx["chips"] * peak)
