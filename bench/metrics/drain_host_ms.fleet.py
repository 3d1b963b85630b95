"""Host time of the drain (``stream.drain``: the blocking read of a step's
predictions and their per-sample bookkeeping) per window step; ms."""
from harness.spans import host_ms_per_step


def read(ctx):
    return host_ms_per_step(ctx, "stream.drain")
