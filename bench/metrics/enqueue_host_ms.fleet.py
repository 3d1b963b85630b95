"""Host time of the enqueue (``stream.enqueue``: the step's operands, its
cached refresh schedule and the pool step program's call) per window
step; ms."""
from harness.spans import host_ms_per_step


def read(ctx):
    return host_ms_per_step(ctx, "stream.enqueue")
