"""Host time of retirement (``stream.retire``: the retiring stream's state
snapshot and the slot's release, one span per retiring stream) per
window step; ms."""
from harness.spans import host_ms_per_step


def read(ctx):
    return host_ms_per_step(ctx, "stream.retire")
