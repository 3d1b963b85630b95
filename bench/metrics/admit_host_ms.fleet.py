"""Host time of admission (``stream.admit``: the fresh mark and the staged
row's pool write, one span per admitted stream) per window step; ms."""
from harness.spans import host_ms_per_step


def read(ctx):
    return host_ms_per_step(ctx, "stream.admit")
