"""Host time of staging (``stream.stage``: a submitted stream's payload
padded and uploaded once) per window step; ms."""
from harness.spans import host_ms_per_step


def read(ctx):
    return host_ms_per_step(ctx, "stream.stage")
