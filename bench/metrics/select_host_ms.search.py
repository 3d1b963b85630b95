"""Host time of the search's selection (``search.select``: the
best-member ranking after each evaluation and the cull before each
refinement) per search job; ms."""
from harness.spans import host_ms_per_step


def read(ctx):
    return host_ms_per_step({"trace": ctx.get("trace"),
                             "steps": ctx.get("jobs")}, "search.select")
