"""Device time of the refinement program (``refine_population``: an
epoch of truncated-BP SGD on every member) per search job, from the
trace; ms."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not ctx.get("jobs"):
        return None
    secs, n = tr.module_time("refine_population")
    return 1e3 * secs / ctx["jobs"] if n else None
