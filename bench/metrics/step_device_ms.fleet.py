"""Device time of the pool step program (``_stream_step_pool_impl``) per
step, from the trace; ms."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    secs, n = tr.module_time("_stream_step_pool")
    return 1e3 * secs / n if n else None
