"""Device time of the evaluation program (``evaluate_population``:
features, dual ridge over the beta sweep, NRMSE and accuracy; twice a
job) per search job, from the trace; ms."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not ctx.get("jobs"):
        return None
    secs, n = tr.module_time("evaluate_population")
    return 1e3 * secs / ctx["jobs"] if n else None
