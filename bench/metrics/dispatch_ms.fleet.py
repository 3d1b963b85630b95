"""Mean host time of one StreamServer.step up to its enqueue (admission,
staging, retirement snapshots, the dispatch), by the server's own clock
(``dispatch_times_s``), over the window's steps; ms."""


def read(ctx):
    d = ctx.get("dispatch_s")
    return 1e3 * sum(d) / len(d) if d else None
