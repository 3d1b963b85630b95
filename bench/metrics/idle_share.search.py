"""Share of the traced window of search jobs in which no operation ran on
the device (the worst device); %."""


def read(ctx):
    tr = ctx.get("trace")
    return tr.idle_share() if tr is not None else None
