"""Device milliseconds per round in the vote kernel (vote_aggregate),
the silos' noised ensemble votes."""


def read(ctx):
    s = ctx.trace.kernel_seconds(r"vote_aggregate")
    n = ctx.window["attempted"]
    return s / n * 1e3 if s > 0 and n else None
