"""Seconds per round of the silo phase, as the program's session clocks
it (``meta["seconds"]["parties"]``): every silo's fit, vote and
students, and the coordinator's fold as updates land over TCP.  Each
update crosses the codec as host bytes before the clock stops."""


def read(ctx):
    xs = ctx.window.get("parties_s") or []
    return sum(xs) / len(xs) if xs else None
