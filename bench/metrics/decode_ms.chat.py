"""Device milliseconds per call of the engine's decode program (one
token for every slot)."""

DECODE = r"jit_decode"


def read(ctx):
    n = ctx.trace.module_calls(DECODE)
    s = ctx.trace.module_seconds(DECODE)
    return s / n * 1e3 if n and s > 0 else None
