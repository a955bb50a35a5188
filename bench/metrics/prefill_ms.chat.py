"""Device milliseconds per call of the engine's bucketed prefill
program (every layer over a padded (batch, length) bucket)."""

PREFILL = r"jit_prefill"


def read(ctx):
    n = ctx.trace.module_calls(PREFILL)
    s = ctx.trace.module_seconds(PREFILL)
    return s / n * 1e3 if n and s > 0 else None
