"""The whole prefill's share of the chip's bf16 peak: model operations
of every admitted prompt at its true length (bench/counts.py, the
output head at the one position read) over the prefill program's
device time."""

import counts

PREFILL = r"jit_prefill"


def read(ctx):
    if ctx.peaks is None:
        return None
    s = ctx.trace.module_seconds(PREFILL)
    lens = [len(p) for p, _ in ctx.window["admitted"]]
    if s <= 0 or not lens:
        return None
    flops = sum(counts.prompt_flops(ctx.config, n) for n in lens)
    return 100.0 * flops / s / ctx.peaks["bf16_flops"]
