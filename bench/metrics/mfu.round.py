"""The whole round's share of the chip's bf16 peak: all of the round's
counted work (every fit, prediction and vote count; bench/counts.py via
bench/fedkt_work.py) over the round time over the peak."""

import fedkt_work


def read(ctx):
    if ctx.peaks is None:
        return None
    n = ctx.window["attempted"]
    if not n:
        return None
    round_s = ctx.window["span_s"] / n
    return (100.0 * fedkt_work.round_work(ctx).flops / round_s
            / ctx.peaks["bf16_flops"])
