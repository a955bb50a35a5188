"""The whole serving step's share of the chip's bf16 peak over the
window: model operations of every prompt admitted and every output
token emitted in it (bench/counts.py, attention at each token's true
context), over the window, over the peak."""

import counts


def read(ctx):
    if ctx.peaks is None:
        return None
    c, win = ctx.config, ctx.window
    flops = sum(counts.prompt_flops(c, n) for n in win["window_prompts"])
    flops += sum(counts.token_flops(c, pos) for pos in win["window_positions"])
    return 100.0 * flops / win["window_s"] / ctx.peaks["bf16_flops"]
