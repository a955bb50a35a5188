"""tree_hist's share of its roofline: the least time of the round's
histogram work (bench/counts.py, at the true row counts, over the
chip's peaks) over the kernel's device time."""

import fedkt_work


def read(ctx):
    if ctx.peaks is None:
        return None
    s = ctx.trace.kernel_seconds(fedkt_work.TREE_FIT_PROGRAMS)
    n = ctx.window["attempted"]
    if s <= 0 or not n:
        return None
    least, _ = fedkt_work.hist_work(ctx).least_s(
        ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * n / s
