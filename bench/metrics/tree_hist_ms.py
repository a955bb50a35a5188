"""Device milliseconds per round in the tree_hist kernel: the kernels
(custom calls) inside the tree-fit programs, which hold no other."""

import fedkt_work


def read(ctx):
    s = ctx.trace.kernel_seconds(fedkt_work.TREE_FIT_PROGRAMS)
    n = ctx.window["attempted"]
    return s / n * 1e3 if s > 0 and n else None
