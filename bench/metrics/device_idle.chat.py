"""Share of the window with no operation on the device: one minus the
union of op intervals in the trace over the window's length."""


def read(ctx):
    if ctx.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.window["span_s"])
