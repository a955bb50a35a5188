"""Share of the window the device sits idle while no silo's turn is
open on any thread: the round's serial path (session set-up, the
coordinator's start and stop, finalize) and the gaps between rounds
(bench/program_spans.py)."""

import program_spans


def read(ctx):
    r = program_spans.reading(ctx)
    return None if r is None else r.idle_serial_pct(ctx.window["span_s"])
