"""Seconds per round from the start of the last silo's turn to the end
of its party vote: its teachers fitted (dispatched, so their device
time is the vote's wait) and their labels and vote gaps on the host.
The last silo is the one whose update the round folded last
(bench/program_spans.py)."""

import program_spans


def read(ctx):
    r = program_spans.reading(ctx)
    return None if r is None else r.silo_labels_s
