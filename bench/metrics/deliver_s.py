"""Seconds per round from the end of the last silo's encode to the end
of its fold: send and ACK over TCP, the coordinator's decode, the
queue, and its students' votes folded into the running histogram
(bench/program_spans.py)."""

import program_spans


def read(ctx):
    r = program_spans.reading(ctx)
    return None if r is None else r.deliver_s
