"""Seconds per round of the coordinator's finalize: the noised vote
over the folded histogram and the final student's distillation, ending
when that student is ready on the device (bench/program_spans.py)."""

import program_spans


def read(ctx):
    r = program_spans.reading(ctx)
    return None if r is None else r.finalize_s
