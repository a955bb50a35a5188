"""Seconds per round from the end of the last silo's party vote to the
end of its encode: its students fitted on the voted labels and its
update encoded for the wire (bench/program_spans.py)."""

import program_spans


def read(ctx):
    r = program_spans.reading(ctx)
    return None if r is None else r.silo_students_s
