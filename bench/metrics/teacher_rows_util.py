"""Share of the rows the teacher fits run over that are true rows: the
``rows`` over the ``padded_rows`` of the program's ``fedkt.pad`` spans
inside a ``fedkt.teacher_fit``, where each silo's teachers are padded
to one shared power-of-two bucket (bench/program_spans.py)."""

import program_spans


def read(ctx):
    r = program_spans.reading(ctx)
    return None if r is None else r.teacher_rows_util()
