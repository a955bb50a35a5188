"""The program's own spans and counters, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: it lands on its thread's
host line of the trace beside the device planes, its counters ride as
the event's args, and it costs about a microsecond when no trace is
running.  Span names carry the ``fedkt.`` prefix.

Every span opened inside ``round_scope`` carries that round's ``round``
id (one per ``FedKTSession.run``, drawn from a process-wide counter),
and inside ``silo_scope`` its ``silo``, so the spans of one round can
be tied together across the silo threads and the coordinator.  The ids
live in context variables; a thread pool carries them to its workers
when work is submitted through ``carry``.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools

import jax

_round_ids = itertools.count()
_round = contextvars.ContextVar("fedkt_round", default=None)
_silo = contextvars.ContextVar("fedkt_silo", default=None)


def span(name: str, *, silo=None, **counters):
    """A host span ``name`` with ``counters`` as its args, plus the
    round and silo in scope (``silo=`` names another, e.g. the silo
    whose update the coordinator folds)."""
    args = {}
    rid = _round.get()
    if rid is not None:
        args["round"] = rid
    silo = _silo.get() if silo is None else silo
    if silo is not None:
        args["silo"] = int(silo)
    args.update(counters)
    return jax.profiler.TraceAnnotation(name, **args)


@contextlib.contextmanager
def round_scope(**counters):
    """Draws the next round id and opens ``fedkt.round`` under it."""
    token = _round.set(next(_round_ids))
    try:
        with span("fedkt.round", **counters):
            yield
    finally:
        _round.reset(token)


@contextlib.contextmanager
def silo_scope(silo: int, **counters):
    """One silo's whole turn: ``fedkt.silo``, and ``silo`` on every
    span opened inside it."""
    token = _silo.set(int(silo))
    try:
        with span("fedkt.silo", **counters):
            yield
    finally:
        _silo.reset(token)


def carry(fn):
    """``fn`` bound to a copy of the caller's round and silo, for one
    piece of work on a worker thread: ``pool.submit(carry(fn), ...)``
    (a copy per submit, as a context runs on one thread at a time)."""
    return functools.partial(contextvars.copy_context().run, fn)
