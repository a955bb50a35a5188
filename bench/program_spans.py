"""The program's own spans and counters (``fedkt.*``, the program's
``repro/obs.py``) read from the window's trace, on the clock the device
planes share.

A span's counters are its event's args (stats); each thread has a host
line of its own.  Per round (the ``round`` arg), the *last silo* is the
silo of the round's last ``fedkt.fold``, the one the round waited for.
Its turn splits into three stretches that end where the next begins:

  silo_labels_s    ``fedkt.silo`` start -> end of its ``fedkt.party_vote``
                   (teachers fitted and voted; the fits are dispatched
                   asynchronously, so their device time is the vote's
                   wait)
  silo_students_s  -> end of its ``fedkt.encode`` (students fitted,
                   update encoded)
  deliver_s        -> end of its ``fedkt.fold`` (send, ACK, decode,
                   queue, the students' votes folded)

then ``finalize_s``, the ``fedkt.finalize`` span (the last fold to the
final student ready on the device).  Each is a mean over the window's
complete rounds.  ``idle_serial_s`` is the device-idle time with no
``fedkt.silo`` open on any thread, from the first round's start to the
last round's end; ``teacher_rows`` and ``padded_rows`` sum the
``fedkt.pad`` spans inside a ``fedkt.teacher_fit``; ``span_s`` sums
each span's seconds by name, over every thread.  ``work`` sums the other
counters over the complete rounds: the silos a round expects
(``fedkt.round``'s ``silos``) against the folds it made, the teachers,
party queries, students and final queries, and the wire bytes sent
(``fedkt.send``) and decoded (``fedkt.decode``); ``last_silos`` tallies
the rounds by their last silo's ``learner`` and ``rows``.

Every idle gap is also named by program phase, for the
``[program_spans]`` line: it is split equally among the threads that
have a ``fedkt.*`` span open at its midpoint, and each share goes to
that thread's innermost span.  A thread whose innermost span is
``fedkt.round`` is only waiting for arrivals, and takes a share only
when no other thread has a span open; time with no span open goes
under ``outside``.

A trace with no ``fedkt.*`` span (a program without them) reads None.
"""
from __future__ import annotations

import bisect
import collections
import functools
from dataclasses import dataclass, field

import common as C
import trace_reduce

PREFIX = "fedkt."


@dataclass
class Span:
    name: str
    start: float
    end: float
    line: int                      # one host line per thread
    args: dict = field(default_factory=dict)


@dataclass
class Reading:
    rounds: int
    silo_labels_s: float
    silo_students_s: float
    deliver_s: float
    finalize_s: float
    idle_s: float                  # device idle, first round to last
    idle_serial_s: float           # ... with no fedkt.silo open
    teacher_rows: int
    padded_rows: int
    idle_by_phase: dict            # phase -> idle seconds (all rounds)
    span_s: dict                   # span name -> its summed seconds
    work: dict                     # counter -> its sum (complete rounds)
    last_silos: dict               # "learner/rows" -> rounds it was last

    def teacher_rows_util(self):
        return (100.0 * self.teacher_rows / self.padded_rows
                if self.padded_rows else None)

    def idle_serial_pct(self, span_s):
        return 100.0 * self.idle_serial_s / span_s if span_s > 0 else None


def program_spans(pd):
    """Every ``fedkt.*`` event of the host planes, in time order."""
    spans, n = [], 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append(Span(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns, n,
                                      dict(ev.stats)))
            n += 1
    return sorted(spans, key=lambda s: (s.start, -s.end))


def device_intervals(pd, device_ids):
    """Per chip, the (start, end) of every op on its device plane."""
    out = []
    for plane in trace_reduce._device_planes(pd, set(device_ids)).values():
        lines = {line.name: list(line.events) for line in plane.lines}
        evs = lines.get("XLA Ops") or lines.get("XLA Modules", [])
        out.append([(e.start_ns, e.start_ns + e.duration_ns) for e in evs])
    return out


def _covered(a, b, merged, starts):
    """Length of [a, b] covered by the disjoint sorted ``merged``."""
    i = max(0, bisect.bisect_right(starts, a) - 1)
    total = 0.0
    while i < len(merged) and merged[i][0] < b:
        total += max(0.0, min(b, merged[i][1]) - max(a, merged[i][0]))
        i += 1
    return total


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(spans):
    """One thread's spans as disjoint (start, end, name) segments: at
    each moment, its innermost open span."""
    segs, stack, cur = [], [], None

    def emit(upto):
        if stack and upto > cur:
            segs.append((cur, upto, stack[-1].name))

    for sp in spans:                         # by start, outer first
        while stack and stack[-1].end <= sp.start:
            emit(stack[-1].end)
            cur = stack.pop().end
        if stack:
            emit(sp.start)
        cur = sp.start
        stack.append(sp)
    while stack:
        emit(stack[-1].end)
        cur = stack.pop().end
    return segs


def _phase_of_gaps(gap_list, spans):
    """Idle seconds by phase, the midpoint rule of the module doc."""
    by_line = collections.defaultdict(list)
    for sp in spans:
        by_line[sp.line].append(sp)
    lines = []
    for line_spans in by_line.values():
        segs = _innermost(line_spans)
        lines.append(([s for s, _, _ in segs], segs))
    phase = collections.Counter()
    for a, b in gap_list:
        mid = (a + b) / 2
        open_ = []
        for starts, segs in lines:
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and segs[i][1] >= mid:
                open_.append(segs[i][2])
        takers = [n for n in open_ if n != "fedkt.round"] or open_
        if not takers:
            phase["outside"] += (b - a) * 1e-9
        for n in takers:
            phase[n] += (b - a) * 1e-9 / len(takers)
    return phase


def _rounds(spans):
    """Per complete round: its id, its last silo's ``fedkt.silo`` and
    its (labels, students, deliver, finalize) seconds."""
    by_round = collections.defaultdict(list)
    for sp in spans:
        if "round" in sp.args:
            by_round[sp.args["round"]].append(sp)
    out = []
    for rid in sorted(by_round):
        rs = by_round[rid]

        def one(name, silo=None):
            found = [s for s in rs if s.name == name and
                     (silo is None or s.args.get("silo") == silo)]
            return found[-1] if found else None

        folds = [s for s in rs if s.name == "fedkt.fold"]
        fin = one("fedkt.finalize")
        if not folds or fin is None or one("fedkt.round") is None:
            continue
        last = max(folds, key=lambda s: s.end)
        silo = last.args.get("silo")
        turn, vote, enc = (one(n, silo) for n in
                           ("fedkt.silo", "fedkt.party_vote",
                            "fedkt.encode"))
        if None in (turn, vote, enc):
            continue
        out.append((rid, turn, ((vote.end - turn.start) * 1e-9,
                                (enc.end - vote.end) * 1e-9,
                                (last.end - enc.end) * 1e-9,
                                (fin.end - fin.start) * 1e-9)))
    return out


# (span, its counter) -> the name its sum goes under in ``work``
WORK = {("fedkt.round", "silos"): "silos",
        ("fedkt.teacher_fit", "teachers"): "teachers",
        ("fedkt.party_vote", "queries"): "party_queries",
        ("fedkt.student_fit", "students"): "students",
        ("fedkt.finalize", "queries"): "final_queries",
        ("fedkt.send", "bytes"): "bytes_sent",
        ("fedkt.decode", "bytes"): "bytes_decoded"}


def _work(spans, complete):
    out = collections.Counter()
    for sp in spans:
        if sp.args.get("round") not in complete:
            continue
        if sp.name == "fedkt.fold":
            out["folds"] += 1
        for (name, arg), key in WORK.items():
            if sp.name == name and arg in sp.args:
                out[key] += int(sp.args[arg])
    return dict(out)


def _pad_rows(spans):
    fits = collections.defaultdict(list)
    for sp in spans:
        if sp.name == "fedkt.teacher_fit":
            fits[sp.line].append(sp)
    rows = padded = 0
    for sp in spans:
        if sp.name == "fedkt.pad" and any(
                f.start <= sp.start and sp.end <= f.end
                for f in fits[sp.line]):
            rows += int(sp.args["rows"])
            padded += int(sp.args["padded_rows"])
    return rows, padded


def reduce_profile(pd, device_ids):
    """The reading of a parsed trace, or None without program spans or
    a complete round."""
    spans = program_spans(pd)
    per_round = _rounds(spans)
    if not per_round:
        return None
    rounds = [s for s in spans if s.name == "fedkt.round"]
    lo, hi = min(s.start for s in rounds), max(s.end for s in rounds)
    silos = _merge([(s.start, s.end) for s in spans
                    if s.name == "fedkt.silo"])
    silo_starts = [s for s, _ in silos]
    chips = device_intervals(pd, device_ids)
    idle = serial = 0.0
    phase = collections.Counter()
    for ivals in chips:
        gap_list = trace_reduce.gaps(ivals, lo, hi)
        for a, b in gap_list:
            idle += (b - a) * 1e-9
            serial += ((b - a) - _covered(a, b, silos, silo_starts)) * 1e-9
        phase.update(_phase_of_gaps(gap_list, spans))
    k = max(1, len(chips))
    rows, padded = _pad_rows(spans)
    n = len(per_round)
    mean = [sum(col) / n for col in zip(*(st for _, _, st in per_round))]
    last = collections.Counter(
        f"{t.args.get('learner')}/{t.args.get('rows')}"
        for _, t, _ in per_round)
    return Reading(rounds=n, silo_labels_s=mean[0], silo_students_s=mean[1],
                   deliver_s=mean[2], finalize_s=mean[3], idle_s=idle / k,
                   idle_serial_s=serial / k, teacher_rows=rows,
                   padded_rows=padded,
                   idle_by_phase={p: s / k for p, s in phase.items()},
                   span_s=_span_seconds(spans),
                   work=_work(spans, {rid for rid, _, _ in per_round}),
                   last_silos=dict(last))


def _span_seconds(spans):
    out = collections.Counter()
    for sp in spans:
        out[sp.name] += (sp.end - sp.start) * 1e-9
    return dict(out)


@functools.lru_cache(maxsize=1)      # one trace per run
def _read_file(path, mtime_ns, device_ids):
    del mtime_ns                        # part of the key: a new trace
    from jax.profiler import ProfileData
    r = reduce_profile(ProfileData.from_file(path), device_ids)
    if r is not None:
        idle = sum(r.idle_by_phase.values())
        covered = 1.0 - r.idle_by_phase.get("outside", 0.0) / idle \
            if idle > 0 else None
        C.log("program_spans", rounds=r.rounds,
              idle_s_per_round={p: s / r.rounds for p, s in sorted(
                  r.idle_by_phase.items(), key=lambda kv: -kv[1])},
              idle_covered=covered,
              idle_serial_s_per_round=r.idle_serial_s / r.rounds,
              silo_labels_s=r.silo_labels_s,
              silo_students_s=r.silo_students_s, deliver_s=r.deliver_s,
              finalize_s=r.finalize_s,
              teacher_rows=r.teacher_rows, padded_rows=r.padded_rows,
              span_s_per_round={n: v / r.rounds for n, v in sorted(
                  r.span_s.items(), key=lambda kv: -kv[1])},
              work_per_round={n: v / r.rounds
                              for n, v in sorted(r.work.items())},
              last_silos=r.last_silos)
    return r


def reading(ctx):
    """The reading of the window's trace of this run (under
    ``bench/.trace/<cell>`` while the readers run), parsed once."""
    files = sorted((C.BENCH / ".trace" / ctx.cell).rglob("*.xplane.pb"))
    if not files:
        return None
    f = files[-1]
    return _read_file(str(f), f.stat().st_mtime_ns,
                      tuple(range(ctx.workload["chips"])))
