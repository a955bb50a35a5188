"""The program-span reduction (bench/program_spans.py) on hand-made
planes: the last silo of a round, the stretches of its turn, the
padding counters, and device idle named by program phase."""
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
import common as C  # noqa: E402
import program_spans as P  # noqa: E402

NS = 1e-9


@dataclass
class Ev:
    name: str
    start_ns: int
    duration_ns: int
    stats: list = field(default_factory=list)


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


@dataclass
class Profile:
    planes: list


def ev(name, a, b, **args):
    return Ev(name, a, b - a, list(args.items()))


def _profile():
    """Round 0 complete (silo 1 folds first, silo 0 last), round 1 cut
    short.  Device ops leave five gaps: (100, 200), (600, 620),
    (680, 700), (740, 760) and (1050, 1080)."""
    main = Line("python", [
        ev("bench.round", 0, 1000),
        ev("fedkt.round", 0, 1000, round=0, silos=2),
        ev("fedkt.fold", 500, 520, round=0, silo=1),
        ev("fedkt.fold", 700, 720, round=0, silo=0),
        ev("fedkt.finalize", 730, 900, round=0, queries=8),
        ev("fedkt.round", 1100, 2000, round=1, silos=2)])
    silo0 = Line("python", [
        ev("fedkt.silo", 10, 688, round=0, silo=0, learner="rf", rows=9),
        ev("fedkt.teacher_fit", 20, 100, round=0, silo=0, teachers=2),
        ev("fedkt.pad", 30, 60, round=0, silo=0, rows=6, padded_rows=8),
        ev("fedkt.party_vote", 100, 300, round=0, silo=0, queries=8),
        ev("fedkt.student_fit", 300, 400, round=0, silo=0, students=1),
        ev("fedkt.pad", 310, 320, round=0, silo=0, rows=8, padded_rows=32),
        ev("fedkt.encode", 400, 450, round=0, silo=0),
        ev("fedkt.send", 450, 688, round=0, silo=0, bytes=99)])
    silo1 = Line("python", [
        ev("fedkt.silo", 10, 490, round=0, silo=1, learner="gbdt", rows=7),
        ev("fedkt.teacher_fit", 15, 50, round=0, silo=1, teachers=2),
        ev("fedkt.pad", 16, 40, round=0, silo=1, rows=5, padded_rows=8),
        ev("fedkt.party_vote", 50, 120, round=0, silo=1, queries=8),
        ev("fedkt.student_fit", 120, 400, round=0, silo=1, students=1),
        ev("fedkt.encode", 400, 480, round=0, silo=1),
        ev("fedkt.send", 480, 490, round=0, silo=1, bytes=77)])
    coord = Line("python", [ev("fedkt.decode", 490, 495, round=0,
                               bytes=77)])
    ops = [Ev("op", a, b - a) for a, b in
           [(0, 100), (200, 600), (620, 680), (700, 740), (760, 1050),
            (1080, 2000)]]
    dev = Plane("/device:TPU:0", [Line("XLA Ops", ops)])
    other = Plane("/device:TPU:1", [Line("XLA Ops", [Ev("x", 0, 5000)])])
    host = Plane("/host:CPU", [main, silo0, silo1, coord])
    return Profile([dev, other, host])


def test_last_silo_sets_the_round_stretches():
    r = P.reduce_profile(_profile(), [0])
    assert r.rounds == 1                       # round 1 has no fold
    # silo 0 folded last: its turn 10 -> vote 300 -> encode 450 ->
    # fold 720, not silo 1's (encode 480, fold 520)
    assert r.silo_labels_s == pytest.approx(290 * NS)
    assert r.silo_students_s == pytest.approx(150 * NS)
    assert r.deliver_s == pytest.approx(270 * NS)
    assert r.finalize_s == pytest.approx(170 * NS)


def test_idle_named_by_phase_split_across_threads():
    r = P.reduce_profile(_profile(), [0])
    assert r.idle_s == pytest.approx(190 * NS)
    assert r.idle_by_phase == pytest.approx({
        # (100, 200): silo 0 votes while silo 1 fits its students, so
        # the gap splits equally; the waiting round takes no share
        "fedkt.party_vote": 50 * NS, "fedkt.student_fit": 50 * NS,
        "fedkt.send": 20 * NS,         # (600, 620): silo 0 alone
        "fedkt.round": 20 * NS,        # (680, 700): only the round open
        "fedkt.finalize": 20 * NS,     # (740, 760)
        "outside": 30 * NS})           # (1050, 1080): between rounds
    # with no silo's turn open: 700 - 688, (740, 760), (1050, 1080)
    assert r.idle_serial_s == pytest.approx(62 * NS)
    assert r.idle_serial_pct(2000 * NS) == pytest.approx(3.1)


def test_teacher_rows_count_pads_inside_teacher_fits_only():
    r = P.reduce_profile(_profile(), [0])
    assert (r.teacher_rows, r.padded_rows) == (11, 16)
    assert r.teacher_rows_util() == pytest.approx(100 * 11 / 16)
    # both silos' send, on their own threads
    assert r.span_s["fedkt.send"] == pytest.approx((238 + 10) * NS)
    assert r.span_s["fedkt.pad"] == pytest.approx((30 + 10 + 24) * NS)


def test_other_counters_summed_over_complete_rounds():
    r = P.reduce_profile(_profile(), [0])
    # round 1 (cut short) adds nothing, not even its silos
    assert r.work == {"silos": 2, "folds": 2, "teachers": 4,
                      "party_queries": 16, "students": 2,
                      "final_queries": 8, "bytes_sent": 99 + 77,
                      "bytes_decoded": 77}
    assert r.last_silos == {"rf/9": 1}


def test_innermost_segments_of_one_thread():
    spans = P.program_spans(_profile())
    line = [s for s in spans if s.line == 1]
    segs = P._innermost(line)
    assert [(a, b, n) for a, b, n in segs[:4]] == [
        (10, 20, "fedkt.silo"), (20, 30, "fedkt.teacher_fit"),
        (30, 60, "fedkt.pad"), (60, 100, "fedkt.teacher_fit")]
    assert all(a < b for a, b, _ in segs)
    assert all(p[1] <= q[0] for p, q in zip(segs, segs[1:]))


def test_no_program_spans_reads_none():
    prof = _profile()
    host = prof.planes[2]
    for line in host.lines:
        line.events = [e for e in line.events
                       if not e.name.startswith("fedkt.")]
    assert P.reduce_profile(prof, [0]) is None
    # a run with no trace under bench/.trace/<cell> reads None too,
    # through every reader of these metrics
    ctx = SimpleNamespace(cell="no-such-cell",
                          workload={"chips": 1}, window={"span_s": 1.0})
    assert P.reading(ctx) is None
    for m in ("silo_labels_s", "silo_students_s", "deliver_s",
              "finalize_s", "idle_serial.round", "teacher_rows_util"):
        assert C.metric_reader(m).read(ctx) is None
