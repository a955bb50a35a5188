"""The program's spans and counters (repro/obs.py) on a FedKT round: a
3-silo socket round traced on the CPU carries every span of the round
with its round and silo, its padding counters, and the same answers as
the round run without the profiler."""
import threading
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FedKTConfig
from repro.core.learners import (GBDTLearner, NNLearner, RFLearner,
                                 _pow2_bucket, shared_bucket)
from repro.core.partition import subsets_of_partition
from repro.data.synthetic import tabular_binary
from repro.federation import FedKTSession, PartyBinding
from repro.federation.engines import VmapEngine
from repro.models.smallnets import MLP

SPANS = ("fedkt.round", "fedkt.silo", "fedkt.teacher_fit",
         "fedkt.party_vote", "fedkt.pad", "fedkt.student_fit",
         "fedkt.encode", "fedkt.send", "fedkt.decode", "fedkt.fold",
         "fedkt.finalize")
CFG = dict(num_parties=3, num_partitions=2, num_subsets=3, num_classes=2,
           privacy_level="L2", gamma=0.1, seed=5)


class RecordingEngine(VmapEngine):
    """The vmap engine, keeping the party labels it returns (one
    engine per silo, so the labels are that silo's)."""

    def __init__(self):
        self.labels = []

    def label_queries(self, *a, **kw):
        labels, gap = super().label_queries(*a, **kw)
        self.labels.append(np.asarray(labels))
        return labels, gap


def _session(data):
    learners = [RFLearner(num_classes=2, num_trees=3, depth=2),
                GBDTLearner(num_rounds=3, depth=2),
                NNLearner(MLP(14, 2, hidden=8), num_classes=2, steps=20)]
    bindings = [PartyBinding(lrn, engine=RecordingEngine())
                for lrn in learners]
    return FedKTSession(bindings, data, FedKTConfig(**CFG),
                        final_learner=learners[2], transport="socket",
                        party_indices=_split(data))


def _split(data):
    # uneven silos, so the teachers of one silo pad to another bucket
    n = len(data["y_train"])
    return [np.arange(0, 60), np.arange(60, 200), np.arange(200, n)]


def _answers(session, res):
    (dom,) = res.by_domain.values()
    return {"party_labels": [p.engine.labels for p in session.parties],
            "students": res.student_states,
            "server_labels": dom["labels"],
            "final": res.final_state, "epsilon": res.epsilon}


def _spans(trace_dir):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(
        str(sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("fedkt."):
                    out.append({"name": ev.name, "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                "line": (plane.name, k),
                                "args": dict(ev.stats)})
    return out


@pytest.fixture(scope="module")
def rounds(tmp_path_factory):
    """The same round twice: under the profiler, and without it."""
    data = tabular_binary(n=480, seed=1)
    tdir = tmp_path_factory.mktemp("trace")
    traced = _session(data)
    jax.profiler.start_trace(str(tdir))
    try:
        res = traced.run()
    finally:
        jax.profiler.stop_trace()
    plain = _session(data)
    res_plain = plain.run()
    return {"data": data, "traced": (traced, res), "spans": _spans(tdir),
            "plain": (plain, res_plain)}


def test_round_carries_every_span(rounds):
    spans = rounds["spans"]
    assert {s["name"] for s in spans} == set(SPANS)
    (rnd,) = [s for s in spans if s["name"] == "fedkt.round"]
    rid = rnd["args"]["round"]
    assert rnd["args"]["silos"] == 3
    # one round id on every span, whatever thread opened it
    assert {s["args"].get("round") for s in spans} == {rid}
    silos = [s for s in spans if s["name"] == "fedkt.silo"]
    assert sorted(s["args"]["silo"] for s in silos) == [0, 1, 2]
    assert [s["args"]["learner"] for s in
            sorted(silos, key=lambda s: s["args"]["silo"])] \
        == ["rf", "gbdt", "nn"]
    sizes = [len(ix) for ix in _split(rounds["data"])]
    assert {s["args"]["silo"]: s["args"]["rows"] for s in silos} \
        == dict(enumerate(sizes))
    # a silo's phases sit inside its turn, on its thread
    for name in ("fedkt.teacher_fit", "fedkt.party_vote",
                 "fedkt.student_fit", "fedkt.encode", "fedkt.send"):
        phase = [s for s in spans if s["name"] == name]
        assert sorted(s["args"]["silo"] for s in phase) == [0, 1, 2]
        for s in phase:
            (turn,) = [t for t in silos
                       if t["args"]["silo"] == s["args"]["silo"]]
            assert s["line"] == turn["line"]
            assert turn["start"] <= s["start"] <= s["end"] <= turn["end"]
    folds = [s for s in spans if s["name"] == "fedkt.fold"]
    assert sorted(s["args"]["silo"] for s in folds) == [0, 1, 2]
    (fin,) = [s for s in spans if s["name"] == "fedkt.finalize"]
    assert fin["start"] >= max(s["end"] for s in folds)
    assert fin["line"] == rnd["line"]
    decodes = [s for s in spans if s["name"] == "fedkt.decode"]
    sends = [s for s in spans if s["name"] == "fedkt.send"]
    assert sorted(s["args"]["bytes"] for s in decodes) \
        == sorted(s["args"]["bytes"] for s in sends)
    _, res = rounds["traced"]
    assert sorted(s["args"]["bytes"] for s in sends) \
        == sorted(res.meta["socket"]["framed_bytes"].values())


def test_pad_counters_read_the_shared_bucket(rounds):
    spans, data = rounds["spans"], rounds["data"]
    s, t = CFG["num_partitions"], CFG["num_subsets"]
    fits = [x for x in spans if x["name"] == "fedkt.teacher_fit"]
    pads = [x for x in spans if x["name"] == "fedkt.pad"]
    for i, ix in enumerate(_split(data)):
        plan = subsets_of_partition(ix, s, t, seed=CFG["seed"] + 17 * i)
        subs = [sub for part in plan for sub in part]
        (fit,) = [f for f in fits if f["args"]["silo"] == i]
        assert fit["args"]["teachers"] == s * t
        (pad,) = [p for p in pads if p["line"] == fit["line"]
                  and fit["start"] <= p["start"] <= p["end"] <= fit["end"]]
        assert pad["args"]["rows"] == sum(len(x) for x in subs)
        assert pad["args"]["padded_rows"] == len(subs) * shared_bucket(subs)
    # every silo's students: s members on the queries, stacked
    nq = len(data["X_public"])
    students = [p for p in pads if p["args"].get("silo") is not None
                and not any(f["line"] == p["line"] and f["start"]
                            <= p["start"] <= f["end"] for f in fits)]
    assert len(students) == 3
    for p in students:
        assert p["args"]["rows"] == s * nq
        assert p["args"]["padded_rows"] == \
            s * shared_bucket([data["X_public"]] * s)
    # the final student's serial fit counts its own padding
    (final,) = [p for p in pads if p["args"].get("silo") is None]
    assert (final["args"]["rows"], final["args"]["padded_rows"]) \
        == (nq, _pow2_bucket(nq))


def test_round_answers_same_with_profiler_on_and_off(rounds):
    a = _answers(*rounds["traced"])
    b = _answers(*rounds["plain"])
    for la, lb in zip(a["party_labels"], b["party_labels"]):
        assert len(la) == len(lb) == CFG["num_partitions"]
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(x, y)
    for key in ("students", "server_labels", "final"):
        la, lb = jax.tree.leaves(a[key]), jax.tree.leaves(b[key])
        assert len(la) == len(lb) > 0
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert a["epsilon"] == b["epsilon"]


def test_server_clock_covers_the_blocked_finalize(rounds):
    spans = rounds["spans"]
    _, res = rounds["traced"]
    (fin,) = [s for s in spans if s["name"] == "fedkt.finalize"]
    secs = res.meta["seconds"]
    assert set(secs) == {"parties", "server"}
    assert secs["server"] >= round((fin["end"] - fin["start"]) * 1e-9, 3) \
        - 1e-3
    assert secs["parties"] > 0


def test_shared_bucket_is_the_largest_members_pow2():
    def rows(*ns):
        return [np.empty((n, 2)) for n in ns]
    assert shared_bucket(rows(1341, 1342, 449)) == 2048
    assert shared_bucket(rows(4615, 4616)) == 8192
    assert shared_bucket(rows(3, 5)) == 32           # the floor
    assert shared_bucket(rows(64)) == 64 == _pow2_bucket(64)


def test_round_and_silo_reach_worker_threads():
    seen = {}

    def work(tag):
        seen[tag] = (obs._round.get(), obs._silo.get(),
                     threading.current_thread().name)

    assert obs._round.get() is None
    with obs.round_scope(silos=2):
        rid = obs._round.get()
        with ThreadPoolExecutor(2) as ex:
            ex.submit(obs.carry(work), "carried").result()
            ex.submit(work, "bare").result()
        with obs.silo_scope(4):
            t = threading.Thread(target=obs.carry(work), args=("silo",))
            t.start()
            t.join()
        assert obs._silo.get() is None
    assert obs._round.get() is None
    assert seen["carried"][:2] == (rid, None)
    assert seen["bare"][:2] == (None, None)
    assert seen["silo"][:2] == (rid, 4)
    with obs.round_scope():
        assert obs._round.get() > rid            # ids only grow
