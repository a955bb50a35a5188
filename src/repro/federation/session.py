"""FedKTSession: drives the paper's single communication round.

The session owns everything that spans the party/server boundary —
PRNG threading, the query-budget split, privacy accounting, and round
metrics — while Party/Server own their protocol sides, an Engine owns
teacher execution, and a Transport owns WHERE parties run and how their
one PartyUpdate message travels (serialized through the wire codec in
every mode).  One session == one round == one result:

    session = FedKTSession(learner, data, cfg, engine="vmap")
    result = session.run()        # RoundResult

    # heterogeneous silos: each party brings its OWN learner and engine
    # (a PartyBinding) — the vote layout is learner-agnostic integer
    # counts, so rf + gbdt + nn ensemble in one round
    from repro.federation.bindings import PartyBinding
    FedKTSession([PartyBinding(RFLearner(num_classes=2)),
                  PartyBinding(GBDTLearner(), engine="vmap"),
                  PartyBinding(nn_learner, engine="vmap")],
                 data, cfg, final_learner=nn_learner).run()

    # cross-process silos: each party's round in its own interpreter,
    # fanned out over ``parallelism`` workers
    FedKTSession(learner, data, cfg, transport="subprocess",
                 parallelism=4).run()

    # fleet scale: parties deliver over TCP, the server folds each
    # arriving update into ONE running vote histogram (constant memory
    # in the party count with retain_students=False), stragglers are
    # dropped at the deadline once ``min_parties`` arrived
    from repro.federation.net import SocketTransport
    FedKTSession(learner, data, cfg, retain_students=False,
                 transport=SocketTransport(parallelism=8, deadline_s=60,
                                           min_parties=90)).run()

Every transport's updates are folded through the SAME
``StreamingVoteAggregate`` — a transport with ``streams = True``
(socket) folds per arrival, the others fold the finished list — so the
batch and streaming servers cannot diverge.

Seed contract: with ``engine="loop"`` the session reproduces the legacy
``run_fedkt`` accuracy and epsilon bit-for-bit at a fixed cfg.seed, and
every transport reproduces the in-process result bit-for-bit — party
keys are precomputed from the serial schedule, so fan-out order never
changes any party's randomness, and the vote histogram is an integer
sum, so arrival order cannot change it either (test-enforced in
tests/test_federation.py, tests/test_transport.py, tests/test_net.py).
"""
from __future__ import annotations

import time
from typing import Any, Dict

import jax
import numpy as np

from repro import obs
from repro.configs.base import FedKTConfig
from repro.core.learners import accuracy
from repro.core.partition import dirichlet_partition
from repro.federation.bindings import resolve_bindings
from repro.federation.engines import get_engine
from repro.federation.messages import RoundResult
from repro.federation.party import Party
from repro.federation.server import Server
from repro.federation.transport import get_transport


def party_starting_keys(parties, seed: int):
    """Every party's starting key (the serial loop's exact split
    positions, played forward without training) plus the key the server
    side continues from.  Shared with launch/federate.py: a remote
    party derives ITS key from the same schedule, so a cross-host round
    reproduces the in-process one seed-for-seed."""
    key = jax.random.PRNGKey(seed)
    keys = []
    for party in parties:
        keys.append(key)
        key = party.advance_key(key)
    return keys, key


def query_budget(cfg: FedKTConfig, num_public: int):
    """(party, server) query counts.  The noised side of the protocol
    answers only a ``query_fraction`` of D_aux — the DP budget knob."""
    frac = max(1, int(num_public * cfg.query_fraction))
    tq_party = num_public if cfg.privacy_level != "L2" else frac
    tq_server = num_public if cfg.privacy_level != "L1" else frac
    return tq_party, tq_server


class FedKTSession:
    """One FedKT round over in-process array data.

    learner: a single Learner (the homogeneous shorthand — every party
        gets the same binding, exactly the pre-binding behavior) OR a
        sequence of ``bindings.PartyBinding``, one per party, for
        heterogeneous ensembles (each silo brings its own learner and
        engine; the (T, U) integer vote layout is the only cross-party
        contract, enforced at aggregation time).
    data: dict with X_train/y_train/X_public/X_test/y_test arrays.
    engine: "loop" | "vmap" | an engines.Engine instance — the default
        engine for bindings that don't name their own.
    final_learner: trains on the server's voted labels; defaults to the
        (first binding's) teacher learner.
    transport: "inprocess" | "thread" | "subprocess" | "socket" | a
        transport.Transport instance — where the party rounds run and
        how their updates cross the party/server boundary.  Pass a
        ``net.SocketTransport(...)`` instance to set the fleet knobs
        (deadline_s, min_parties, backoff).
    parallelism: worker count for the fan-out transports (defaults to
        one worker per party — the socket transport caps at 8; must be
        omitted when passing a transport instance).
    retain_students: keep every party's student states in the
        RoundResult (the default, and the historical behavior).  False
        drops each update after it is folded into the running vote
        aggregate — constant server memory in the party count, the
        fleet-scale mode.
    """

    def __init__(self, learner, data: Dict[str, np.ndarray],
                 cfg: FedKTConfig, *, student_learner=None,
                 final_learner=None, engine="loop", party_indices=None,
                 transport="inprocess", parallelism=None,
                 retain_students=True):
        self.bindings, self.final_learner = resolve_bindings(
            learner, student_learner=student_learner, engine=engine,
            num_parties=cfg.num_parties, final_learner=final_learner)
        # the homogeneous shorthand's session-wide fields (every
        # binding is the same one there); heterogeneous sessions should
        # read self.bindings instead
        self.learner = self.bindings[0].learner
        self.student_learner = self.bindings[0].student_learner
        self.data = data
        self.cfg = cfg
        self.engine = get_engine(engine)
        self.transport = get_transport(transport, parallelism)
        self.retain_students = retain_students

        ytr = data["y_train"]
        if party_indices is None:
            party_indices = dirichlet_partition(ytr, cfg.num_parties,
                                                cfg.beta, cfg.seed)
        self.parties = [
            Party(party_id=i, X=data["X_train"], y=ytr, indices=ix,
                  cfg=cfg, learner=b.learner,
                  student_learner=b.student_learner, engine=b.engine)
            for i, (ix, b) in enumerate(zip(party_indices,
                                            self.bindings))]
        self.server = Server(cfg, self.student_learner,
                             self.final_learner,
                             bindings=dict(enumerate(self.bindings)))
        self.tq_party, self.tq_server = query_budget(cfg,
                                                     len(data["X_public"]))

    def run(self, verbose: bool = False) -> RoundResult:
        with obs.round_scope(silos=len(self.parties)):
            return self._run(verbose)

    def _run(self, verbose: bool) -> RoundResult:
        cfg = self.cfg
        Xpub = self.data["X_public"]
        party_keys, key = party_starting_keys(self.parties, cfg.seed)
        agg = self.server.make_aggregate(
            Xpub, self.tq_server, self.engine,
            retain_students=self.retain_students)
        streaming = getattr(self.transport, "streams", False)

        def fold(upd):
            with obs.span("fedkt.fold", silo=upd.party_id):
                agg.add(upd)
            if verbose:
                print(f"party {upd.party_id}: {upd.num_examples} "
                      f"examples, {upd.meta['num_teachers']} teachers "
                      f"trained, {upd.meta['encoded_bytes']} wire bytes")

        t0 = time.perf_counter()
        # engine=None: every party runs under its OWN bound engine (the
        # heterogeneous contract; in the homogeneous shorthand all
        # bindings share the session engine, so nothing changes)
        if streaming:
            # the server folds each update the moment it arrives; party
            # training and aggregation overlap, so "parties" time IS the
            # whole collect-and-fold phase
            for upd in self.transport.stream_round(
                    self.parties, party_keys, Xpub, self.tq_party,
                    None):
                fold(upd)
            t_parties = time.perf_counter() - t0
            t0 = time.perf_counter()
        else:
            updates = self.transport.run_round(
                self.parties, party_keys, Xpub, self.tq_party, None)
            t_parties = time.perf_counter() - t0
            t0 = time.perf_counter()
            for upd in updates:
                fold(upd)
        # returns once the final student is ready on the device
        final_state, vote, votes, key = self.server.finalize_all(key, agg)
        t_server = time.perf_counter() - t0

        acc = accuracy(self.final_learner, final_state,
                       self.data["X_test"], self.data["y_test"])
        # per-domain breakdown: one VoteResult + one epsilon fold per
        # vote domain (a legacy round has exactly one entry, and the
        # top-level fields are that entry's)
        by_domain: Dict[str, Dict[str, Any]] = {}
        for dom in agg.domains():
            v = votes[dom.ident]
            by_domain[dom.ident] = {
                "domain": dom,
                "vote": v,
                "labels": np.asarray(v.labels),
                "epsilon": agg.epsilon(v),
                "parties": agg.domain_parties(dom),
                "student_states": agg.student_states_for(dom),
            }
        # session-level bound: privacy composes across domains by max —
        # each domain's fold already max-composes its own parties
        # (Thm 4), and in a single-domain round this IS that domain's
        # epsilon, unchanged from the legacy path
        dom_eps = [row["epsilon"] for row in by_domain.values()
                   if row["epsilon"] is not None]
        eps = max(dom_eps) if dom_eps else None

        engine_names = sorted({b.engine.name for b in self.bindings})
        meta: Dict[str, Any] = {
            "party_sizes": [p.num_examples for p in self.parties],
            "engine": (engine_names[0] if len(engine_names) == 1
                       else "mixed"),
            # one row per party: which model family and engine each silo
            # brought to the round (identical rows = the homogeneous
            # shorthand)
            "party_bindings": [{"learner": b.kind,
                                "engine": b.engine.name}
                               for b in self.bindings],
            "transport": self.transport.name,
            "parallelism": getattr(self.transport, "parallelism", None),
            "queries": {"party": self.tq_party, "server": self.tq_server},
            "seconds": {"parties": round(t_parties, 3),
                        "server": round(t_server, 3)},
            # measured codec-framed bytes + raw-payload accounting,
            # summed over the parties whose updates actually arrived
            "wire_bytes": agg.wire_meta(),
            "num_updates": agg.num_parties,
        }
        if streaming:
            report = dict(self.transport.round_report)
            meta["socket"] = report
            # dropout accounting: stragglers excluded from the vote
            meta["dropped_parties"] = report.get("dropped", [])
        return RoundResult(final_state=final_state, accuracy=acc,
                           student_states=agg.student_states(),
                           epsilon=eps, meta=meta, by_domain=by_domain)
