"""Party: the data-holder side of the FedKT protocol (Algorithm 1
lines 2-12).

A party never shares raw examples or teacher models.  Its entire
contribution to the round is one PartyUpdate: s student models, each
distilled from a t-teacher ensemble vote on the public queries, plus
(under L2) the vote-gap trace its local accountant needs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List

import jax
import numpy as np

from repro import obs
from repro.configs.base import FedKTConfig
from repro.core.partition import subsets_of_partition
from repro.federation.bindings import learner_kind
from repro.federation.engines import Engine, get_engine
from repro.federation.messages import LABEL_BYTES, PartyUpdate


@dataclass
class Party:
    """One silo.  ``indices`` selects its local shard of the (conceptually
    party-private) training arrays; in a deployed setting X/y would be
    the silo's own storage and ``indices`` the identity.

    The learner/student_learner/engine triple is the party's BINDING
    (federation/bindings.py): each silo brings its own model family and
    execution engine to the round, so one session can ensemble rf, gbdt,
    nn, and lm parties.  ``engine`` may be None — ``local_round`` then
    needs an explicit engine argument (the pre-binding calling
    convention, kept for the transports and direct callers)."""
    party_id: int
    X: np.ndarray
    y: np.ndarray
    indices: np.ndarray
    cfg: FedKTConfig
    learner: Any
    student_learner: Any
    engine: Any = None

    @property
    def num_examples(self) -> int:
        return len(self.indices)

    def _key_schedule(self, key, s: int, t: int):
        """The legacy loop's exact split order: per partition j, t
        teacher keys, then one vote key, then one student key.  Played
        forward here so engines get explicit keys and can batch the
        whole s*t teacher grid without changing any teacher's seed."""
        teacher_keys, vote_keys, student_keys = [], [], []
        for _ in range(s):
            for _ in range(t):
                key, kk = jax.random.split(key)
                teacher_keys.append(kk)
            key, kk = jax.random.split(key)
            vote_keys.append(kk)
            key, kk = jax.random.split(key)
            student_keys.append(kk)
        return teacher_keys, vote_keys, student_keys, key

    def advance_key(self, key):
        """The key ``local_round`` would return, WITHOUT training: the
        schedule consumes a fixed split count (s * (t + 2)), so the
        session can precompute every party's starting key and fan the
        parties out in parallel with unchanged serial-loop seeds."""
        cfg = self.cfg
        return self._key_schedule(key, cfg.num_partitions,
                                  cfg.num_subsets)[3]

    def local_round(self, key, X_public, num_queries: int,
                    engine: Engine = None):
        """Runs the party side of the single round.

        Returns (PartyUpdate, advanced key).  Key threading matches the
        legacy ``run_fedkt`` loop split-for-split, so results are
        seed-for-seed reproducible across API versions and engines.

        ``engine=None`` uses the party's OWN bound engine — the
        heterogeneous path, where each silo's binding decides how its
        teachers train; an explicit engine overrides the binding (the
        transports pass None so every party runs its own).
        """
        cfg = self.cfg
        if engine is None:
            if self.engine is None:
                raise ValueError(
                    f"party {self.party_id} has no bound engine; pass "
                    f"engine= to local_round or bind one at construction")
            engine = self.engine
        engine = get_engine(engine)
        # the party's declared VoteDomain: the layout its STUDENTS vote
        # in at the server, over the SERVER-side query slice (under
        # L1/L2 the party answers tq_party queries but its students are
        # folded over tq_server), fingerprinted so two parties cannot
        # silently vote on different query sets.  Lazy imports: session
        # imports party, and domain derivation is only needed here.
        from repro.federation.domain import (fingerprint_queries,
                                             learner_domain)
        from repro.federation.session import query_budget
        _, tq_server = query_budget(cfg, len(X_public))
        Xq_server = X_public[:tq_server]
        dom = learner_domain(self.student_learner, Xq_server,
                             cfg.num_classes,
                             fingerprint=fingerprint_queries(Xq_server))
        s, t, u = cfg.num_partitions, cfg.num_subsets, dom.num_classes
        Xq = X_public[:num_queries]
        plan = subsets_of_partition(self.indices, s, t,
                                    seed=cfg.seed + 17 * self.party_id)
        gamma = cfg.gamma if cfg.privacy_level == "L2" else 0.0

        teacher_keys, vote_keys, student_keys, key = \
            self._key_schedule(key, s, t)
        datasets = [(self.X[sub], self.y[sub])
                    for j in range(s) for sub in plan[j]]
        # the fits are dispatched, not awaited: their device time shows
        # in the vote's wait for its labels
        with obs.span("fedkt.teacher_fit", teachers=s * t):
            bank = engine.fit_teachers(teacher_keys, self.learner,
                                       datasets)

        labelsets: List[np.ndarray] = []
        gaps: List[np.ndarray] = []
        with obs.span("fedkt.party_vote", queries=len(Xq)):
            for j in range(s):
                bank_j = engine.slice_bank(bank, j * t, (j + 1) * t)
                # HOW the queries get labeled is the engine's concern
                # (serial predicts + histogram vote, or the LM path's
                # fused label step); the protocol only needs labels +
                # clean gaps
                labels, gap = engine.label_queries(
                    self.learner, bank_j, Xq, u, gamma=gamma,
                    key=vote_keys[j])
                gaps.append(np.asarray(gap))
                labelsets.append(np.asarray(labels))
        # all s students vote on the same Xq, so the engine may train
        # them as ONE stacked fit; student_keys is the precomputed legacy
        # schedule, so batching never changes a student's seed
        with obs.span("fedkt.student_fit", students=s):
            students: List[Any] = engine.fit_students(
                student_keys, self.student_learner, Xq, labelsets)

        update = PartyUpdate(party_id=self.party_id,
                             student_states=students,
                             vote_gaps=np.concatenate(gaps),
                             num_examples=self.num_examples,
                             # the STUDENT family: what the server must
                             # run to fold this party's votes
                             learner_kind=learner_kind(
                                 self.student_learner),
                             # the declared vote layout, validated at
                             # ACK time (net.py) and at fold time
                             # (aggregate.py)
                             domain=dom,
                             meta={"num_teachers": s * t,
                                   # label answers are one vote unit per
                                   # LABEL (= per token on the LM path,
                                   # not per query sequence) — the
                                   # session's wire accounting reads this
                                   "num_query_labels": int(
                                       labelsets[0].size),
                                   "label_payload_bytes": int(
                                       labelsets[0].size * LABEL_BYTES)})
        return update, key
