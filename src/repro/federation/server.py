"""Server: the aggregator side of the FedKT protocol (Algorithm 1
lines 13-23).

Folds the arriving PartyUpdates into a ``StreamingVoteAggregate``
(federation/aggregate.py) — one running consistent-vote histogram,
constant memory in the party count — then noises, argmaxes, and
distills the final model from the voted labels.  Being the only place
that sees the global vote histogram, the server side owns the L1
privacy accounting; L2 accounting composes the parties' local gap
traces (Thm 4 parallel composition), folded per arrival.  The batch
``aggregate`` entry point and the socket transport's streaming path are
the SAME fold, so they cannot diverge.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np

from repro import obs
from repro.configs.base import FedKTConfig
from repro.federation.aggregate import StreamingVoteAggregate
from repro.federation.engines import Engine, LoopEngine
from repro.federation.messages import PartyUpdate


class Server:
    def __init__(self, cfg: FedKTConfig, student_learner, final_learner,
                 *, bindings=None):
        """``bindings`` (party_id -> ResolvedBinding) is the
        heterogeneous contract: the fold runs each arriving update's
        states under THAT party's student learner and engine.  Without
        it, the session-wide (student_learner, engine) pair applies to
        every party — the homogeneous shorthand."""
        self.cfg = cfg
        self.student_learner = student_learner
        self.final_learner = final_learner
        self.bindings = bindings

    def make_aggregate(self, X_public, num_queries: int,
                       engine: Engine = None, *,
                       retain_students: bool = True
                       ) -> StreamingVoteAggregate:
        """A fresh per-round fold.  ``engine`` decides how each party's
        s student models answer the query set (serial loop vs one
        stacked predict); defaults to the serial reference engine.
        Per-party bindings, when registered, override both the learner
        and the engine for their party's updates."""
        return StreamingVoteAggregate(
            self.cfg, self.student_learner, engine or LoopEngine(),
            X_public[:num_queries], retain_students=retain_students,
            bindings=self.bindings)

    def finalize(self, key, agg: StreamingVoteAggregate):
        """Vote over the finished histogram + final distillation, for a
        SINGLE-domain round (the legacy entry point; multi-domain rounds
        use ``finalize_all``).  Returns (final_state, VoteResult, key) —
        key threading matches the legacy loop split-for-split (one split
        for vote noise, one for the final fit)."""
        key, kk = jax.random.split(key)
        vote = agg.finalize(kk)
        key, kk = jax.random.split(key)
        final_state = self.final_learner.fit(kk, agg.Xq,
                                             np.asarray(vote.labels))
        return final_state, vote, key

    def finalize_all(self, key, agg: StreamingVoteAggregate):
        """Per-domain finalize: every domain that received votes gets
        its own noise split and its own VoteResult, in sorted-identity
        order (deterministic whatever order the updates streamed in);
        the final model distills from the PRIMARY domain — the one the
        final learner itself votes in (agg.primary_domain).

        Returns (final_state, primary VoteResult, {domain.ident ->
        VoteResult}, key), once ``final_state`` is ready on the device.
        With one domain this is split-for-split the legacy ``finalize``
        — one split for vote noise, one for the final fit — so every
        existing single-domain round stays bit-identical."""
        with obs.span("fedkt.finalize", queries=len(agg.Xq)):
            votes = {}
            for dom in agg.domains():
                key, kk = jax.random.split(key)
                votes[dom.ident] = agg.finalize_domain(dom, kk)
            primary = agg.primary_domain(self.final_learner)
            vote = votes[primary.ident]
            key, kk = jax.random.split(key)
            final_state = self.final_learner.fit(kk, agg.Xq,
                                                 np.asarray(vote.labels))
            # the span (and the session's server clock) ends with the
            # final student on the device, not with its dispatch
            jax.block_until_ready(final_state)
        return final_state, vote, votes, key

    def aggregate(self, key, updates: Sequence[PartyUpdate], X_public,
                  num_queries: int, engine: Engine = None):
        """Batch entry point: fold a finished update list, then
        finalize.  Bit-identical to the streaming path in any order."""
        agg = self.make_aggregate(X_public, num_queries, engine)
        for upd in updates:
            agg.add(upd)
        return self.finalize(key, agg)

    def epsilon(self, vote, agg: StreamingVoteAggregate) -> Optional[float]:
        """Data-dependent (eps, delta=1e-5) bound for the configured
        privacy level; None under L0.  Delegates to the aggregate, which
        folded the per-party L2 terms at arrival time."""
        return agg.epsilon(vote)
