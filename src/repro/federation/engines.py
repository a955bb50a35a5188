"""Execution engines: HOW a party's teachers get trained and queried.

The protocol (who sends what, once) lives in party.py / server.py /
session.py; an Engine only decides how a batch of teachers is fit and
how a trained bank predicts the public queries:

  LoopEngine : one ``learner.fit`` per teacher, serially — the seed
               semantics of the original ``run_fedkt`` loop.
  VmapEngine : stacks all given teachers into one ``jax.vmap``-ed fit
               over a shared pow2-padded bucket.  The Party hands it
               its full s*t teacher grid, so the n*s*t sequential jit
               dispatches of the serial loop collapse to one batched
               dispatch per party — the headline wall-clock win (see
               BENCH_federation_engines.json).
  LMEngine   : the sharded-LM path (core/distill.py) behind the same
               contract.  Teachers are a full ``models.Model`` each;
               a trained bank is ONE pytree with the member params
               stacked on a leading axis (the mesh "data" axis at
               datacenter scale), and the per-partition vote runs as
               the fused ``make_label_step`` — vmap'd greedy predict +
               blocked token vote, the paper's single collective round.
               Requires a learner with the LM hooks
               (``vote_members``/``predict_stacked``: core.learners.
               LMLearner).

The full written contract (method-by-method, the ``fit_stacked``
key-for-key reproduction rule, the zero-weight padding rule, and the
wire message kinds) lives in docs/engines.md.

PRNG contract: engines never split keys.  The Party precomputes the
legacy loop's exact key schedule (one split per teacher, in partition/
subset order) and passes one key per teacher, so switching engines
never changes which key a teacher sees.  When every subset pads to the
same pow2 bucket the two engines are bit-identical; otherwise they may
differ in trailing pad size and are only required to agree on vote
labels (test-enforced).

Vote contract: the party-side vote is an engine concern too
(``label_queries``), because HOW the queries get labeled is execution —
serial predicts + one histogram build, or the LM path's fused
label step.  Every engine must return the labels AND the CLEAN
(pre-noise) top1-top2 gap the Lemma-7 accountant needs, bit-identical
to serial predicts + ``core.voting.teacher_vote`` at the same key.
"""
from __future__ import annotations

from typing import Any, List, Protocol, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.voting import party_vote_counts, teacher_vote


class Engine(Protocol):
    """Pluggable teacher/student-execution backend."""
    name: str

    def fit_teachers(self, keys: Sequence[Any], learner,
                     datasets: Sequence[Tuple[Any, Any]]) -> Any:
        """Trains one teacher per (X, y) dataset with the paired key.
        Returns an opaque teacher bank."""
        ...

    def slice_bank(self, bank, start: int, stop: int) -> Any:
        """The sub-bank holding teachers [start, stop)."""
        ...

    def predict_teachers(self, learner, bank, X) -> jnp.ndarray:
        """Predictions of every teacher in the bank: (t, T) int32."""
        ...

    def label_queries(self, learner, bank, X, num_classes: int, *,
                      gamma: float = 0.0, key=None):
        """One partition's ensemble answers the public queries: noisy
        max-vote ``labels (T,)`` plus the CLEAN top1-top2 ``gap (T,)``
        (Lemma 7).  Must be bit-identical to serial per-teacher predicts
        + ``teacher_vote`` at the same key."""
        ...

    def fit_students(self, keys: Sequence[Any], learner, X,
                     labelsets: Sequence[Any]) -> List[Any]:
        """Trains one student per voted labelset, all on the SAME query
        set X.  Returns a plain list of student states — the PartyUpdate
        wire format — so batching is an execution detail, not a protocol
        change."""
        ...

    def predict_students(self, learner, states: Sequence[Any],
                         X) -> jnp.ndarray:
        """Predictions of a list of (unstacked) student states on one
        shared X: (len(states), T) int32."""
        ...

    def student_vote_counts(self, learner, states: Sequence[Any], X,
                            domain, *,
                            consistent: bool = True) -> jnp.ndarray:
        """ONE party's additive server-vote contribution, shaped by its
        VoteDomain: (domain.num_units, domain.num_classes) int32.  The
        streaming aggregator (federation/aggregate.py) folds these per
        arriving update, so the server never holds more than one
        party's predictions at a time.  Must equal
        ``voting.party_vote_counts(predict_students(...), domain)`` —
        the default below — but an engine may fuse predict + count into
        one dispatch."""
        ...


def _students_vote_counts(engine, learner, states, X, domain,
                          consistent):
    """Default ``student_vote_counts``: the engine's own student
    predicts, reduced by ``voting.party_vote_counts`` over the party's
    declared domain."""
    preds = engine.predict_students(learner, states, X)
    return party_vote_counts(preds, domain, consistent=consistent)


def _serial_fit_students(keys, learner, X, labelsets):
    return [learner.fit(kk, X, y) for kk, y in zip(keys, labelsets)]


def _serial_predict(learner, states, X):
    return jnp.stack([learner.predict(st, X) for st in states])


def _histogram_vote(engine, learner, bank, X, num_classes, gamma, key):
    """Default ``label_queries``: per-teacher predicts + one histogram
    build (``votes_with_clean`` under the hood)."""
    preds = engine.predict_teachers(learner, bank, X)
    vote = teacher_vote(preds, num_classes, gamma=gamma, key=key)
    return vote.labels, vote.top_gap


class LoopEngine:
    """Serial reference engine (seed semantics of the legacy loop)."""
    name = "loop"

    def fit_teachers(self, keys, learner, datasets):
        return [learner.fit(kk, X, y)
                for kk, (X, y) in zip(keys, datasets)]

    def slice_bank(self, bank, start, stop):
        return bank[start:stop]

    def predict_teachers(self, learner, bank, X):
        return _serial_predict(learner, bank, X)

    def label_queries(self, learner, bank, X, num_classes, *,
                      gamma=0.0, key=None):
        return _histogram_vote(self, learner, bank, X, num_classes,
                               gamma, key)

    def fit_students(self, keys, learner, X, labelsets):
        return _serial_fit_students(keys, learner, X, labelsets)

    def predict_students(self, learner, states, X):
        return _serial_predict(learner, states, X)

    def student_vote_counts(self, learner, states, X, domain, *,
                            consistent=True):
        return _students_vote_counts(self, learner, states, X,
                                     domain, consistent)


class VmapEngine:
    """Batched engine: one vmap'd fit over the stacked teacher grid.

    Learners opt in by providing ``fit_stacked(keys, Xs, ys)`` /
    ``predict_stacked(states, X)`` (NNLearner, RFLearner, GBDTLearner);
    learners without the hooks fall back to the serial path with
    identical keys, so mixing learner kinds stays correct.

    Students batch too: a party's s students all train on the same query
    set, so their fits share one bucket and stacking is always
    bit-identical to the serial loop (engine-agreement test-enforced).
    """
    name = "vmap"

    def fit_teachers(self, keys, learner, datasets):
        if not hasattr(learner, "fit_stacked"):
            return [learner.fit(kk, X, y)
                    for kk, (X, y) in zip(keys, datasets)]
        return learner.fit_stacked(jnp.stack(list(keys)),
                                   [X for X, _ in datasets],
                                   [y for _, y in datasets])

    def slice_bank(self, bank, start, stop):
        if isinstance(bank, list):                 # serial fallback
            return bank[start:stop]
        return jax.tree.map(lambda leaf: leaf[start:stop], bank)

    def predict_teachers(self, learner, bank, X):
        if isinstance(bank, list):                 # serial fallback
            return _serial_predict(learner, bank, X)
        return learner.predict_stacked(bank, X)

    def label_queries(self, learner, bank, X, num_classes, *,
                      gamma=0.0, key=None):
        return _histogram_vote(self, learner, bank, X, num_classes,
                               gamma, key)

    def fit_students(self, keys, learner, X, labelsets):
        if not hasattr(learner, "fit_stacked") or len(labelsets) < 2:
            return _serial_fit_students(keys, learner, X, labelsets)
        stacked = learner.fit_stacked(jnp.stack(list(keys)),
                                      [X] * len(labelsets),
                                      list(labelsets))
        return [jax.tree.map(lambda leaf: leaf[i], stacked)
                for i in range(len(labelsets))]

    def predict_students(self, learner, states, X):
        if not hasattr(learner, "predict_stacked") or len(states) < 2:
            return _serial_predict(learner, states, X)
        bank = jax.tree.map(lambda *leaves: jnp.stack(leaves), *states)
        return learner.predict_stacked(bank, X)

    def student_vote_counts(self, learner, states, X, domain, *,
                            consistent=True):
        return _students_vote_counts(self, learner, states, X,
                                     domain, consistent)


class LMEngine:
    """Sharded-LM engine: distill.py's label/train steps as the
    execution backend.

    Teacher fits are full training loops (one jitted step reused across
    fits — serial dispatch is already one jit call per step), but the
    trained bank is the distill.py layout: member params STACKED on a
    leading axis, which is what ``make_label_step`` vmaps over and what
    fedkt_dryrun shards over the production mesh's "data" axis.  The
    per-partition vote is the fused label step — greedy predict + the
    blocked token vote in one dispatch (ONE cross-member all-reduce
    under pjit: the paper's single communication round at scale).

    Requires the learner to provide the LM hooks (``vote_members``,
    ``predict_stacked`` — core.learners.LMLearner); generic learners
    should use the loop/vmap engines instead.
    """
    name = "lm"

    @staticmethod
    def _require_lm(learner):
        if not hasattr(learner, "vote_members"):
            raise TypeError(
                f"engine='lm' needs an LM learner (vote_members/"
                f"predict_stacked hooks); got {type(learner).__name__}. "
                f"Use engine='loop' or 'vmap' for generic learners.")

    def fit_teachers(self, keys, learner, datasets):
        self._require_lm(learner)
        states = [learner.fit(kk, X, y)
                  for kk, (X, y) in zip(keys, datasets)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    def slice_bank(self, bank, start, stop):
        return jax.tree.map(lambda leaf: leaf[start:stop], bank)

    def predict_teachers(self, learner, bank, X):
        return learner.predict_stacked(bank, X)

    def label_queries(self, learner, bank, X, num_classes, *,
                      gamma=0.0, key=None):
        self._require_lm(learner)
        vocab = learner.model.cfg.vocab_size
        if num_classes != vocab:
            raise ValueError(f"cfg.num_classes={num_classes} must equal "
                             f"the model vocab_size={vocab} on the LM "
                             f"path (token labels ARE class labels)")
        return learner.vote_members(bank, X, gamma=gamma, key=key)

    def fit_students(self, keys, learner, X, labelsets):
        return _serial_fit_students(keys, learner, X, labelsets)

    def predict_students(self, learner, states, X):
        return _serial_predict(learner, states, X)

    def student_vote_counts(self, learner, states, X, domain, *,
                            consistent=True):
        return _students_vote_counts(self, learner, states, X,
                                     domain, consistent)


_ENGINES = {"loop": LoopEngine, "vmap": VmapEngine, "lm": LMEngine}


def get_engine(engine) -> Engine:
    """Engine instance from a name ("loop" | "vmap" | "lm") or
    pass-through."""
    if isinstance(engine, str):
        if engine not in _ENGINES:
            raise ValueError(f"unknown engine {engine!r}; "
                             f"available: {sorted(_ENGINES)}")
        return _ENGINES[engine]()
    return engine
