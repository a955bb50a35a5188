"""Uniform Learner interface: anything with fit/predict can be a FedKT
teacher, student, or final model — differentiable or not.

NNLearner : jit-compiled Adam training loop over a smallnet (MLP / CNN /
            VGG).  Data is padded to power-of-two buckets so party/subset
            size variation doesn't retrigger compilation.
RFLearner / GBDTLearner : the JAX histogram tree learners (trees.py).
LMLearner : a full transformer-family Model behind the same contract —
            examples are (N, S+1) token sequences, "classes" are vocab
            ids, and a prediction is one vocab id per TOKEN (the flat
            (N*S,) layout every vote op already uses).  Wraps the
            sharded distill.py steps, so the federation session drives
            LM distillation through the exact code path launch/train.py
            and the fedkt_dryrun lower at datacenter scale.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import trees as T
from repro.optim import adamw


def _pow2_bucket(n, min_size=32):
    return max(min_size, 1 << (n - 1).bit_length())


def shared_bucket(Xs):
    """The one pow2 bucket a stacked fit pads all of its members to:
    the largest member's own."""
    return max(_pow2_bucket(len(X)) for X in Xs)


def _pad_span(Xs, bucket):
    """``fedkt.pad`` over the padding of ``Xs`` to ``bucket`` rows each
    (with the padded arrays' put to the device): the true rows against
    the rows the fit runs over."""
    return obs.span("fedkt.pad", rows=sum(len(X) for X in Xs),
                    padded_rows=len(Xs) * bucket)


def _mask_cols(X, mask):
    """Selects a party's feature columns (vertical federation: each
    silo holds a slice of the feature space).  ``mask`` is a tuple of
    column indices — a TUPLE, not an array, because the learners are
    frozen dataclasses used as jit static arguments and every field
    must hash.  None = all columns (the horizontal default)."""
    if mask is None:
        return np.asarray(X)
    return np.asarray(X)[:, list(mask)]


def _pad_pow2(X, y, min_size=32, bucket=None):
    n = len(X)
    m = bucket or _pow2_bucket(n, min_size)
    mask = np.zeros((m,), np.float32)
    mask[:n] = 1.0
    Xp = np.zeros((m,) + X.shape[1:], X.dtype)
    Xp[:n] = X
    yp = np.zeros((m,), np.int32)
    yp[:n] = y
    return jnp.asarray(Xp), jnp.asarray(yp), jnp.asarray(mask)


@dataclass(frozen=True)
class NNLearner:
    net: Any                      # smallnets module object (init/apply)
    num_classes: int
    steps: int = 300
    batch_size: int = 64
    lr: float = 1e-3
    l2: float = 1e-6
    # vertical federation: this party trains and predicts on only these
    # feature columns of any X it is handed (core.partition.
    # vertical_split); the net must be sized to len(feature_mask)
    feature_mask: Any = None      # Optional[Tuple[int, ...]]

    def _fit_body(self, key, X, y, mask):
        opt = adamw(weight_decay=self.l2)
        params = self.net.init(jax.random.fold_in(key, 1))
        state = opt.init(params)
        p_sel = mask / mask.sum()

        def loss_fn(p, xb, yb):
            logits = self.net.apply(p, xb)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(
                jnp.take_along_axis(logp, yb[:, None], axis=1))

        def step(carry, k):
            params, state = carry
            idx = jax.random.choice(k, X.shape[0], (self.batch_size,),
                                    p=p_sel)
            g = jax.grad(loss_fn)(params, X[idx], y[idx])
            params, state = opt.update(g, state, params, self.lr)
            return (params, state), None

        keys = jax.random.split(jax.random.fold_in(key, 2), self.steps)
        (params, _), _ = jax.lax.scan(step, (params, state), keys)
        return params

    @functools.partial(jax.jit, static_argnums=0)
    def _fit(self, key, X, y, mask):
        return self._fit_body(key, X, y, mask)

    @functools.partial(jax.jit, static_argnums=0)
    def _fit_stacked(self, keys, X, y, mask):
        return jax.vmap(self._fit_body)(keys, X, y, mask)

    def fit(self, key, X, y):
        X = _mask_cols(X, self.feature_mask)
        bucket = _pow2_bucket(len(X))
        with _pad_span([X], bucket):
            Xp, yp, mask = _pad_pow2(X, np.asarray(y), bucket=bucket)
        return self._fit(key, Xp, yp, mask)

    def fit_stacked(self, keys, Xs, ys):
        """Trains len(Xs) models as ONE vmap'd fit (federation vmap
        engine).  All datasets share the largest member's pow2 bucket;
        per-row masks keep each model's sampling distribution on its own
        examples, so a model trained here matches its serial ``fit``
        whenever its individual bucket equals the shared one."""
        Xs = [_mask_cols(X, self.feature_mask) for X in Xs]
        bucket = shared_bucket(Xs)
        with _pad_span(Xs, bucket):
            padded = [_pad_pow2(X, np.asarray(y), bucket=bucket)
                      for X, y in zip(Xs, ys)]
        Xp, yp, mask = (jnp.stack([p[i] for p in padded])
                        for i in range(3))
        return self._fit_stacked(jnp.asarray(keys), Xp, yp, mask)

    def _predict_body(self, state, X):
        return jnp.argmax(self.net.apply(state, X), -1).astype(jnp.int32)

    @functools.partial(jax.jit, static_argnums=0)
    def _predict(self, state, X):
        return self._predict_body(state, X)

    def predict(self, state, X):
        return self._predict(state,
                             jnp.asarray(_mask_cols(X, self.feature_mask)))

    @functools.partial(jax.jit, static_argnums=0)
    def _predict_stacked(self, states, X):
        return jax.vmap(lambda st: self._predict_body(st, X))(states)

    def predict_stacked(self, states, X):
        """(k, T) predictions of k stacked models on one shared X."""
        return self._predict_stacked(
            states, jnp.asarray(_mask_cols(X, self.feature_mask)))


@dataclass(frozen=True)
class RFLearner:
    num_classes: int
    num_trees: int = 20
    depth: int = 6
    impl: str = "auto"            # ops.tree_hist backend knob
    feature_mask: Any = None      # vertical: this silo's columns

    def _rf(self):
        return T.RandomForest(self.num_trees, self.depth, self.num_classes,
                              impl=self.impl)

    def fit(self, key, X, y):
        X = _mask_cols(X, self.feature_mask).astype(np.float32)
        edges = jnp.asarray(T.make_bins(X))
        forest = self._rf().fit(key, jnp.asarray(X),
                                jnp.asarray(y, jnp.int32), edges)
        return (forest, edges)

    def fit_stacked(self, keys, Xs, ys):
        """k forests as one stacked jit fit (federation vmap engine).

        Each dataset keeps its own quantile edges and a bootstrap draw
        at its TRUE size (key-for-key identical to serial ``fit``); rows
        padding up to the shared pow2 bucket carry ZERO sample weight,
        so the stacked states are bit-identical to the serial loop
        regardless of bucket size (histograms ignore w == 0 rows).  The
        draws are made, padded and stacked on the device in one program
        (``RandomForest.bootstrap_stacked``): nothing is pulled to the
        host."""
        Xs = [_mask_cols(X, self.feature_mask).astype(np.float32)
              for X in Xs]
        bucket = shared_bucket(Xs)
        edges = jnp.asarray(np.stack([T.make_bins(X) for X in Xs]))
        w, fm = self._rf().bootstrap_stacked(
            keys, tuple(len(X) for X in Xs), bucket, Xs[0].shape[1])
        with _pad_span(Xs, bucket):
            Xp, yp, _ = zip(*(_pad_pow2(X, np.asarray(y), bucket=bucket)
                              for X, y in zip(Xs, ys)))
        forest = T.fit_forest_stacked(
            jnp.stack(Xp), edges, jnp.stack(yp), w, fm,
            depth=self.depth, num_classes=self.num_classes,
            impl=self.impl)
        return (forest, edges)

    def predict(self, state, X):
        forest, edges = state
        X = _mask_cols(X, self.feature_mask)
        return self._rf().predict(forest, jnp.asarray(X, jnp.float32),
                                  edges)

    def predict_stacked(self, states, X):
        """(k, T) predictions of k stacked forests on one shared X."""
        forest, edges = states
        X = _mask_cols(X, self.feature_mask)
        return T.predict_forest_stacked(forest,
                                        jnp.asarray(X, jnp.float32), edges)


@dataclass(frozen=True)
class GBDTLearner:
    num_classes: int = 2
    num_rounds: int = 30
    depth: int = 6
    impl: str = "auto"            # ops.tree_hist backend knob
    feature_mask: Any = None      # vertical: this silo's columns

    def _gb(self):
        return T.GBDT(self.num_rounds, self.depth, impl=self.impl)

    def fit(self, key, X, y):
        X = _mask_cols(X, self.feature_mask).astype(np.float32)
        edges = jnp.asarray(T.make_bins(X))
        gb = self._gb()
        return (gb.fit(key, jnp.asarray(X), jnp.asarray(y, jnp.int32),
                       edges), edges)

    def fit_stacked(self, keys, Xs, ys):
        """k GBDTs as one stacked jit fit.  Shared pow2 bucket; padding
        rows carry zero g/h weight, so stacked == serial bit-for-bit
        (see trees.fit_gbdt)."""
        gb = self._gb()
        Xs = [_mask_cols(X, self.feature_mask).astype(np.float32)
              for X in Xs]
        bucket = shared_bucket(Xs)
        edges = jnp.asarray(np.stack([T.make_bins(X) for X in Xs]))
        with _pad_span(Xs, bucket):
            Xp, yp, wp = zip(*(_pad_pow2(X, np.asarray(y), bucket=bucket)
                               for X, y in zip(Xs, ys)))
        trees = T.fit_gbdt_stacked(
            jnp.stack(Xp), edges, jnp.stack(yp), jnp.stack(wp),
            gb.learning_rate, num_rounds=self.num_rounds, depth=self.depth,
            impl=self.impl)
        return (trees, edges)

    def predict(self, state, X):
        trees, edges = state
        X = _mask_cols(X, self.feature_mask)
        return self._gb().predict(trees, jnp.asarray(X, np.float32), edges)

    def predict_stacked(self, states, X):
        """(k, T) predictions of k stacked GBDTs on one shared X."""
        trees, edges = states
        X = _mask_cols(X, self.feature_mask)
        return T.predict_gbdt_stacked(trees, jnp.asarray(X, np.float32),
                                      edges, self._gb().learning_rate)


@dataclass(frozen=True, eq=False)
class LMLearner:
    """Language model as a FedKT learner (the paper's "any
    classification model" claim at LM scale).

    X is an (N, S+1) int32 token matrix; ``fit`` dispatches on the label
    shape: per-sequence labels (size N — the partitioner's proxy classes)
    mean plain next-token training, per-token labels (size N*S — a vote
    answer) mean distillation on the given labels.  ``predict`` returns
    one vocab id per token, flattened to (N*S,), which is exactly the
    (t, T) layout ``teacher_vote``/``consistent_vote`` consume.

    PRNG contract: LM training randomness is owned by ``tcfg.seed``
    (init) and ``data_seed`` (the TokenDataset shuffle stream), matching
    launch/train.py's ``train_lm`` — the federation key a fit receives
    only feeds DP vote noise elsewhere in the protocol, so it is
    deliberately unused here and engine/transport fan-out cannot change
    a fit.  Construct with ``data_seed=cfg.seed`` for the student/final
    roles (the legacy loop shuffled the public stream with the federation
    seed) and the default 0 for teachers.
    """
    model: Any                    # models.Model
    tcfg: Any                     # configs.TrainConfig
    data_seed: int = 0            # TokenDataset shuffle seed

    # jitted-step caches live in __dict__ (cached_property); drop them on
    # pickle so Subprocess transports ship only the config fields
    def __getstate__(self):
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}

    def __setstate__(self, state):
        for k, v in state.items():
            object.__setattr__(self, k, v)

    @functools.cached_property
    def _train_machinery(self):
        from repro.core.distill import make_train_step
        step, opt = make_train_step(self.model, self.tcfg)
        return jax.jit(step), opt

    @functools.cached_property
    def _predict_jit(self):
        return jax.jit(
            lambda p, toks: self.model.predict(p, {"tokens": toks}))

    @functools.cached_property
    def _predict_stacked_jit(self):
        return jax.jit(jax.vmap(
            lambda p, toks: self.model.predict(p, {"tokens": toks}),
            in_axes=(0, None)))

    @functools.cached_property
    def _label_steps(self):
        return {}                 # (num_members, gamma) -> jitted step

    def _tokens(self, X):
        X = np.asarray(X)
        assert X.ndim == 2 and X.shape[1] >= 3, \
            "LMLearner expects (N, S+1) token sequences with S >= 2"
        return X.astype(np.int32)

    def fit(self, key, X, y=None):
        from repro.data.pipeline import TokenDataset
        X = self._tokens(X)
        N, S = X.shape[0], X.shape[1] - 1
        if N < self.tcfg.batch_size:
            raise ValueError(f"LMLearner.fit needs >= batch_size="
                             f"{self.tcfg.batch_size} sequences, got {N}")
        labels = None
        if y is not None:
            y = np.asarray(y)
            if y.size == N * S:               # voted token labels
                labels = y.reshape(N, S).astype(np.int32)
            elif y.size != N:                 # size N: proxy classes
                raise ValueError(f"labels of size {y.size} match neither "
                                 f"{N} sequences nor {N * S} tokens")
        step, opt = self._train_machinery
        params = self.model.init(jax.random.PRNGKey(self.tcfg.seed))
        opt_state = opt.init(params)
        ds = TokenDataset(X, self.data_seed)
        for batch in ds.batches(self.tcfg.batch_size,
                                steps=self.tcfg.steps, labels=labels):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, _ = step(params, opt_state, batch)
        return params

    def predict(self, state, X):
        toks = jnp.asarray(self._tokens(X)[:, :-1])
        return self._predict_jit(state, toks).reshape(-1)

    def predict_stacked(self, bank, X):
        """(M, N*S) predictions of M member-stacked param sets."""
        toks = jnp.asarray(self._tokens(X)[:, :-1])
        preds = self._predict_stacked_jit(bank, toks)
        return preds.reshape(preds.shape[0], -1)

    def vote_domain(self, Xq, default_num_classes: int, *,
                    fingerprint=None):
        """The LM path's vote layout, declared by the learner (the
        ``vote_domain`` hook — docs/engines.md "Vote domains"): one
        vote row per query TOKEN (T = N*S over an (N, S+1) query
        matrix) ranging over the model's own vocab, regardless of the
        session's default class count."""
        from repro.federation.domain import (fingerprint_queries,
                                             token_domain)
        X = self._tokens(Xq)
        if fingerprint is None:
            fingerprint = fingerprint_queries(np.asarray(Xq))
        return token_domain(X.shape[0] * (X.shape[1] - 1),
                            self.model.cfg.vocab_size,
                            fingerprint=fingerprint)

    def label_step(self, num_members: int, gamma: float = 0.0):
        """The raw distill.make_label_step fn over ``num_members``
        stacked param sets — the step fedkt_dryrun lowers onto the
        production mesh, exposed so the dry-run prices the session
        engine's exact computation."""
        from repro.core.distill import make_label_step
        return make_label_step(self.model, num_members, gamma=gamma)

    def vote_members(self, bank, X, *, gamma: float = 0.0, key=None):
        """Greedy-predict + token vote over a stacked member bank in ONE
        step (the cross-member reduction is the paper's single round at
        scale).  Returns (labels (N*S,), clean gaps (N*S,)) — identical
        bit-for-bit to serial per-member predicts + ``teacher_vote``
        (test-enforced)."""
        toks = jnp.asarray(self._tokens(X)[:, :-1])
        m = int(jax.tree.leaves(bank)[0].shape[0])
        ck = (m, float(gamma))
        if ck not in self._label_steps:
            self._label_steps[ck] = jax.jit(self.label_step(m, gamma))
        labels, gap = self._label_steps[ck](bank, {"tokens": toks}, key)
        return labels.reshape(-1), gap.reshape(-1)


def accuracy(learner, state, X, y) -> float:
    preds = np.asarray(learner.predict(state, X))
    return float((preds == np.asarray(y)).mean())
