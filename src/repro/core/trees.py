"""Non-differentiable learners in pure JAX: random forest and GBDT.

FedKT's headline claim is model-agnosticism — it federates models that
FedAvg cannot (paper Table 1 trains a random forest on Adult and a GBDT
on cod-rna).  These are histogram-based, fixed-depth, fully-vectorized
tree learners: every depth level builds (node, feature, bin) histograms
over the whole dataset via ``ops.tree_hist`` — a blocked one-hot-matmul
formulation (Pallas kernel on TPU, restructured XLA matmul elsewhere)
that replaces the old giant scatter-add — so tree fitting is a single
jit-compiled program and forests fit under vmap.

Trees are complete binary trees in heap layout:
  split_feat/split_bin : (2^depth - 1,)  internal nodes
  leaf                 : (2^depth, C)    class scores / regression values

The fit functions take an ``impl`` parameter ("auto" | "kernel" |
"kernel_interpret" | "xla") forwarded to ``ops.tree_hist`` — the same
dispatch convention as ``ops.votes``.  It is a parameter of the fit
functions only: the learners call them at "auto".
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

NUM_BINS = 32
GBDT_LEARNING_RATE = 0.3


# ---------------------------------------------------------------------------
# Binning
# ---------------------------------------------------------------------------
def make_bins(X: np.ndarray, num_bins: int = NUM_BINS) -> np.ndarray:
    """Per-feature quantile bin edges: (F, num_bins - 1)."""
    qs = np.linspace(0, 100, num_bins + 1)[1:-1]
    return np.percentile(X, qs, axis=0).T.astype(np.float32)


def binize(X, edges) -> jnp.ndarray:
    """X: (N, F) -> int32 bins (N, F) in [0, num_bins).

    bin = #{edges e : x >= e} (edges sorted ascending), counted by
    comparing x with all B - 1 edges and summing; the compare fuses
    into the sum, so no (N, F, B) array is stored.  A binary search
    (``searchsorted``'s default) does O(log B) steps instead of O(B),
    but on the TPU it is a ``while`` loop of gathers, far slower than
    B - 1 vector compares at B = 32.  The comparator is searchsorted's
    own, so NaNs and signed zeros bin as the binary search bins them.
    """
    return jax.vmap(
        lambda col, e: jnp.searchsorted(e, col, side="right",
                                        method="compare_all"),
        in_axes=(1, 0), out_axes=1)(X, edges).astype(jnp.int32)


def _select(table, idx):
    """``table[..., idx]``: one entry of a small table's last axis.

    A compare of ``idx`` against the axis's K positions, a select and a
    sum.  On the TPU a gather is a serial lookup per row; across a
    static axis of a few dozen entries, this is a few vector ops.
    Exact: one term is kept and the others are zeros (a -0.0 entry
    reads +0.0).  ``idx`` broadcasts against ``table``'s leading axes.
    """
    hit = idx[..., None] == jnp.arange(table.shape[-1], dtype=idx.dtype)
    return jnp.sum(jnp.where(hit, table, jnp.zeros((), table.dtype)),
                   axis=-1)


def _route(node, xb, feat, thr):
    """One level down: each row goes right where its split feature's bin
    exceeds the split bin.  node: (N,) ids within the level; feat/thr:
    (2^level,) the level's split table."""
    go_right = _select(xb, _select(feat, node)) > _select(thr, node)
    return 2 * node + go_right.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Classification tree (gini)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("depth", "num_classes",
                                             "num_bins", "impl"))
def fit_tree_gini(xb, y, w, feat_mask, *, depth, num_classes,
                  num_bins=NUM_BINS, impl="auto"):
    """xb: (N, F) int32 bins; y: (N,) int32; w: (N,) f32 sample weights
    (bootstrap); feat_mask: (F,) f32 in {0,1}.  Returns tree arrays."""
    N, F = xb.shape
    C = num_classes
    n_internal = 2 ** depth - 1
    split_feat = jnp.zeros((n_internal,), jnp.int32)
    split_bin = jnp.zeros((n_internal,), jnp.int32)
    node = jnp.zeros((N,), jnp.int32)
    # class-masked sample weights: channel c holds w where y == c, so a
    # single tree_hist emits the (node, feature, bin, class) counts
    wc = jax.nn.one_hot(y, C, dtype=jnp.float32).T * w[None]       # (C, N)

    for level in range(depth):
        n_nodes = 2 ** level
        base = n_nodes - 1
        hist = ops.tree_hist(xb, node, wc, num_nodes=n_nodes,
                             num_bins=num_bins, impl=impl)
        hist = hist.transpose(1, 2, 3, 0)                 # (n, F, B, C)

        left = jnp.cumsum(hist, axis=2)                   # split at bin<=b
        total = left[:, :, -1:, :]
        right = total - left
        ln = left.sum(-1)                                  # (n,F,B)
        rn = right.sum(-1)
        gini_l = ln - (left ** 2).sum(-1) / jnp.maximum(ln, 1e-9)
        gini_r = rn - (right ** 2).sum(-1) / jnp.maximum(rn, 1e-9)
        score = -(gini_l + gini_r)                         # maximize
        # last bin => empty right split; mask it and masked features
        score = score.at[:, :, -1].set(-jnp.inf)
        score = jnp.where(feat_mask[None, :, None] > 0, score, -jnp.inf)

        flat_best = jnp.argmax(score.reshape(n_nodes, -1), axis=1)
        bf = (flat_best // num_bins).astype(jnp.int32)     # (n_nodes,)
        bb = (flat_best % num_bins).astype(jnp.int32)
        split_feat = jax.lax.dynamic_update_slice(split_feat, bf, (base,))
        split_bin = jax.lax.dynamic_update_slice(split_bin, bb, (base,))

        node = _route(node, xb, bf, bb)

    # leaves: class histograms
    leaf = ops.node_hist(node, wc, num_nodes=2 ** depth, impl=impl).T
    leaf = leaf / jnp.maximum(leaf.sum(-1, keepdims=True), 1e-9)
    return split_feat, split_bin, leaf


def tree_apply(tree, xb):
    """Returns per-sample leaf rows (N, C)."""
    split_feat, split_bin, leaf = tree
    N = xb.shape[0]
    depth = int(np.log2(leaf.shape[0]))
    node = jnp.zeros((N,), jnp.int32)
    for level in range(depth):
        base, n_nodes = 2 ** level - 1, 2 ** level
        node = _route(node, xb, split_feat[base:base + n_nodes],
                      split_bin[base:base + n_nodes])
    return _select(leaf.T, node[:, None])


# ---------------------------------------------------------------------------
# Random forest
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("depth", "num_classes",
                                             "num_bins", "impl"))
def fit_forest(xb, y, w, fm, *, depth, num_classes, num_bins=NUM_BINS,
               impl="auto"):
    """One forest: vmap of fit_tree_gini over the tree axis.
    w: (T, N) per-tree sample weights; fm: (T, F) feature masks."""
    fit_one = functools.partial(fit_tree_gini, depth=depth,
                                num_classes=num_classes, num_bins=num_bins,
                                impl=impl)
    return jax.vmap(lambda wi, fi: fit_one(xb, y, wi, fi))(w, fm)


@functools.partial(jax.jit, static_argnames=("depth", "num_classes",
                                             "num_bins", "impl"))
def fit_forest_stacked(X, edges, y, w, fm, *, depth, num_classes,
                       num_bins=NUM_BINS, impl="auto"):
    """k forests as one batched fit.  X: (k, M, F) f32 rows padded to a
    shared bucket M; edges: (k, F, num_bins-1); y: (k, M); w: (k, T, M);
    fm: (k, T, F).  Padding rows ride at w == 0: every histogram and
    leaf build sees only exact zeros for them, so each stacked tree is
    bit-identical to its serial fit regardless of bucket size."""

    def fit_one_forest(Xi, ei, yi, wi, fi):
        return fit_forest(binize(Xi, ei), yi, wi, fi, depth=depth,
                          num_classes=num_classes, num_bins=num_bins,
                          impl=impl)

    return jax.vmap(fit_one_forest)(X, edges, y, w, fm)


def _forest_probs(forest, xb):
    probs = jax.vmap(lambda t: tree_apply(t, xb))(forest)      # (T,N,C)
    return probs.mean(0)


@jax.jit
def predict_forest_stacked(forests, X, edges):
    """(k,) stacked forests on one shared X -> (k, N) int32 labels."""

    def one(forest, e):
        return jnp.argmax(_forest_probs(forest, binize(X, e)),
                          axis=-1).astype(jnp.int32)

    return jax.vmap(one)(forests, edges)


@dataclass(frozen=True)
class RandomForest:
    """A forest's bootstrap draw; the fit is ``fit_forest_stacked``."""
    num_trees: int = 20
    feature_frac: float = 0.7

    @functools.partial(jax.jit, static_argnums=(0, 2, 3))
    def bootstrap(self, key, N, F):
        """Per-tree bootstrap weights (T, N) and feature masks (T, F).
        Drawn at the TRUE dataset size N, so a draw never depends on a
        bucket and key usage matches ``fit`` split-for-split."""
        kb, kf = jax.random.split(key)
        # bootstrap via draw-with-replacement counts as sample weights
        # (multinomial(N, uniform) == histogram of N uniform draws)
        idx = jax.random.randint(kb, (self.num_trees, N), 0, N)
        w = jax.vmap(lambda r: jnp.bincount(r, length=N))(idx).astype(
            jnp.float32)
        fm = (jax.random.uniform(kf, (self.num_trees, F))
              < self.feature_frac).astype(jnp.float32)
        fm = jnp.maximum(fm, jnp.zeros_like(fm).at[:, 0].set(1.0))
        return w, fm

    @functools.partial(jax.jit, static_argnums=(0, 2, 3, 4))
    def bootstrap_stacked(self, keys, sizes, bucket, F):
        """k members' draws as one program: member i is ``bootstrap(
        keys[i], sizes[i], F)``, its weights zero-padded on the device
        to (T, bucket).  Returns w (k, T, bucket) and fm (k, T, F);
        one compile per (sizes, bucket, F)."""
        w, fm = zip(*(self.bootstrap(keys[i], n, F)
                      for i, n in enumerate(sizes)))
        w = [jnp.pad(w_i, ((0, 0), (0, bucket - n)))
             for w_i, n in zip(w, sizes)]
        return jnp.stack(w), jnp.stack(fm)


# ---------------------------------------------------------------------------
# GBDT (binary, logistic loss, XGBoost-style gains)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("depth", "num_bins", "impl"))
def fit_tree_gh(xb, g, h, *, depth, num_bins=NUM_BINS, lam=1.0,
                impl="auto"):
    """Regression tree on gradients/hessians.  Returns tree arrays with
    scalar leaves (2^depth, 1)."""
    N, F = xb.shape
    n_internal = 2 ** depth - 1
    split_feat = jnp.zeros((n_internal,), jnp.int32)
    split_bin = jnp.zeros((n_internal,), jnp.int32)
    node = jnp.zeros((N,), jnp.int32)
    gh_w = jnp.stack([g, h])                                   # (2, N)

    for level in range(depth):
        n_nodes = 2 ** level
        base = n_nodes - 1
        gh = ops.tree_hist(xb, node, gh_w, num_nodes=n_nodes,
                           num_bins=num_bins, impl=impl)   # (2, n, F, B)
        G, H = gh[0], gh[1]
        GL, HL = jnp.cumsum(G, 2), jnp.cumsum(H, 2)
        GT, HT = GL[:, :, -1:], HL[:, :, -1:]
        GR, HR = GT - GL, HT - HL
        gain = GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam) \
            - GT ** 2 / (HT + lam)
        gain = gain.at[:, :, -1].set(-jnp.inf)

        flat_best = jnp.argmax(gain.reshape(n_nodes, -1), axis=1)
        bf = (flat_best // num_bins).astype(jnp.int32)
        bb = (flat_best % num_bins).astype(jnp.int32)
        split_feat = jax.lax.dynamic_update_slice(split_feat, bf, (base,))
        split_bin = jax.lax.dynamic_update_slice(split_bin, bb, (base,))
        node = _route(node, xb, bf, bb)

    GHs = ops.node_hist(node, gh_w, num_nodes=2 ** depth, impl=impl)
    leaf = (-GHs[0] / (GHs[1] + lam))[:, None]
    return split_feat, split_bin, leaf


@functools.partial(jax.jit, static_argnames=("num_rounds", "depth",
                                             "num_bins", "impl"))
def fit_gbdt(xb, y, w, lr, *, num_rounds, depth, num_bins=NUM_BINS,
             impl="auto"):
    """Full boosting loop as ONE jitted lax.scan over rounds (the former
    Python loop re-dispatched an un-jitted ``tree_apply`` every round).

    w: (N,) f32 masks the gradients/hessians — rows padded into a shared
    bucket ride at w == 0 and contribute exact zeros to every G/H
    histogram and leaf sum, so padding never changes a split or leaf."""
    yf = y.astype(jnp.float32)

    def boost_round(logits, _):
        p = jax.nn.sigmoid(logits)
        tree = fit_tree_gh(xb, (p - yf) * w, (p * (1.0 - p)) * w,
                           depth=depth, num_bins=num_bins, impl=impl)
        logits = logits + lr * tree_apply(tree, xb)[:, 0]
        return logits, tree

    _, trees = jax.lax.scan(boost_round,
                            jnp.zeros((xb.shape[0],), jnp.float32),
                            None, length=num_rounds)
    return trees                       # leaves stacked over rounds (R, ...)


@functools.partial(jax.jit, static_argnames=("num_rounds", "depth",
                                             "num_bins", "impl"))
def fit_gbdt_stacked(X, edges, y, w, lr, *, num_rounds, depth,
                     num_bins=NUM_BINS, impl="auto"):
    """k GBDTs as one batched fit.  X: (k, M, F) rows padded to a shared
    bucket; edges: (k, F, num_bins-1); y: (k, M); w: (k, M) zero on
    padding rows (see fit_gbdt)."""

    def one(Xi, ei, yi, wi):
        return fit_gbdt(binize(Xi, ei), yi, wi, lr, num_rounds=num_rounds,
                        depth=depth, num_bins=num_bins, impl=impl)

    return jax.vmap(one)(X, edges, y, w)


def _gbdt_logits(trees, xb, lr):
    vals = jax.vmap(lambda t: tree_apply(t, xb)[:, 0])(trees)
    return lr * vals.sum(0)


@jax.jit
def predict_gbdt_stacked(trees, X, edges, lr):
    """(k,) stacked GBDTs on one shared X -> (k, N) int32 labels."""

    def one(ti, ei):
        return (_gbdt_logits(ti, binize(X, ei), lr) > 0).astype(jnp.int32)

    return jax.vmap(one)(trees, edges)
