"""JAX tree learners: correctness on separable data, GBDT improvement,
and routing and binning exactly equal to a plain numpy reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import trees as T
from repro.core.learners import GBDTLearner, RFLearner, accuracy


def _separable(n=600, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, f)).astype(np.float32)
    y = ((X[:, 0] > 0.2) ^ (X[:, 1] < -0.1)).astype(np.int32)
    return X, y


def test_single_tree_fits_axis_aligned():
    X, y = _separable()
    edges = jnp.asarray(T.make_bins(X))
    xb = T.binize(jnp.asarray(X), edges)
    tree = T.fit_tree_gini(xb, jnp.asarray(y), jnp.ones(len(y)),
                           jnp.ones(X.shape[1]), depth=4, num_classes=2)
    preds = jnp.argmax(T.tree_apply(tree, xb), -1)
    assert (np.asarray(preds) == y).mean() > 0.95


def test_random_forest_learner():
    X, y = _separable(seed=1)
    rf = RFLearner(num_classes=2, num_trees=8, depth=4)
    st = rf.fit(jax.random.PRNGKey(0), X[:400], y[:400])
    assert accuracy(rf, st, X[400:], y[400:]) > 0.9


def test_gbdt_improves_with_rounds():
    X, y = _separable(seed=2)
    accs = []
    for rounds in (2, 20):
        gb = GBDTLearner(num_rounds=rounds, depth=3)
        st = gb.fit(jax.random.PRNGKey(0), X[:400], y[:400])
        accs.append(accuracy(gb, st, X[400:], y[400:]))
    assert accs[1] >= accs[0]
    assert accs[1] > 0.9


def test_stacked_tree_fits_bit_identical_to_serial():
    """Zero-weight padding into a shared pow2 bucket: stacked RF/GBDT
    states equal the serial loop EXACTLY, even when dataset sizes (and
    hence individual buckets) differ — histograms ignore w == 0 rows."""
    rng = np.random.default_rng(3)
    sizes = (40, 70, 130)                # pow2 buckets 64, 128, 256
    Xs = [rng.normal(0, 1, (n, 6)).astype(np.float32) for n in sizes]
    ys = [((X[:, 0] > 0).astype(np.int32) ^ (X[:, 1] < 0)).astype(np.int32)
          for X in Xs]
    keys = jax.random.split(jax.random.PRNGKey(5), len(sizes))
    Xq = rng.normal(0, 1, (33, 6)).astype(np.float32)

    for learner in (RFLearner(num_classes=2, num_trees=6, depth=4),
                    GBDTLearner(num_rounds=8, depth=3)):
        stacked = learner.fit_stacked(keys, Xs, ys)
        preds = np.asarray(learner.predict_stacked(stacked, Xq))
        for i in range(len(sizes)):
            serial = learner.fit(keys[i], Xs[i], ys[i])
            sliced = jax.tree.map(lambda leaf: leaf[i], stacked)
            for a, b in zip(jax.tree.leaves(serial),
                            jax.tree.leaves(sliced)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            row = np.asarray(learner.predict(sliced, Xq))
            np.testing.assert_array_equal(preds[i], row)


@pytest.mark.parametrize("sizes,bucket", [
    ((40, 70, 130), 256),
    ((1342, 1341, 1341, 1342, 1341, 1341), 2048),   # a forest silo's teachers
    ((6105, 6105), 8192),                           # its two students
    ((64, 256), 256),                               # a member fills the bucket
])
def test_forest_bootstrap_stacked_matches_padded_serial_draws(sizes, bucket):
    """The device-side draw-pad-stack equals, exactly, each member's own
    draw at its true size run op by op, pulled and zero-padded on the
    host; the jitted serial ``bootstrap`` equals it too."""
    rf = T.RandomForest(num_trees=20, depth=6, num_classes=2)
    F = 14
    keys = jax.random.split(jax.random.PRNGKey(2 ** 31 + 11), len(sizes))
    w, fm = rf.bootstrap_stacked(keys, sizes, bucket, F)
    assert w.shape == (len(sizes), 20, bucket) and fm.shape == (
        len(sizes), 20, F)
    for i, n in enumerate(sizes):
        with jax.disable_jit():
            w_ref, fm_ref = map(np.asarray, rf.bootstrap(keys[i], n, F))
        want = np.zeros((20, bucket), np.float32)
        want[:, :n] = w_ref
        np.testing.assert_array_equal(np.asarray(w[i]), want)
        np.testing.assert_array_equal(np.asarray(fm[i]), fm_ref)
        for got, ref in zip(rf.bootstrap(keys[i], n, F), (w_ref, fm_ref)):
            np.testing.assert_array_equal(np.asarray(got), ref)


class _HostOnlyNumpy:
    """numpy for the learners module, refusing device arrays: on the CPU
    a device array already lives in host memory, so the transfer guard
    lets ``np.asarray(device_array)`` through there unseen."""

    def __getattr__(self, name):
        attr = getattr(np, name)
        if isinstance(attr, type) or not callable(attr):
            return attr

        def refuse_device_arrays(*args, **kwargs):
            if any(isinstance(a, jax.Array) for a in args):
                raise AssertionError(f"np.{name} pulled a device array")
            return attr(*args, **kwargs)
        return refuse_device_arrays


@pytest.mark.parametrize("case", ["teachers", "students"])
def test_forest_fit_stacked_pulls_nothing_to_host(case, monkeypatch):
    """A stacked forest fit on host data makes no device-to-host
    transfer: the bootstrap weights are drawn and padded on the device.
    Teachers are members of unequal sizes; students share one X."""
    from repro.core import learners
    monkeypatch.setattr(learners, "np", _HostOnlyNumpy())
    rng = np.random.default_rng(7)
    sizes = (40, 70, 130) if case == "teachers" else (90, 90)
    X = rng.normal(0, 1, (max(sizes), 6)).astype(np.float32)
    Xs = [X[:n] for n in sizes]
    ys = [(Xi[:, 0] > 0).astype(np.int32) for Xi in Xs]
    if case == "students":
        ys[1] = 1 - ys[1]
    keys = jax.random.split(jax.random.PRNGKey(9), len(sizes))
    rf = RFLearner(num_classes=2, num_trees=6, depth=4)
    with jax.transfer_guard_device_to_host("disallow"):
        forest, edges = rf.fit_stacked(keys, Xs, ys)
    assert edges.shape[0] == len(sizes)
    assert jax.tree.leaves(forest)[0].shape[:2] == (len(sizes), 6)


def test_binize_matches_broadcast_compare():
    """binize == the O(N*F*B) broadcast-compare sum(X >= edges) and ==
    numpy's per-feature searchsorted, including ties ON edges and
    duplicate edges (constant features)."""
    rng = np.random.default_rng(7)
    X = rng.normal(0, 1, (257, 9)).astype(np.float32)
    X[:, -1] = 1.0                          # constant => duplicate edges
    edges = T.make_bins(X)
    # land some values exactly on edges to exercise the >= tie
    X[::5, 0] = edges[0, 3]
    X[1::7, 2] = edges[2, 30]
    Xj, ej = jnp.asarray(X), jnp.asarray(edges)
    old = jnp.sum(Xj[:, :, None] >= ej[None], axis=-1).astype(jnp.int32)
    new = T.binize(Xj, ej)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
    np.testing.assert_array_equal(np.asarray(new), _np_binize(X, edges))
    assert np.asarray(new).min() >= 0
    assert np.asarray(new).max() < T.NUM_BINS


# ---------------------------------------------------------------------------
# Routing and binning against a plain numpy reference: np.searchsorted per
# feature, per-row fancy indexing into the split tables and leaves.
# ---------------------------------------------------------------------------
B = T.NUM_BINS


def _np_binize(X, edges):
    return np.stack([np.searchsorted(edges[f], X[:, f], side="right")
                     for f in range(X.shape[1])], axis=1)


def _np_route(split_feat, split_bin, xb, depth):
    """Leaf id of every row, one level at a time by fancy indexing."""
    rows = np.arange(len(xb))
    node = np.zeros(len(xb), np.int64)
    for level in range(depth):
        at = 2 ** level - 1 + node
        go_right = xb[rows, split_feat[at]] > split_bin[at]
        node = 2 * node + go_right
    return node


def _table(rng, n, F, edges=None):
    """Rows whose last feature is constant (so its edges all coincide)
    when F > 1; with ``edges``, a quarter of the values sit exactly on
    one of their feature's edges."""
    X = rng.normal(0, 1, (n, F)).astype(np.float32)
    if F > 1:
        X[:, -1] = 0.5
    if edges is not None:
        on = edges[np.arange(F)[None], rng.integers(0, B - 1, (n, F))]
        hit = rng.random((n, F)) < 0.25
        X[hit] = on[hit]
    return X


def _random_trees(rng, lead, depth, F, C):
    """Trees of the given depth with random splits and dyadic leaves
    (sums of them are exact in float32, in any order)."""
    split_feat = rng.integers(0, F, lead + (2 ** depth - 1,)).astype(np.int32)
    split_bin = rng.integers(0, B, lead + (2 ** depth - 1,)).astype(np.int32)
    leaf = (rng.integers(-8, 9, lead + (2 ** depth, C)) / 8).astype(np.float32)
    return split_feat, split_bin, leaf


@pytest.mark.parametrize("F", [1, 6, 14, 33])
def test_binize_matches_numpy_searchsorted(F):
    rng = np.random.default_rng(F)
    edges = T.make_bins(_table(rng, 300, F))
    X = _table(rng, 500, F, edges)
    got = T.binize(jnp.asarray(X), jnp.asarray(edges))
    np.testing.assert_array_equal(np.asarray(got), _np_binize(X, edges))


@pytest.mark.parametrize("depth", [1, 3, 6, 8])
@pytest.mark.parametrize("F", [1, 6, 14, 33])
def test_tree_apply_matches_numpy_routing(depth, F):
    rng = np.random.default_rng(100 * depth + F)
    edges = T.make_bins(_table(rng, 300, F))
    xb = _np_binize(_table(rng, 700, F, edges), edges)
    sf, sb, leaf = _random_trees(rng, (), depth, F, 2)
    got = jax.jit(T.tree_apply)((jnp.asarray(sf), jnp.asarray(sb),
                                 jnp.asarray(leaf)), jnp.asarray(xb))
    np.testing.assert_array_equal(np.asarray(got),
                                  leaf[_np_route(sf, sb, xb, depth)])


@pytest.mark.parametrize("kind", ["forest", "gbdt"])
@pytest.mark.parametrize("depth,F", [(1, 1), (3, 6), (6, 14), (8, 33)])
def test_stacked_predicts_match_numpy_routing(kind, depth, F):
    """k stacked models on one shared query set: the labels equal the
    reference's, forest by the mean of its trees' leaf rows, GBDT by the
    sign of its rounds' summed leaves."""
    rng = np.random.default_rng(10 * depth + F)
    k, n_trees = 3, 5
    edges = np.stack([T.make_bins(_table(rng, 200, F)) for _ in range(k)])
    X = _table(rng, 611, F, edges[0])
    sf, sb, leaf = _random_trees(rng, (k, n_trees), depth, F,
                                 2 if kind == "forest" else 1)
    args = ((jnp.asarray(sf), jnp.asarray(sb), jnp.asarray(leaf)),
            jnp.asarray(X), jnp.asarray(edges))
    want = np.empty((k, len(X)), np.int32)
    for i in range(k):
        xb = _np_binize(X, edges[i])
        rows = np.stack([leaf[i, t][_np_route(sf[i, t], sb[i, t], xb, depth)]
                         for t in range(n_trees)])            # (T, N, C)
        if kind == "forest":
            want[i] = np.argmax(rows.mean(0), axis=-1)
        else:
            want[i] = rows.sum(0)[:, 0] > 0
    if kind == "forest":
        got = T.predict_forest_stacked(*args)
    else:
        got = T.predict_gbdt_stacked(*args, 0.3)
    np.testing.assert_array_equal(np.asarray(got), want)


def _np_fit(xb, wc, depth, score, feat_mask):
    """Histogram tree fit by numpy: (C, node, F, bin) sums of ``wc`` per
    level, ``score`` over them, rows routed by fancy indexing.  Weights
    are dyadic, so every sum is exact in float32 and the argmax is the
    program's."""
    N, F = xb.shape
    node = np.zeros(N, np.int64)
    split_feat, split_bin = [], []
    for level in range(depth):
        hist = np.zeros((len(wc), 2 ** level, F, B), np.float32)
        for f in range(F):
            np.add.at(hist, (slice(None), node, f, xb[:, f]), wc)
        s = score(hist)
        s[:, :, -1] = -np.inf
        s = np.where(feat_mask[None, :, None] > 0, s, -np.inf)
        best = np.argmax(s.reshape(2 ** level, -1), axis=1)
        bf, bb = best // B, best % B
        split_feat.append(bf)
        split_bin.append(bb)
        node = 2 * node + (xb[np.arange(N), bf[node]] > bb[node])
    return (np.concatenate(split_feat).astype(np.int32),
            np.concatenate(split_bin).astype(np.int32), node)


def _np_gini(hist):
    h = hist.transpose(1, 2, 3, 0)
    left = np.cumsum(h, axis=2)
    right = left[:, :, -1:] - left
    ln, rn = left.sum(-1), right.sum(-1)
    tiny = np.float32(1e-9)
    gl = ln - (left ** 2).sum(-1) / np.maximum(ln, tiny)
    gr = rn - (right ** 2).sum(-1) / np.maximum(rn, tiny)
    return -(gl + gr)


def _np_gain(hist, lam=np.float32(1.0)):
    GL, HL = np.cumsum(hist[0], 2), np.cumsum(hist[1], 2)
    GT, HT = GL[:, :, -1:], HL[:, :, -1:]
    GR, HR = GT - GL, HT - HL
    return GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam) - GT ** 2 / (HT + lam)


@pytest.mark.parametrize("kind", ["forest", "gbdt"])
@pytest.mark.parametrize("depth,F", [(1, 1), (3, 6), (6, 14)])
def test_stacked_fit_split_tables_match_numpy(kind, depth, F):
    """The stacked fits' split tables and leaves equal a numpy fit's:
    a row routed to the wrong node would move the next level's
    histograms.  Bootstrap counts (forest) and the first boosting
    round's g = p - y, h = p (1 - p) at p = 1/2 (GBDT) are dyadic, and
    zero-weight rows stand in for bucket padding."""
    rng = np.random.default_rng(1000 + 10 * depth + F)
    k, M, n_trees = 2, 256, 3
    Xs = [_table(rng, M, F) for _ in range(k)]
    edges = np.stack([T.make_bins(X[:200]) for X in Xs])
    Xs = [_table(rng, M, F, e) for e in edges]
    ys = [((X[:, 0] > 0) ^ (X[:, -1] > 0.7)).astype(np.int32) for X in Xs]
    xbs = [_np_binize(X, e) for X, e in zip(Xs, edges)]
    X, y = jnp.asarray(np.stack(Xs)), jnp.asarray(np.stack(ys))
    if kind == "forest":
        w = rng.integers(0, 3, (k, n_trees, M)).astype(np.float32)
        w[..., 200:] = 0
        fm = (rng.random((k, n_trees, F)) < 0.7).astype(np.float32)
        fm[..., 0] = 1
        sf, sb, leaf = T.fit_forest_stacked(
            X, jnp.asarray(edges), y, jnp.asarray(w), jnp.asarray(fm),
            depth=depth, num_classes=2, impl="xla")
        for i in range(k):
            for t in range(n_trees):
                wc = np.eye(2, dtype=np.float32)[ys[i]].T * w[i, t]
                rsf, rsb, node = _np_fit(xbs[i], wc, depth, _np_gini,
                                         fm[i, t])
                np.testing.assert_array_equal(np.asarray(sf[i, t]), rsf)
                np.testing.assert_array_equal(np.asarray(sb[i, t]), rsb)
                counts = np.zeros((2 ** depth, 2), np.float32)
                np.add.at(counts, node, wc.T)
                want = counts / np.maximum(counts.sum(-1, keepdims=True),
                                           np.float32(1e-9))
                np.testing.assert_array_equal(np.asarray(leaf[i, t]), want)
    else:
        w = np.ones((k, M), np.float32)
        w[:, 200:] = 0
        sf, sb, leaf = T.fit_gbdt_stacked(
            X, jnp.asarray(edges), y, jnp.asarray(w), 0.3, num_rounds=1,
            depth=depth, impl="xla")
        for i in range(k):
            g = (np.float32(0.5) - ys[i]) * w[i]
            gh = np.stack([g, np.float32(0.25) * w[i]]).astype(np.float32)
            rsf, rsb, node = _np_fit(xbs[i], gh, depth, _np_gain,
                                     np.ones(F, np.float32))
            np.testing.assert_array_equal(np.asarray(sf[i, 0]), rsf)
            np.testing.assert_array_equal(np.asarray(sb[i, 0]), rsb)
            G = np.zeros((2, 2 ** depth), np.float32)
            np.add.at(G, (slice(None), node), gh)
            want = (-G[0] / (G[1] + np.float32(1.0)))[:, None]
            np.testing.assert_array_equal(np.asarray(leaf[i, 0]), want)


def test_tree_fit_bench_smoke():
    """Tier-1 guard: the tree-fit benchmark runs end-to-end on its tiny
    config (scatter-vs-tree_hist parity asserts run inside)."""
    from benchmarks.tree_fit_bench import bench
    rec = bench(tiny=True, write=False)
    assert rec["hist_levels"] and rec["fits"]
    for row in rec["hist_levels"].values():
        assert row["tree_hist_ms"] > 0 and row["scatter_ms"] > 0
    for row in rec["fits"].values():
        assert row["warm_ms"] > 0


def test_forest_feature_mask_respected():
    """Trees never split on masked features."""
    X, y = _separable()
    edges = jnp.asarray(T.make_bins(X))
    xb = T.binize(jnp.asarray(X), edges)
    mask = jnp.zeros(X.shape[1]).at[0].set(1.0)   # only feature 0 allowed
    tree = T.fit_tree_gini(xb, jnp.asarray(y), jnp.ones(len(y)), mask,
                           depth=3, num_classes=2)
    assert (np.asarray(tree[0]) == 0).all()
