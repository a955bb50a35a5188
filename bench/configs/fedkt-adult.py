"""Plain reference of one FedKT round (IJCAI'21, Li, He and Song,
Algorithm 1) for the ``fedkt-adult`` deployment.  Imports nothing of
the program under test: histogram trees in numpy, the MLP in
``jax.numpy`` at the highest matmul precision on the host CPU; the rows
and their Dirichlet split over the silos come from the benchmark's own
copies (``bench/fedkt_data.py``).

What it states, stage by stage, for each silo i with key schedule
  key_0 = PRNGKey(seed); key_{i+1} = key_i split s*(t+2) times
  (per partition j: t teacher splits, one vote split, one student split)
and the coordinator continuing from key_n (one split per vote domain,
then one for the final fit):

  1. teachers: t learners per partition j, each fit on one of t disjoint
     subsets of a seeded shuffle of the silo's rows
     (``np.random.default_rng(seed + 17 i)``, one permutation per
     partition, ``np.array_split`` into t parts);
  2. party vote: per query, argmax(vote counts + Laplace(1/gamma)),
     noise drawn by inverse CDF from the partition's vote key;
  3. students: s learners fit on the queries and the party labels;
  4. coordinator fold: a silo adds s votes for class m where all its s
     students predict m; the label is the argmax of the sum;
  5. final student: an MLP fit on the queries and the coordinator's
     labels.

Learners, as the configuration states them:
  rf   20 gini trees of depth 6 over 32 quantile bins, bootstrap weights
       (N draws with replacement), each feature kept with p = 0.7
       (feature 0 always); predict by the mean of leaf class shares;
  gbdt 20 rounds of depth-6 logistic trees, XGBoost gains with
       lambda 1, learning rate 0.3;
  nn   MLP 14-16-16-2 (ReLU), N(0, 1/fan_in) weights, zero biases,
       100 AdamW steps (lr 1e-3, betas 0.9/0.999, eps 1e-8, decay
       1e-6) on batches of 64 rows drawn with replacement.

Stated as the program states its own sampling: an MLP draws its batch
indices over its rows padded to a power of two (at least 32; a silo's
teachers share the largest teacher's size) with padding given
probability 0, which is the same distribution over real rows.

``precision`` runs every stage in float32 (the reference) or rounds
every stored value to bfloat16 (the control of ``correct``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

import fedkt_data

NUM_BINS = 32
RF_FEATURE_FRAC = 0.7
GBDT_LR = 0.3
GBDT_LAMBDA = 1.0
NN_BATCH = 64
NN_LR = 1e-3
NN_DECAY = 1e-6


def _round_fn(precision):
    if precision == "float32":
        return lambda x: np.asarray(x, np.float32)
    if precision == "bfloat16":
        return lambda x: np.asarray(x, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(precision)


# ---------------------------------------------------------------------------
# Key schedule and data plumbing
# ---------------------------------------------------------------------------
def split(key):
    key, sub = jax.random.split(key)
    return key, sub


def party_keys(seed, n, s, t):
    """Per silo: (teacher keys [s*t], vote keys [s], student keys [s]);
    plus the coordinator's key."""
    key = jax.random.PRNGKey(seed)
    out = []
    for _ in range(n):
        start = key
        tk, vk, sk = [], [], []
        k = start
        for _ in range(s):
            for _ in range(t):
                k, sub = split(k)
                tk.append(sub)
            k, sub = split(k)
            vk.append(sub)
            k, sub = split(k)
            sk.append(sub)
        out.append((tk, vk, sk))
        key = k
    return out, key


def subsets(indices, s, t, seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(s):
        perm = rng.permutation(indices)
        out.append([np.sort(a) for a in np.array_split(perm, t)])
    return out


def pow2(n, lo=32):
    return max(lo, 1 << (n - 1).bit_length())


# ---------------------------------------------------------------------------
# Histogram trees (numpy)
# ---------------------------------------------------------------------------
def make_bins(X):
    qs = np.linspace(0, 100, NUM_BINS + 1)[1:-1]
    return np.percentile(X, qs, axis=0).T.astype(np.float32)


def binize(X, edges):
    return np.stack([np.searchsorted(edges[f], X[:, f], side="right")
                     for f in range(X.shape[1])], axis=1).astype(np.int64)


def _hist(xb, node, w, n_nodes):
    """(K, n_nodes, F, B) sums of each weight channel of w (K, N)."""
    N, F = xb.shape
    idx = (node[:, None] * F + np.arange(F)[None]) * NUM_BINS + xb
    return np.stack([
        np.bincount(idx.ravel(), weights=np.repeat(wk, F),
                    minlength=n_nodes * F * NUM_BINS)
        .reshape(n_nodes, F, NUM_BINS) for wk in w]).astype(np.float32)


def _route(xb, node, bf, bb):
    return 2 * node + (xb[np.arange(len(node)), bf[node]]
                       > bb[node]).astype(np.int64)


def _best(score):
    n = score.shape[0]
    flat = np.argmax(score.reshape(n, -1), axis=1)
    return flat // NUM_BINS, flat % NUM_BINS


def fit_gini(xb, y, w, fmask, depth, C, rnd):
    N, F = xb.shape
    node = np.zeros(N, np.int64)
    wc = np.stack([rnd(np.where(y == c, w, 0.0)) for c in range(C)])
    sf, sb = [], []
    with np.errstate(divide="ignore", invalid="ignore"):
        for level in range(depth):
            n = 2 ** level
            h = rnd(_hist(xb, node, wc, n)).transpose(1, 2, 3, 0)
            left = rnd(np.cumsum(h, axis=2))
            right = rnd(left[:, :, -1:, :] - left)
            ln, rn = rnd(left.sum(-1)), rnd(right.sum(-1))
            gl = rnd(ln - rnd(rnd(left ** 2).sum(-1))
                     / np.maximum(ln, np.float32(1e-9)))
            gr = rnd(rn - rnd(rnd(right ** 2).sum(-1))
                     / np.maximum(rn, np.float32(1e-9)))
            score = rnd(-(gl + gr))
            score[:, :, -1] = -np.inf
            score[:, fmask == 0, :] = -np.inf
            bf, bb = _best(score)
            sf.append(bf)
            sb.append(bb)
            node = _route(xb, node, bf, bb)
        leaf = rnd(_hist(xb[:, :1] * 0, node, wc, 2 ** depth)[:, :, 0, 0].T)
        leaf = rnd(leaf / np.maximum(leaf.sum(-1, keepdims=True),
                                     np.float32(1e-9)))
    return (np.concatenate(sf).astype(np.int32),
            np.concatenate(sb).astype(np.int32), leaf)


def fit_gh(xb, g, h, depth, rnd):
    N, F = xb.shape
    node = np.zeros(N, np.int64)
    gh = np.stack([g, h])
    sf, sb = [], []
    lam = np.float32(GBDT_LAMBDA)
    for level in range(depth):
        n = 2 ** level
        hist = rnd(_hist(xb, node, gh, n))
        GL, HL = rnd(np.cumsum(hist[0], 2)), rnd(np.cumsum(hist[1], 2))
        GT, HT = GL[:, :, -1:], HL[:, :, -1:]
        GR, HR = rnd(GT - GL), rnd(HT - HL)
        gain = rnd(rnd(GL ** 2 / (HL + lam)) + rnd(GR ** 2 / (HR + lam))
                   - rnd(GT ** 2 / (HT + lam)))
        gain[:, :, -1] = -np.inf
        bf, bb = _best(gain)
        sf.append(bf)
        sb.append(bb)
        node = _route(xb, node, bf, bb)
    tot = rnd(_hist(xb[:, :1] * 0, node, gh, 2 ** depth)[:, :, 0, 0])
    leaf = rnd(-tot[0] / (tot[1] + lam))[:, None]
    return (np.concatenate(sf).astype(np.int32),
            np.concatenate(sb).astype(np.int32), leaf)


def tree_leaf(tree, xb):
    sf, sb, leaf = (np.asarray(a) for a in tree)
    depth = int(np.log2(leaf.shape[0]))
    node = np.zeros(len(xb), np.int64)
    for level in range(depth):
        base = 2 ** level - 1
        f, b = sf[base + node], sb[base + node]
        node = 2 * node + (xb[np.arange(len(xb)), f] > b).astype(np.int64)
    return leaf[node]


def fit_rf(key, X, y, *, num_trees, depth, C, rnd):
    X = rnd(X)
    edges = rnd(make_bins(X))
    xb = binize(X, edges)
    N, F = xb.shape
    kb, kf = jax.random.split(key)
    idx = np.asarray(jax.random.randint(kb, (num_trees, N), 0, N))
    w = np.stack([np.bincount(r, minlength=N) for r in idx]).astype(
        np.float32)
    fm = (np.asarray(jax.random.uniform(kf, (num_trees, F)))
          < RF_FEATURE_FRAC).astype(np.float32)
    fm[:, 0] = 1.0
    trees = [fit_gini(xb, y, w[i], fm[i], depth, C, rnd)
             for i in range(num_trees)]
    forest = tuple(np.stack(a) for a in zip(*trees))
    return (forest, edges)


def predict_rf(state, X, rnd):
    forest, edges = state
    edges = np.asarray(edges)
    xb = binize(rnd(np.asarray(X, np.float32)), edges)
    sf, sb, leaf = (np.asarray(a) for a in forest)
    probs = np.stack([tree_leaf((sf[i], sb[i], leaf[i]), xb)
                      for i in range(len(sf))])
    return np.argmax(rnd(probs.mean(0)), -1).astype(np.int32)


def fit_gbdt(key, X, y, *, num_rounds, depth, rnd):
    del key                               # deterministic learner
    X = rnd(X)
    edges = rnd(make_bins(X))
    xb = binize(X, edges)
    yf = y.astype(np.float32)
    logits = np.zeros(len(X), np.float32)
    lr = np.float32(GBDT_LR)
    trees = []
    for _ in range(num_rounds):
        p = rnd(1.0 / (1.0 + np.exp(-logits)))
        tree = fit_gh(xb, rnd(p - yf), rnd(p * (1.0 - p)), depth, rnd)
        trees.append(tree)
        logits = rnd(logits + lr * tree_leaf(tree, xb)[:, 0])
    return (tuple(np.stack(a) for a in zip(*trees)), edges)


def predict_gbdt(state, X, rnd):
    trees, edges = state
    xb = binize(rnd(np.asarray(X, np.float32)), np.asarray(edges))
    sf, sb, leaf = (np.asarray(a) for a in trees)
    vals = np.stack([tree_leaf((sf[i], sb[i], leaf[i]), xb)[:, 0]
                     for i in range(len(sf))])
    return (rnd(np.float32(GBDT_LR) * vals.sum(0)) > 0).astype(np.int32)


# ---------------------------------------------------------------------------
# MLP (jax.numpy on the host CPU)
# ---------------------------------------------------------------------------
def _cpu():
    return jax.devices("cpu")[0]


def mlp_init(key, sizes, dtype):
    keys = jax.random.split(key, len(sizes) - 1)
    p = {}
    for i, (k, a, b) in enumerate(zip(keys, sizes[:-1], sizes[1:])):
        kw, _ = jax.random.split(k)
        p[f"l{i + 1}"] = {
            "w": (jax.random.normal(kw, (a, b)) * a ** -0.5).astype(dtype),
            "b": jnp.zeros((b,), dtype)}
    return p


def mlp_apply(p, x):
    hp = jax.lax.Precision.HIGHEST
    h = jax.nn.relu(jnp.dot(x, p["l1"]["w"], precision=hp) + p["l1"]["b"])
    h = jax.nn.relu(jnp.dot(h, p["l2"]["w"], precision=hp) + p["l2"]["b"])
    return jnp.dot(h, p["l3"]["w"], precision=hp) + p["l3"]["b"]


@functools.partial(jax.jit, static_argnames=("sizes", "steps", "dtype"))
def _fit_mlp(key, X, y, mask, *, sizes, steps, dtype):
    dt = jnp.dtype(dtype)
    params = mlp_init(jax.random.fold_in(key, 1), sizes, dt)
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    X = X.astype(dt)
    p_sel = mask / mask.sum()

    def loss(p, xb, yb):
        logp = jax.nn.log_softmax(mlp_apply(p, xb).astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))

    def step(carry, k):
        p, m, v, i = carry
        idx = jax.random.choice(k, X.shape[0], (NN_BATCH,), p=p_sel)
        g = jax.grad(loss)(p, X[idx], y[idx])
        i = i + 1
        t = i.astype(jnp.float32)

        def upd(g, m, v, p):
            gf = g.astype(jnp.float32)
            m = 0.9 * m + 0.1 * gf
            v = 0.999 * v + 0.001 * gf * gf
            mh = m / (1 - 0.9 ** t)
            vh = v / (1 - 0.999 ** t)
            d = mh / (jnp.sqrt(vh) + 1e-8) + NN_DECAY * p.astype(
                jnp.float32)
            return (p.astype(jnp.float32) - NN_LR * d).astype(dt), m, v

        out = jax.tree.map(upd, g, m, v, p)
        pick = functools.partial(jax.tree.map, is_leaf=lambda x:
                                 isinstance(x, tuple))
        return (pick(lambda o: o[0], out), pick(lambda o: o[1], out),
                pick(lambda o: o[2], out), i), None

    keys = jax.random.split(jax.random.fold_in(key, 2), steps)
    (params, _, _, _), _ = jax.lax.scan(step, (params, m, v, jnp.int32(0)),
                                        keys)
    return params


def fit_nn(key, X, y, *, hidden, steps, bucket, precision):
    with jax.default_device(_cpu()):
        n = len(X)
        Xp = np.zeros((bucket, X.shape[1]), np.float32)
        Xp[:n] = X
        yp = np.zeros(bucket, np.int32)
        yp[:n] = y
        mask = np.zeros(bucket, np.float32)
        mask[:n] = 1.0
        sizes = (X.shape[1], hidden, hidden, 2)
        p = _fit_mlp(jax.device_put(key, _cpu()), Xp, yp, mask,
                     sizes=sizes, steps=steps, dtype=precision)
        return jax.tree.map(np.asarray, p)


def predict_nn(state, X, precision):
    with jax.default_device(_cpu()):
        dt = jnp.dtype(precision)
        p = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)).astype(dt),
                         state)
        out = mlp_apply(p, jnp.asarray(X).astype(dt))
        return np.asarray(jnp.argmax(out.astype(jnp.float32), -1),
                          np.int32)


# ---------------------------------------------------------------------------
# Learners by kind
# ---------------------------------------------------------------------------
class Learners:
    def __init__(self, sizes, precision="float32"):
        self.sz = sizes
        self.precision = precision
        self.rnd = _round_fn(precision)

    def fit(self, kind, key, X, y, bucket):
        sz = self.sz
        if kind == "rf":
            return fit_rf(key, X, y, num_trees=sz["trees"],
                          depth=sz["depth"], C=2, rnd=self.rnd)
        if kind == "gbdt":
            return fit_gbdt(key, X, y, num_rounds=sz["trees"],
                            depth=sz["depth"], rnd=self.rnd)
        if kind == "nn":
            return fit_nn(key, X, y, hidden=sz["nn_hidden"],
                          steps=sz["nn_steps"], bucket=bucket,
                          precision=self.precision)
        raise ValueError(kind)

    def predict(self, kind, state, X):
        if kind == "rf":
            return predict_rf(state, X, self.rnd)
        if kind == "gbdt":
            return predict_gbdt(state, X, self.rnd)
        if kind == "nn":
            return predict_nn(state, X, self.precision)
        raise ValueError(kind)


def laplace(key, shape, scale):
    with jax.default_device(_cpu()):
        u = jax.random.uniform(jax.device_put(key, _cpu()), shape,
                               minval=-0.5, maxval=0.5)
        u = jnp.clip(u, -0.5 + 1e-7, 0.5 - 1e-7)
        return np.asarray(-scale * jnp.sign(u) * jnp.log1p(-2.0 * jnp.abs(u)))


def vote_counts(preds, C=2):
    """(k, T) predictions -> (T, C) counts."""
    return np.stack([(preds == c).sum(0) for c in range(C)], 1)


def noisy_vote(preds, key, gamma):
    counts = vote_counts(preds).astype(np.float32)
    if gamma > 0:
        counts = counts + laplace(key, counts.shape, 1.0 / gamma)
    return np.argmax(counts, -1).astype(np.int32)


def consistent_vote(student_preds):
    """[per silo (s, T)] -> coordinator labels (T,)."""
    total = 0
    for sp in student_preds:
        s = sp.shape[0]
        agree = np.all(sp == sp[0][None], 0)
        onehot = np.stack([sp[0] == c for c in range(2)], 1)
        total = total + s * onehot * agree[:, None]
    return np.argmax(total.astype(np.float32), -1).astype(np.int32)


# ---------------------------------------------------------------------------
# A whole round, as the program's answers would be
# ---------------------------------------------------------------------------
class RoundInputs:
    """What a round is made of: the deployment's rows and their split
    over the silos (from the configuration's ``deployment_seed``), the
    learner kinds and sizes, and the run's seed."""

    def __init__(self, cfg, kinds, seed):
        self.data = fedkt_data.data(cfg)
        self.party_indices = fedkt_data.silo_rows(cfg, self.data["y_train"])
        self.kinds = kinds
        self.sz = sizes = cfg["sizes"]
        self.seed = seed
        s, t = sizes["partitions"], sizes["subsets"]
        self.keys, self.server_key = party_keys(seed, len(kinds), s, t)
        # query budgets under L2: silos answer every public query
        self.Xq = self.data["X_public"]

    def teacher_sets(self, i):
        s, t = self.sz["partitions"], self.sz["subsets"]
        plan = subsets(self.party_indices[i], s, t, self.seed + 17 * i)
        X, y = self.data["X_train"], self.data["y_train"]
        return [(X[sub], y[sub]) for j in range(s) for sub in plan[j]]

    def final_key(self):
        key, _ = split(self.server_key)      # the one vote domain
        _, kk = split(key)
        return kk


def party_labels(inp, lrn, i):
    """Stage 1-2 for silo i: [s] label vectors over the queries."""
    s, t = inp.sz["partitions"], inp.sz["subsets"]
    tk, vk, _ = inp.keys[i]
    kind = inp.kinds[i]
    sets = inp.teacher_sets(i)
    bucket = max(pow2(len(X)) for X, _ in sets)
    out = []
    for j in range(s):
        preds = np.stack([
            lrn.predict(kind, lrn.fit(kind, tk[j * t + a], *sets[j * t + a],
                                      bucket), inp.Xq)
            for a in range(t)])
        out.append(noisy_vote(preds, vk[j], inp.sz["gamma"]))
    return out


def fit_students(inp, lrn, i, labelsets):
    _, _, sk = inp.keys[i]
    kind = inp.kinds[i]
    return [lrn.fit(kind, sk[j], inp.Xq, labelsets[j], pow2(len(inp.Xq)))
            for j in range(len(labelsets))]


def fit_final(inp, lrn, labels):
    return lrn.fit("nn", inp.final_key(), inp.Xq, labels,
                   pow2(len(inp.Xq)))


def play_round(inp, precision):
    """The control: every answer of the round from this reference at
    ``precision``, in the program's place."""
    lrn = Learners(inp.sz, precision)
    labels = {i: party_labels(inp, lrn, i) for i in range(len(inp.kinds))}
    students = {i: fit_students(inp, lrn, i, labels[i])
                for i in range(len(inp.kinds))}
    preds = [np.stack([lrn.predict(inp.kinds[i], st, inp.Xq)
                       for st in students[i]]) for i in students]
    server = consistent_vote(preds)
    final = fit_final(inp, lrn, server)
    return {"party_labels": labels, "students": students,
            "server_labels": server, "final": final}


def compare(inp, answers):
    """Each stage of ``answers`` against the float32 reference, the
    stage's inputs taken from the answers themselves (so a difference
    in one stage does not carry into the next).  Returns the share of
    answers that differ, per stage."""
    lrn = Learners(inp.sz, "float32")
    n = len(inp.kinds)
    diff = {}
    mism = total = 0
    for i in range(n):
        ref = party_labels(inp, lrn, i)
        for a, b in zip(ref, answers["party_labels"][i]):
            mism += int(np.sum(a != np.asarray(b)))
            total += a.size
    diff["party_labels"] = mism / total

    mism = total = 0
    fwd = []
    for i in range(n):
        ref_students = fit_students(inp, lrn, i, answers["party_labels"][i])
        kind = inp.kinds[i]
        want = np.stack([lrn.predict(kind, st, inp.Xq)
                         for st in ref_students])
        if i in answers["students"]:
            got = np.stack([lrn.predict(kind, st, inp.Xq)
                            for st in answers["students"][i]])
        else:       # a silo the coordinator never folded: all missing
            got = np.full_like(want, -1)
        fwd.append(got if i in answers["students"] else want)
        mism += int(np.sum(got != want))
        total += got.size
    diff["student_preds"] = mism / total

    server = np.asarray(answers["server_labels"])
    diff["server_labels"] = float(np.mean(consistent_vote(fwd) != server))

    X_test = inp.data["X_test"]
    ref_final = fit_final(inp, lrn, server)
    got = lrn.predict("nn", answers["final"], X_test)
    want = lrn.predict("nn", ref_final, X_test)
    diff["final_preds"] = float(np.mean(got != want))
    return diff
