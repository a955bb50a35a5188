"""The rows of a ``fedkt`` deployment and how its silos hold them.

A deployment is one table and one split of it over the silos, both
drawn from the configuration's ``deployment_seed``, as the program's
``launch/federate.build_session`` draws its data and its partition from
one seed.  A run's own seed draws only the protocol's randomness
(teacher subsets, bootstrap and split draws, the vote's noise, the MLP
initialisations), so every run does the same work on the same rows.

Both functions are copies of the program's, kept here so that the
benchmark's inputs and its reference cannot change under it: the
system under test splits the rows with the program's own
``core/partition.dirichlet_partition``, and the reference and the
counted work with this copy.
"""
from __future__ import annotations

import numpy as np


def tabular_binary(n, num_features, seed, class_sep=1.2):
    """Adult-shaped binary task: four Gaussian clusters per class and a
    nonlinear flip region, split 75 / 12.5 / 12.5 into train, public
    queries and test.  A copy of the program's
    ``data/synthetic.tabular_binary``."""
    rng = np.random.default_rng(seed)
    n_clusters = 4
    means = rng.normal(0, 2.0, (2, n_clusters, num_features))
    y = rng.integers(0, 2, n)
    cl = rng.integers(0, n_clusters, n)
    X = means[y, cl] * class_sep + rng.normal(0, 1.0, (n, num_features))
    flip = (np.sin(X[:, 0]) * X[:, 1] > 1.5)
    y = np.where(flip, 1 - y, y).astype(np.int32)
    X = X.astype(np.float32)
    idx = rng.permutation(n)
    X, y = X[idx], y[idx]
    n_tr, n_pub = int(n * 0.75), int(n * 0.125)
    return {"X_train": X[:n_tr], "y_train": y[:n_tr],
            "X_public": X[n_tr:n_tr + n_pub],
            "y_public": y[n_tr:n_tr + n_pub],
            "X_test": X[n_tr + n_pub:], "y_test": y[n_tr + n_pub:]}


def dirichlet_partition(y, num_parties, beta, seed, min_size=2):
    """Label skew over silos: for each class k, p_k ~ Dir(beta) and silo
    j gets a p_kj share of the class's rows.  A copy of the program's
    ``core/partition.dirichlet_partition``."""
    rng = np.random.default_rng(seed)
    n_classes = int(y.max()) + 1
    for _ in range(100):
        party_idx = [[] for _ in range(num_parties)]
        for k in range(n_classes):
            idx_k = np.where(y == k)[0]
            rng.shuffle(idx_k)
            p = rng.dirichlet([beta] * num_parties)
            cuts = (np.cumsum(p) * len(idx_k)).astype(int)[:-1]
            for j, part in enumerate(np.split(idx_k, cuts)):
                party_idx[j].extend(part.tolist())
        if min(len(ix) for ix in party_idx) >= min_size:
            return [np.array(sorted(ix)) for ix in party_idx]
    raise RuntimeError("could not satisfy min_size partition")


def data(cfg):
    sz = cfg["sizes"]
    return tabular_binary(sz["rows"], sz["features"], cfg["deployment_seed"])


def silo_rows(cfg, y_train):
    sz = cfg["sizes"]
    return dirichlet_partition(y_train, sz["silos"], sz["beta"],
                               cfg["deployment_seed"])
