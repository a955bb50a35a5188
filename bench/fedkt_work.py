"""The counted work of one FedKT round of a ``fedkt`` configuration
(bench/counts.py), shared by the federation cells' metric readers.
Rows are counted at their true sizes: each silo's rows as the
deployment splits them (bench/fedkt_data.py), its teachers' subsets as
Algorithm 1 cuts them."""
from __future__ import annotations

import functools
import json

import numpy as np

import counts
import fedkt_data

# the jitted tree fits; tree_hist is the only kernel inside them
TREE_FIT_PROGRAMS = r"fit_forest|fit_gbdt"
NN_BATCH = 64
NUM_BINS = 32
NUM_CLASSES = 2


@functools.lru_cache(maxsize=None)
def _silo_rows(cfg_json):
    cfg = json.loads(cfg_json)
    y = fedkt_data.data(cfg)["y_train"]
    return tuple(len(ix) for ix in fedkt_data.silo_rows(cfg, y))


def _shape(ctx):
    """(sizes, learner kinds, [per silo: its teachers' row counts],
    query rows, test rows)."""
    sz = ctx.config["sizes"]
    kinds = ctx.workload["traffic"]["learners"]
    silos = _silo_rows(json.dumps(ctx.config, sort_keys=True))
    teachers = [[len(a) for a in np.array_split(np.arange(n),
                                                sz["subsets"])]
                * sz["partitions"] for n in silos]
    n_tr, n_q = int(sz["rows"] * 0.75), int(sz["rows"] * 0.125)
    return sz, kinds, teachers, n_q, sz["rows"] - n_tr - n_q


def _tree_fit(sz, n):
    return counts.tree_fit(n, sz["features"], NUM_CLASSES, sz["depth"],
                           NUM_BINS) * sz["trees"]


def hist_work(ctx):
    """Every histogram of every tree fit of the round: the silos'
    teachers on their subsets and their students on the queries."""
    sz, kinds, teachers, n_q, _ = _shape(ctx)
    total = counts.Work()
    for kind, rows in zip(kinds, teachers):
        if kind in ("rf", "gbdt"):
            for n in rows + [n_q] * sz["partitions"]:
                total = total + _tree_fit(sz, n)
    return total


def round_work(ctx):
    """All of a round's counted work: every fit (histograms and the
    routing of its rows through each tree, or the MLP's training
    steps), every prediction (teachers and students on the queries, the
    final student on the test rows) and every vote count."""
    sz, kinds, teachers, n_q, n_test = _shape(ctx)
    D, T, s = sz["depth"], sz["trees"], sz["partitions"]
    mlp = (sz["features"], sz["nn_hidden"], sz["nn_hidden"], NUM_CLASSES)
    nn_fit = counts.mlp_train(mlp, NN_BATCH, sz["nn_steps"])
    total = counts.Work()
    for kind, rows in zip(kinds, teachers):
        fits = rows + [n_q] * s                   # teachers, then students
        predicts = len(fits)                      # each on the queries
        if kind in ("rf", "gbdt"):
            k_out = NUM_CLASSES if kind == "rf" else 1
            for n in fits:
                total = total + _tree_fit(sz, n) + \
                    counts.tree_predict(n, D, NUM_CLASSES) * T
            total = total + counts.tree_predict(
                n_q, D, k_out, sz["features"]) * (T * predicts)
        else:
            total = total + nn_fit * len(fits) + \
                counts.mlp_predict(mlp, n_q) * predicts
        total = total + counts.votes(n_q, len(rows))       # party vote
    total = total + counts.votes(n_q, s * len(kinds))      # coordinator
    return total + nn_fit + counts.mlp_predict(mlp, n_test)   # final
