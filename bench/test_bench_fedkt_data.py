"""The federation deployment's rows and split: the benchmark's copies
agree with the program's, the split is fixed by the configuration, and
the counted work follows it."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
import common  # noqa: E402
import counts  # noqa: E402
import faults  # noqa: E402
import fedkt_data  # noqa: E402
import fedkt_work  # noqa: E402

CFG = common.config("fedkt-adult")


def test_copies_agree_with_the_program():
    from repro.core.partition import dirichlet_partition
    from repro.data.synthetic import tabular_binary
    sz = CFG["sizes"]
    ours = fedkt_data.data(CFG)
    theirs = tabular_binary(n=sz["rows"], num_features=sz["features"],
                            seed=CFG["deployment_seed"])
    for k in ours:
        np.testing.assert_array_equal(ours[k], theirs[k])
    want = dirichlet_partition(ours["y_train"], sz["silos"], sz["beta"],
                               CFG["deployment_seed"])
    got = fedkt_data.silo_rows(CFG, ours["y_train"])
    assert len(got) == len(want) == sz["silos"]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_split_is_label_skewed_and_covers_the_rows():
    y = fedkt_data.data(CFG)["y_train"]
    rows = fedkt_data.silo_rows(CFG, y)
    allrows = np.concatenate(rows)
    assert sorted(allrows.tolist()) == list(range(len(y)))
    shares = [float(np.mean(y[ix])) for ix in rows]
    assert max(shares) - min(shares) > 0.5      # Dirichlet(0.5) skew


def test_round_work_counts_every_fit_at_the_split_sizes():
    ctx = SimpleNamespace(config=CFG,
                          workload=common.workload("fedkt-adult.trees"))
    sz, kinds, teachers, n_q, n_test = fedkt_work._shape(ctx)
    y = fedkt_data.data(CFG)["y_train"]
    silos = [len(ix) for ix in fedkt_data.silo_rows(CFG, y)]
    assert [sum(t) for t in teachers] == [sz["partitions"] * n
                                          for n in silos]
    hist = fedkt_work.hist_work(ctx)
    total = fedkt_work.round_work(ctx)
    assert total.flops > hist.flops
    # the final student alone: its training steps and the test rows
    mlp = (sz["features"], sz["nn_hidden"], sz["nn_hidden"], 2)
    final = (counts.mlp_train(mlp, fedkt_work.NN_BATCH, sz["nn_steps"])
             + counts.mlp_predict(mlp, n_test))
    assert total.flops > hist.flops + final.flops


def test_patch_undoes_its_attributes():
    obj = SimpleNamespace(a=1)
    with faults.Patch() as mp:
        mp.setattr(obj, "a", 2)
        assert obj.a == 2
    assert obj.a == 1
