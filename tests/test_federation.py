"""Federation API: seed-for-seed reproducibility, engine agreement,
strategies, protocol messages."""
import jax
import numpy as np
import pytest

from repro.configs.base import FedKTConfig
from repro.core.learners import GBDTLearner, NNLearner, RFLearner
from repro.core.partition import homogeneous_partition
from repro.data.synthetic import tabular_binary
from repro.federation import (CentralPATEStrategy, FedKTSession,
                              LoopEngine, PartyBinding, PartyUpdate,
                              ResolvedBinding, SoloStrategy,
                              StreamingVoteAggregate, VmapEngine,
                              get_engine, label_wire_bytes, learner_kind,
                              pytree_bytes, query_budget)
from repro.federation.party import Party
from repro.models.smallnets import MLP


@pytest.fixture(scope="module")
def data():
    # n=2048 -> 1536 train examples: halves/quarters stay pow2-aligned
    # so loop and vmap engines share identical padding buckets
    return tabular_binary(n=2048, seed=0)


@pytest.fixture(scope="module")
def learner():
    return NNLearner(MLP(14, 2, hidden=16), num_classes=2, steps=60)


def _tree_equal(a, b):
    leaves_a, leaves_b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(leaves_a) == len(leaves_b)
    for la, lb in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# First recorded from the legacy ``run_fedkt`` entry point (since
# deleted) on the exact config below; the loop engine reproduced it
# bit-for-bit, including the transport-layer codec round-trip.
# Re-recorded under JAX 0.9.0's default PRNG: the old accuracy,
# 0.50390625, came from the non-partitionable threefry stream, which
# this JAX no longer uses by default (the old number still comes back
# with JAX_THREEFRY_PARTITIONABLE=0).  Epsilon did not move.
LEGACY_ACCURACY = 0.63671875
LEGACY_EPSILON = 13.436462732485094


def test_session_loop_matches_recorded_legacy_run(data, learner):
    """The acceptance contract: engine="loop" reproduces the (now
    removed) run_fedkt entry point's accuracy AND epsilon at a fixed
    seed, against the recorded expectation."""
    cfg = FedKTConfig(num_parties=3, num_partitions=1, num_subsets=2,
                      num_classes=2, privacy_level="L2", gamma=0.1,
                      query_fraction=0.5, seed=7)
    res = FedKTSession(learner, data, cfg, engine="loop").run()
    assert res.accuracy == LEGACY_ACCURACY
    assert res.epsilon == pytest.approx(LEGACY_EPSILON, rel=1e-9)


def test_loop_and_vmap_engines_agree(data, learner):
    """Same protocol, same PRNG schedule, same votes: with pow2-aligned
    party shards the two engines match down to the student weights."""
    cfg = FedKTConfig(num_parties=2, num_partitions=2, num_subsets=2,
                      num_classes=2, seed=3)
    parts = homogeneous_partition(len(data["y_train"]), 2, seed=3)
    r_loop = FedKTSession(learner, data, cfg, engine="loop",
                          party_indices=parts).run()
    r_vmap = FedKTSession(learner, data, cfg, engine="vmap",
                          party_indices=parts).run()
    assert r_loop.accuracy == r_vmap.accuracy
    _tree_equal(r_loop.student_states, r_vmap.student_states)


def test_party_engines_produce_identical_updates(data, learner):
    cfg = FedKTConfig(num_parties=1, num_partitions=2, num_subsets=2,
                      num_classes=2, seed=11)
    idx = np.arange(512)
    party = Party(party_id=0, X=data["X_train"], y=data["y_train"],
                  indices=idx, cfg=cfg, learner=learner,
                  student_learner=learner)
    key = jax.random.PRNGKey(0)
    upd_l, key_l = party.local_round(key, data["X_public"], 128,
                                     LoopEngine())
    upd_v, key_v = party.local_round(key, data["X_public"], 128,
                                     VmapEngine())
    np.testing.assert_array_equal(np.asarray(key_l), np.asarray(key_v))
    np.testing.assert_array_equal(upd_l.vote_gaps, upd_v.vote_gaps)
    _tree_equal(upd_l.student_states, upd_v.student_states)
    assert upd_l.wire_bytes() == upd_v.wire_bytes() > 0


@pytest.mark.parametrize("make_learner", [
    lambda: RFLearner(num_classes=2, num_trees=4, depth=3),
    lambda: GBDTLearner(num_rounds=6, depth=3),
], ids=["rf", "gbdt"])
def test_tree_engines_agree_on_quickstart(data, make_learner):
    """Acceptance: engine="vmap" with the tree learners reproduces the
    loop engine's vote labels on the quickstart federation shape — and
    because stacked tree fits are bit-identical under zero-weight
    padding, the students and the final model match exactly too."""
    learner = make_learner()
    cfg = FedKTConfig(num_parties=5, num_partitions=2, num_subsets=4,
                      num_classes=2, beta=0.5, seed=0)
    r_loop = FedKTSession(learner, data, cfg, engine="loop").run()
    r_vmap = FedKTSession(learner, data, cfg, engine="vmap").run()
    assert r_loop.accuracy == r_vmap.accuracy
    _tree_equal(r_loop.student_states, r_vmap.student_states)
    _tree_equal(r_loop.final_state, r_vmap.final_state)


def test_tree_party_update_identical_across_engines(data):
    """Party-level: identical vote gaps and student states for an
    RFLearner party under loop vs vmap engines."""
    learner = RFLearner(num_classes=2, num_trees=4, depth=3)
    cfg = FedKTConfig(num_parties=1, num_partitions=2, num_subsets=2,
                      num_classes=2, seed=11)
    party = Party(party_id=0, X=data["X_train"], y=data["y_train"],
                  indices=np.arange(512), cfg=cfg, learner=learner,
                  student_learner=learner)
    key = jax.random.PRNGKey(0)
    upd_l, _ = party.local_round(key, data["X_public"], 128, LoopEngine())
    upd_v, _ = party.local_round(key, data["X_public"], 128, VmapEngine())
    np.testing.assert_array_equal(upd_l.vote_gaps, upd_v.vote_gaps)
    _tree_equal(upd_l.student_states, upd_v.student_states)
    assert upd_l.wire_bytes() == upd_v.wire_bytes() > 0


@pytest.mark.parametrize("make_learner,engine", [
    (lambda: NNLearner(MLP(14, 2, hidden=16), num_classes=2, steps=60),
     "loop"),
    (lambda: NNLearner(MLP(14, 2, hidden=16), num_classes=2, steps=60),
     "vmap"),
    (lambda: RFLearner(num_classes=2, num_trees=4, depth=3), "loop"),
    (lambda: RFLearner(num_classes=2, num_trees=4, depth=3), "vmap"),
    (lambda: GBDTLearner(num_rounds=6, depth=3), "loop"),
    (lambda: GBDTLearner(num_rounds=6, depth=3), "vmap"),
], ids=["nn-loop", "nn-vmap", "rf-loop", "rf-vmap", "gbdt-loop",
        "gbdt-vmap"])
def test_binding_api_matches_legacy_constructor(data, make_learner,
                                                engine):
    """The bindings refactor's regression contract: a homogeneous
    session expressed as explicit per-party bindings is bit-identical —
    students, final model, epsilon, accuracy — to the legacy
    single-learner constructor, for every learner family and engine.
    The L2 config exercises the epsilon path (per-party gap folding)
    too."""
    learner = make_learner()
    cfg = FedKTConfig(num_parties=3, num_partitions=1, num_subsets=2,
                      num_classes=2, privacy_level="L2", gamma=0.1,
                      query_fraction=0.5, seed=7)
    legacy = FedKTSession(learner, data, cfg, engine=engine).run()
    bindings = [PartyBinding(learner, engine=engine)
                for _ in range(cfg.num_parties)]
    bound = FedKTSession(bindings, data, cfg, engine=engine).run()
    assert bound.accuracy == legacy.accuracy
    assert bound.epsilon == legacy.epsilon
    _tree_equal(bound.student_states, legacy.student_states)
    _tree_equal(bound.final_state, legacy.final_state)
    assert (bound.meta["wire_bytes"]["per_party"]
            == legacy.meta["wire_bytes"]["per_party"])
    # the shorthand reports itself as per-party bindings, one identical
    # row per party
    kind = learner_kind(learner)
    assert legacy.meta["party_bindings"] == [
        {"learner": kind, "engine": engine}] * cfg.num_parties


def test_session_rejects_malformed_bindings(data, learner):
    cfg = FedKTConfig(num_parties=3, num_partitions=1, num_subsets=2,
                      num_classes=2, seed=0)
    with pytest.raises(ValueError, match="num_parties=3"):
        FedKTSession([PartyBinding(learner)] * 2, data, cfg)
    with pytest.raises(TypeError, match="PartyBinding"):
        FedKTSession([learner] * 3, data, cfg)
    with pytest.raises(ValueError, match="student_learner"):
        FedKTSession([PartyBinding(learner)] * 3, data, cfg,
                     student_learner=learner)


def test_fit_stacked_matches_serial_fit(learner):
    rng = np.random.default_rng(0)
    Xs = [rng.normal(0, 1, (40, 14)).astype(np.float32) for _ in range(3)]
    ys = [rng.integers(0, 2, 40).astype(np.int32) for _ in range(3)]
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    stacked = learner.fit_stacked(keys, Xs, ys)
    for i in range(3):
        serial = learner.fit(keys[i], Xs[i], ys[i])
        _tree_equal(serial, jax.tree.map(lambda a: a[i], stacked))
    # stacked predict rows == serial predict
    Xq = rng.normal(0, 1, (17, 14)).astype(np.float32)
    preds = np.asarray(learner.predict_stacked(stacked, Xq))
    for i in range(3):
        row = np.asarray(learner.predict(
            jax.tree.map(lambda a: a[i], stacked), Xq))
        np.testing.assert_array_equal(preds[i], row)


def test_baseline_strategies_run(data, learner):
    cfg = FedKTConfig(num_parties=2, num_partitions=1, num_subsets=2,
                      num_classes=2, seed=1)
    solo = SoloStrategy(learner).run(data, cfg)
    assert 0.0 <= solo.accuracy <= 1.0
    assert len(solo.meta["per_party"]) == cfg.num_parties
    pate = CentralPATEStrategy(learner, 2).run(data, cfg)
    assert 0.0 <= pate.accuracy <= 1.0


def test_query_budget_levels():
    n = 100
    l0 = FedKTConfig(privacy_level="L0", query_fraction=0.2)
    assert query_budget(l0, n) == (n, n)
    l1 = FedKTConfig(privacy_level="L1", query_fraction=0.2)
    assert query_budget(l1, n) == (n, 20)
    l2 = FedKTConfig(privacy_level="L2", query_fraction=0.2)
    assert query_budget(l2, n) == (20, n)
    tiny = FedKTConfig(privacy_level="L1", query_fraction=0.001)
    assert query_budget(tiny, n) == (n, 1)      # never zero queries


def test_engine_registry():
    assert get_engine("loop").name == "loop"
    assert get_engine("vmap").name == "vmap"
    eng = LoopEngine()
    assert get_engine(eng) is eng
    with pytest.raises(ValueError):
        get_engine("warp")


class _RawCountsEngine:
    """Stub engine that contributes a FIXED (possibly wrong-layout)
    vote-count array, for exercising the aggregate's layout contract
    without building per-token learners."""
    name = "raw"

    def __init__(self, counts):
        self.counts = np.asarray(counts, dtype=np.int32)

    def student_vote_counts(self, learner, states, X, num_classes, *,
                            consistent=True):
        return self.counts


def _stub_update(pid, kind=None):
    return PartyUpdate(party_id=pid, student_states=[None],
                       vote_gaps=np.zeros(4, np.float32),
                       num_examples=8, learner_kind=kind,
                       meta={"num_query_labels": 0, "encoded_bytes": 0})


def _stub_binding(counts):
    return ResolvedBinding(learner=None, student_learner=None,
                           engine=_RawCountsEngine(counts))


def _agg(bindings=None):
    cfg = FedKTConfig(num_parties=2, num_partitions=1, num_subsets=1,
                      num_classes=2, privacy_level="L0", seed=0)
    return StreamingVoteAggregate(cfg, None, _RawCountsEngine(
        np.zeros((8, 2))), np.zeros((8, 14), np.float32),
        bindings=bindings)


def test_aggregate_rejects_vote_unit_mismatch():
    """The footgun this PR closes: a party voting 2 units/query (the
    per-token layout) folded against a 1-unit/query round used to
    broadcast or crash deep in jnp; now it is refused with an error
    naming BOTH parties and their unit counts."""
    agg = _agg(bindings={0: _stub_binding(np.zeros((8, 2))),
                         1: _stub_binding(np.zeros((16, 2)))})
    agg.add(_stub_update(0))
    with pytest.raises(ValueError, match=r"(?s)party 1.*2 unit\(s\)/"
                                         r"query.*party 0.*1 unit\(s\)"
                                         r"/query.*per-token"):
        agg.add(_stub_update(1))
    # the refused update was NOT folded
    assert agg.num_parties == 1 and agg.party_ids == [0]


def test_aggregate_rejects_class_count_mismatch():
    agg = _agg(bindings={0: _stub_binding(np.zeros((8, 3)))})
    with pytest.raises(ValueError, match=r"party 0.*num_classes=2"):
        agg.add(_stub_update(0))


def test_aggregate_rejects_declared_kind_mismatch():
    """A decoded update whose wire-declared learner kind contradicts
    the session's binding for that party must be refused before its
    states are run under the wrong model."""
    agg = _agg(bindings={0: _stub_binding(np.zeros((8, 2)))})
    with pytest.raises(ValueError, match="declares learner kind 'rf'"):
        agg.add(_stub_update(0, kind="rf"))
    # undeclared (None) skips the cross-check — pre-binding updates
    # still fold
    agg.add(_stub_update(0))
    assert agg.num_parties == 1


def test_aggregate_still_rejects_duplicates():
    agg = _agg()
    agg.add(_stub_update(0))
    with pytest.raises(ValueError, match="duplicate update from party 0"):
        agg.add(_stub_update(0))


def test_message_wire_sizes():
    tree = {"w": np.zeros((4, 8), np.float32), "b": np.zeros(8, np.int32)}
    assert pytree_bytes(tree) == 4 * 8 * 4 + 8 * 4
    assert pytree_bytes(jax.eval_shape(lambda: tree)) == pytree_bytes(tree)
    assert label_wire_bytes(750) == 3000
