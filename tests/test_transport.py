"""Transport layer: codec round-trips, versioned-frame rejection, wire
accounting, cross-transport bit-identity of the federation round, and
the worker-cleanup contract when a party fails mid-round."""
import multiprocessing

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.configs.base import FedKTConfig
from repro.core.learners import GBDTLearner, NNLearner, RFLearner
from repro.data.synthetic import tabular_binary
from repro.federation import (FedKTSession, InProcessTransport, PartyUpdate,
                              ThreadTransport, TokenLabels, codec,
                              get_transport, pytree_bytes)
from repro.models.smallnets import MLP


@pytest.fixture(scope="module")
def data():
    return tabular_binary(n=512, seed=0)


@pytest.fixture(scope="module")
def learner():
    return NNLearner(MLP(14, 2, hidden=8), num_classes=2, steps=20)


def _tree_equal(a, b):
    leaves_a, leaves_b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(leaves_a) == len(leaves_b)
    for la, lb in zip(leaves_a, leaves_b):
        la, lb = np.asarray(la), np.asarray(lb)
        assert la.dtype == lb.dtype and la.shape == lb.shape
        np.testing.assert_array_equal(la, lb)


# ---------------------------------------------------------------------------
# Codec
# ---------------------------------------------------------------------------
def _roundtrip(tree):
    buf = codec.encode(tree)
    out, header = codec.decode(buf)
    assert codec.encoded_nbytes(tree) == len(buf)
    return out, buf


@pytest.mark.parametrize("make_learner", [
    lambda: NNLearner(MLP(14, 2, hidden=8), num_classes=2, steps=10),
    lambda: RFLearner(num_classes=2, num_trees=3, depth=2),
    lambda: GBDTLearner(num_rounds=3, depth=2),
], ids=["nn", "rf", "gbdt"])
def test_codec_roundtrips_student_states(data, make_learner):
    """encode∘decode identity over every student-state pytree kind the
    protocol ships (dict params, nested forest/edges tuples)."""
    lrn = make_learner()
    states = [lrn.fit(jax.random.fold_in(jax.random.PRNGKey(0), i),
                      data["X_train"][:64], data["y_train"][:64])
              for i in range(2)]
    out, buf = _roundtrip({"students": states})
    _tree_equal(states, out["students"])
    assert isinstance(out["students"], list)


def test_codec_mixed_dtypes_and_containers():
    tree = {
        "f32": np.arange(6, dtype=np.float32).reshape(2, 3),
        "f64": np.linspace(0, 1, 4),
        "f16": np.ones((3,), np.float16),
        "bf16": jnp.full((2, 2), 1.5, jnp.bfloat16),
        "ints": (np.int32(7), np.arange(3, dtype=np.int64),
                 np.array(255, np.uint8)),
        "flags": [np.array([True, False]), None],
        "nested": {"deep": [({"x": np.zeros((1, 2), np.int16)},)]},
    }
    out, _ = _roundtrip(tree)
    _tree_equal(tree, out)
    assert out["flags"][1] is None
    assert isinstance(out["ints"], tuple)
    assert isinstance(out["nested"]["deep"][0], tuple)
    assert out["bf16"].dtype == jnp.bfloat16


def test_codec_empty_leaves_and_containers():
    tree = {"empty1d": np.zeros((0,), np.float32),
            "empty3d": np.zeros((3, 0, 2), np.int32),
            "scalar": np.float64(3.5),
            "emptydict": {}, "emptylist": [], "none": None}
    out, buf = _roundtrip(tree)
    _tree_equal(tree, out)
    assert out["emptydict"] == {} and out["emptylist"] == []
    assert out["none"] is None
    # empty payload entries contribute zero bytes but keep shape/dtype
    assert out["empty3d"].shape == (3, 0, 2)


def test_codec_abstract_sizing_matches_concrete():
    """encoded_nbytes prices a message exactly from eval_shape — the
    dry-run / comm-overhead path for models too big to materialize."""
    # float32 throughout: eval_shape re-types leaves under jax's default
    # x64-disabled config, and the point here is size parity
    tree = {"w": np.zeros((8, 4), np.float32),
            "b": np.zeros((4,), np.float32)}
    abstract = jax.eval_shape(lambda: tree)
    assert codec.encoded_nbytes(abstract) == len(codec.encode(tree))


def test_codec_rejects_bad_input():
    with pytest.raises(ValueError):
        codec.decode(b"NOPE" + b"\x00" * 16)
    with pytest.raises(TypeError):
        codec.encode({"bad/key": np.zeros(1)})
    with pytest.raises(TypeError):
        codec.encode({1: np.zeros(1)})
    with pytest.raises(ValueError):
        codec.decode_update(codec.encode({"w": np.zeros(1)}))


def test_codec_version_header():
    """Every frame leads with magic + version; a frame speaking another
    version is refused with an error naming both versions, and the
    pre-versioning wire format (magic ``FKT1``) is rejected rather than
    misread."""
    buf = codec.encode({"w": np.zeros((2,), np.float32)})
    assert buf[:3] == codec.MAGIC and buf[3] == codec.VERSION
    tampered = buf[:3] + bytes([codec.VERSION + 1]) + buf[4:]
    with pytest.raises(ValueError, match=f"v{codec.VERSION + 1}"):
        codec.decode(tampered)
    with pytest.raises(ValueError, match="version"):
        codec.decode(b"FKT1" + buf[4:])


def test_codec_empty_gap_trace():
    """A party whose queries produced no clean gaps (e.g. zero teachers
    answered) still round-trips: the empty trace survives with shape and
    dtype intact and prices at zero payload bytes."""
    upd = PartyUpdate(party_id=3,
                      student_states=[{"w": np.ones((2, 2), np.float32)}],
                      vote_gaps=np.zeros((0,), np.float64),
                      num_examples=5, meta={"num_teachers": 0})
    dec = codec.decode_update(codec.encode_update(upd))
    assert dec.vote_gaps.shape == (0,)
    assert dec.vote_gaps.dtype == np.float64
    assert dec.wire_bytes() == upd.wire_bytes() == \
        pytree_bytes(upd.student_states)


def test_codec_zero_length_label_payload():
    """An empty vote answer (query_fraction rounding to zero on a tiny
    shard) frames, prices, and decodes cleanly."""
    msg = TokenLabels(party_id=1, labels=np.zeros((0,), np.int32))
    buf = codec.encode_labels(msg)
    assert codec.labels_encoded_nbytes(msg) == len(buf)
    dec = codec.decode_labels(buf)
    assert dec.labels.shape == (0,) and dec.labels.dtype == np.int32
    assert dec.party_id == 1 and msg.wire_bytes() == 0


def test_codec_truncated_frames_always_raise():
    """EVERY strict prefix of a frame raises ValueError — truncation in
    the magic, the version, the header length, the header JSON, or the
    payload is detected, never mis-parsed into a wrong tree."""
    upd = PartyUpdate(party_id=0,
                      student_states=[{"w": np.arange(4, dtype=np.float32)}],
                      vote_gaps=np.arange(3, dtype=np.float64),
                      num_examples=9, meta={"num_teachers": 1})
    buf = codec.encode_update(upd)
    for n in range(len(buf)):
        with pytest.raises(ValueError):
            codec.decode(buf[:n])
    # the untruncated frame still decodes (the loop above is strict)
    assert codec.decode_update(buf).party_id == 0


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 4))
def test_codec_roundtrip_property(seed, depth):
    """Random nested dict/list/tuple trees over random dtypes/shapes
    (including empty dims) survive encode∘decode bit-for-bit."""
    rng = np.random.default_rng(seed)
    dtypes = [np.float32, np.float64, np.int32, np.int64, np.uint8,
              np.float16, bool]

    def leaf():
        shape = tuple(int(d) for d in
                      rng.integers(0, 4, size=rng.integers(0, 3)))
        dt = dtypes[rng.integers(len(dtypes))]
        return (rng.integers(0, 2, size=shape).astype(dt) if dt is bool
                else rng.normal(0, 1, size=shape).astype(dt))

    def build(d):
        if d == 0 or rng.random() < 0.3:
            return leaf()
        kind = rng.integers(4)
        n = int(rng.integers(0, 3))
        if kind == 0:
            return {f"k{i}": build(d - 1) for i in range(n)}
        if kind == 1:
            return [build(d - 1) for _ in range(n)]
        if kind == 2:
            return tuple(build(d - 1) for _ in range(n))
        return None

    tree = {"root": build(depth)}
    out, _ = _roundtrip(tree)
    _tree_equal(tree, out)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.sampled_from(["nn", "rf", "gbdt", "lm", "custom-learner",
                        None]),
       st.integers(1, 4))
def test_codec_mixed_learner_update_roundtrip_property(seed, kind,
                                                       n_students):
    """Heterogeneous-wire contract: a PartyUpdate from ANY learner
    family — arbitrary state pytrees, arbitrary declared kind,
    including undeclared (None) and unregistered custom kinds —
    round-trips through the codec with its learner_kind, states, gap
    trace, and framed size intact."""
    rng = np.random.default_rng(seed)
    # one shape per family-ish pytree: dense float stacks (nn), int
    # split/leaf tables (trees), scalars
    states = []
    for _ in range(n_students):
        states.append({
            "w": rng.normal(0, 1, (int(rng.integers(1, 5)), 3)
                            ).astype(np.float32),
            "splits": rng.integers(0, 7, int(rng.integers(0, 6))
                                   ).astype(np.int32),
            "bias": np.float64(rng.normal()),
        })
    upd = PartyUpdate(
        party_id=int(rng.integers(0, 1000)),
        student_states=states,
        vote_gaps=rng.normal(0, 1, int(rng.integers(0, 9))
                             ).astype(np.float32),
        num_examples=int(rng.integers(0, 10**6)),
        learner_kind=kind,
        meta={"num_query_labels": int(rng.integers(0, 100))})
    buf = codec.encode_update(upd)
    assert codec.update_encoded_nbytes(upd) == len(buf)
    out = codec.decode_update(buf)
    assert out.party_id == upd.party_id
    assert out.learner_kind == kind
    assert out.num_examples == upd.num_examples
    np.testing.assert_array_equal(out.vote_gaps, upd.vote_gaps)
    _tree_equal(out.student_states, upd.student_states)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.sampled_from(["nn", "rf", "lm", None]),
       st.sampled_from(["example", "token", None]),
       st.integers(1, 500), st.integers(1, 70),
       st.sampled_from([None, "fp", "names"]))
def test_codec_domain_header_roundtrip_property(seed, kind, unit, T, U,
                                                flavor):
    """The vote-domain wire contract: a PartyUpdate declaring ANY
    VoteDomain — either unit, any (T, U), fingerprinted or anonymous,
    with or without label_names — round-trips through the codec with
    the domain's full identity key AND its learner_kind intact;
    undeclared (None) stays None."""
    from repro.federation import VoteDomain

    rng = np.random.default_rng(seed)
    dom = None
    if unit is not None:
        dom = VoteDomain(
            unit=unit, num_units=T, num_classes=U,
            fingerprint=(f"{rng.integers(2**32):08x}"
                         if flavor == "fp" else None),
            label_names=(tuple(f"c{i}" for i in range(U))
                         if flavor == "names" and U <= 8 else None))
    upd = PartyUpdate(
        party_id=int(rng.integers(0, 100)),
        student_states=[{"w": rng.normal(0, 1, (2, 3)
                                         ).astype(np.float32)}],
        vote_gaps=rng.normal(0, 1, 4).astype(np.float32),
        num_examples=int(rng.integers(1, 100)),
        learner_kind=kind, domain=dom,
        meta={"num_query_labels": T})
    buf = codec.encode_update(upd)
    assert codec.update_encoded_nbytes(upd) == len(buf)
    out = codec.decode_update(buf)
    assert out.learner_kind == kind
    if dom is None:
        assert out.domain is None
    else:
        assert out.domain == dom and out.domain.key == dom.key
        assert out.domain.label_names == dom.label_names


def test_codec_legacy_frame_decodes_to_no_domain():
    """A pre-domain peer at the SAME codec version never sets the
    header's "domain" key at all (not even to null).  Such a frame must
    decode to domain=None — the "undeclared" sentinel the aggregate
    resolves from the party's binding — with every other field intact."""
    states = [{"w": np.arange(6, dtype=np.float32).reshape(2, 3)}]
    gaps = np.arange(3, dtype=np.float64)
    legacy_header = {"kind": "PartyUpdate", "party_id": 7,
                     "num_examples": 42, "learner_kind": "rf",
                     "meta": {"num_teachers": 2}}   # no "domain" key
    buf = codec.encode({"student_states": states, "vote_gaps": gaps},
                       legacy_header)
    out = codec.decode_update(buf)
    assert out.domain is None
    assert out.party_id == 7 and out.learner_kind == "rf"
    assert out.num_examples == 42
    np.testing.assert_array_equal(out.vote_gaps, gaps)
    _tree_equal(out.student_states, states)
    # and a same-version frame that DOES declare is byte-compatible:
    # only the header field differs
    assert buf[:4] == codec.encode_update(PartyUpdate(
        party_id=7, student_states=states, vote_gaps=gaps,
        num_examples=42, learner_kind="rf",
        meta={"num_teachers": 2}))[:4]


def test_codec_domain_frame_truncation_sweep():
    """EVERY strict prefix of a domain-extended frame raises — the
    header grew (domain + learner_kind ride in it), so the truncation
    guarantee is re-proved over the extended header, not grandfathered
    from the pre-domain frame layout."""
    from repro.federation import VoteDomain

    upd = PartyUpdate(
        party_id=1,
        student_states=[{"w": np.arange(4, dtype=np.float32)}],
        vote_gaps=np.arange(3, dtype=np.float64), num_examples=9,
        learner_kind="nn",
        domain=VoteDomain("example", 8, 2, fingerprint="deadbeef",
                          label_names=("neg", "pos")),
        meta={"num_teachers": 1})
    buf = codec.encode_update(upd)
    for n in range(len(buf)):
        with pytest.raises(ValueError):
            codec.decode(buf[:n])
    out = codec.decode_update(buf)          # the full frame is intact
    assert out.domain == upd.domain
    assert out.domain.label_names == ("neg", "pos")


# ---------------------------------------------------------------------------
# CRC trailer (codec v3)
# ---------------------------------------------------------------------------
def _crc_test_frame():
    upd = PartyUpdate(
        party_id=1,
        student_states=[{"w": np.arange(4, dtype=np.float32)}],
        vote_gaps=np.arange(3, dtype=np.float64), num_examples=9,
        learner_kind="nn", meta={"num_teachers": 1})
    return codec.encode_update(upd)


def test_codec_crc_trailer_detects_every_single_byte_flip():
    """No single corrupted byte anywhere in a frame decodes silently:
    magic/version damage is a codec error, header/payload/trailer
    damage trips the crc32 trailer.  This is the property the socket
    coordinator's NAK-with-reason-``corrupt`` path stands on
    (tests/test_faults.py exercises it over a real wire)."""
    buf = _crc_test_frame()
    for k in range(len(buf)):
        flipped = buf[:k] + bytes([buf[k] ^ 0xFF]) + buf[k + 1:]
        with pytest.raises(ValueError):
            codec.decode(flipped)
    assert codec.decode_update(buf).party_id == 1   # strict loop above


def test_codec_corruption_raises_typed_errors():
    """The coordinator maps refusals to NAK reasons by exception type,
    so the types are wire contract: corruption/truncation are
    CorruptFrameError/TruncatedFrameError, an alien version is
    VersionMismatchError, and all are CodecError ⊂ ValueError (old
    ``except ValueError`` callers still catch everything)."""
    buf = _crc_test_frame()
    with pytest.raises(codec.CorruptFrameError, match="crc32"):
        codec.decode(buf[:-1] + bytes([buf[-1] ^ 0x01]))
    with pytest.raises(codec.TruncatedFrameError):
        codec.decode(buf[:-1])
    with pytest.raises(codec.CorruptFrameError, match="trailing"):
        codec.decode(buf + b"\x00")
    with pytest.raises(codec.VersionMismatchError):
        codec.decode(buf[:3] + bytes([codec.VERSION + 1]) + buf[4:])
    for exc in (codec.CorruptFrameError, codec.TruncatedFrameError,
                codec.VersionMismatchError):
        assert issubclass(exc, codec.CodecError)
        assert issubclass(exc, ValueError)


def test_codec_v2_frame_still_decodes():
    """Version-bump compatibility: a v2 peer's frame (no crc trailer)
    is the same bytes minus the trailer with version byte 2 — it must
    decode to the identical update, and the pricing helper must agree
    with the v3 trailer it now includes."""
    buf = _crc_test_frame()
    v2 = buf[:3] + bytes([2]) + buf[4:-4]      # strip the crc trailer
    out = codec.decode_update(v2)
    assert out.party_id == 1 and out.num_examples == 9
    np.testing.assert_array_equal(out.vote_gaps,
                                  np.arange(3, dtype=np.float64))
    # a v2 frame with slack bytes is NOT tolerated: the downgrade path
    # must never become a crc bypass
    with pytest.raises(ValueError):
        codec.decode(v2 + b"\x00\x00\x00\x00")


# ---------------------------------------------------------------------------
# Wire accounting
# ---------------------------------------------------------------------------
def test_update_wire_bytes_counts_gap_trace():
    """The L1 accounting bug: vote_gaps ride in the same message as the
    student states, so wire_bytes must count both — and must equal the
    codec's measured payload exactly (framed size adds only header)."""
    states = [{"w": np.zeros((4, 2), np.float32)}]
    gaps = np.arange(16, dtype=np.float64)
    upd = PartyUpdate(party_id=0, student_states=states, vote_gaps=gaps,
                      num_examples=10, meta={"num_teachers": 2})
    assert upd.wire_bytes() == pytree_bytes(states) + gaps.nbytes
    buf = codec.encode_update(upd)
    measured = len(buf)
    assert codec.update_encoded_nbytes(upd) == measured
    # framed = header + payload; payload is exactly the accounted bytes
    header_overhead = measured - upd.wire_bytes()
    assert 0 < header_overhead < 4096
    dec = codec.decode_update(buf)
    assert dec.party_id == 0 and dec.num_examples == 10
    assert dec.meta["num_teachers"] == 2
    assert dec.wire_bytes() == upd.wire_bytes()
    _tree_equal(upd.student_states, dec.student_states)
    np.testing.assert_array_equal(upd.vote_gaps, dec.vote_gaps)


# ---------------------------------------------------------------------------
# Transports
# ---------------------------------------------------------------------------
L2_CFG = dict(num_parties=3, num_partitions=1, num_subsets=2,
              num_classes=2, privacy_level="L2", gamma=0.1,
              query_fraction=0.5, seed=7)


def test_thread_transport_matches_inprocess(data, learner):
    """Transport smoke (tier-1): parallel parties over a thread pool are
    bit-identical to the serial in-process round at a fixed seed."""
    cfg = FedKTConfig(**L2_CFG)
    ref = FedKTSession(learner, data, cfg, engine="loop").run()
    par = FedKTSession(learner, data, cfg, engine="loop",
                       transport="thread", parallelism=3).run()
    assert par.accuracy == ref.accuracy
    assert par.epsilon == ref.epsilon
    _tree_equal(par.student_states, ref.student_states)
    assert par.meta["wire_bytes"] == ref.meta["wire_bytes"]
    assert par.meta["transport"] == "thread"
    assert par.meta["wire_bytes"]["updates"] > \
        par.meta["wire_bytes"]["updates_payload"] > 0


def test_subprocess_transport_matches_inprocess(data, learner):
    """Acceptance: transport="subprocess" (one spawned interpreter per
    party, PartyUpdate crossing as codec bytes) returns bit-identical
    accuracy AND epsilon to the in-process loop engine."""
    cfg = FedKTConfig(**L2_CFG)
    ref = FedKTSession(learner, data, cfg, engine="loop").run()
    sub = FedKTSession(learner, data, cfg, engine="loop",
                       transport="subprocess", parallelism=2).run()
    assert sub.accuracy == ref.accuracy
    assert sub.epsilon == ref.epsilon
    _tree_equal(sub.student_states, ref.student_states)
    assert sub.meta["wire_bytes"] == ref.meta["wire_bytes"]


def test_transports_agree_across_engines_and_learners(data):
    """Engine x transport grid on a tree learner: the vmap engine under
    a parallel transport still reproduces the serial loop exactly."""
    cfg = FedKTConfig(num_parties=2, num_partitions=2, num_subsets=2,
                      num_classes=2, seed=3)
    lrn = RFLearner(num_classes=2, num_trees=3, depth=2)
    ref = FedKTSession(lrn, data, cfg, engine="loop").run()
    par = FedKTSession(lrn, data, cfg, engine="vmap",
                       transport="thread").run()
    assert par.accuracy == ref.accuracy
    _tree_equal(par.student_states, ref.student_states)


def test_get_transport_registry():
    assert get_transport("inprocess").name == "inprocess"
    assert get_transport("thread", 4).parallelism == 4
    assert get_transport("subprocess").name == "subprocess"
    assert get_transport("socket", 4).name == "socket"
    t = ThreadTransport(parallelism=2)
    assert get_transport(t) is t
    with pytest.raises(ValueError):
        get_transport("carrier-pigeon")
    with pytest.raises(ValueError):
        get_transport(InProcessTransport(), parallelism=2)


# ---------------------------------------------------------------------------
# Cleanup contract
# ---------------------------------------------------------------------------
def test_transports_are_context_managers():
    """Every transport supports ``with`` and idempotent close."""
    for name in ("inprocess", "thread", "subprocess", "socket"):
        with get_transport(name) as t:
            assert t.name == name
        t.close()


def test_subprocess_cleanup_on_party_failure(data, learner):
    """Regression: a party that raises mid-round must not leak worker
    interpreters.  The old executor-based round kept the remaining
    spawned processes alive (still training dropped parties) after the
    session had already failed; the pool is now terminated in-place."""
    cfg = FedKTConfig(**L2_CFG)
    shards = [np.arange(0, 100), np.arange(100, 200),
              np.array([10 ** 9])]          # out-of-range: party 2 dies
    session = FedKTSession(learner, data, cfg, engine="loop",
                           party_indices=shards,
                           transport="subprocess", parallelism=3)
    before = set(multiprocessing.active_children())
    with pytest.raises(IndexError):
        session.run()
    # terminate() + join() ran in the round's finally: no spawned
    # worker outlives the failure
    leaked = [p for p in multiprocessing.active_children()
              if p not in before]
    assert leaked == []


def test_thread_cleanup_on_party_failure(data, learner):
    """The thread transport's failed round raises promptly (queued
    parties are cancelled) and the session object stays reusable."""
    cfg = FedKTConfig(**L2_CFG)
    shards = [np.array([10 ** 9]), np.arange(0, 100),
              np.arange(100, 200)]
    with ThreadTransport(parallelism=1) as transport:
        session = FedKTSession(learner, data, cfg, engine="loop",
                               party_indices=shards, transport=transport)
        with pytest.raises(IndexError):
            session.run()


def test_subprocess_transport_refuses_a_tpu_parent(monkeypatch):
    """One process per chip: on a TPU backend the parent holds the chip,
    so spawned parties could not reach it.  The transport refuses up
    front, naming why; on a CPU backend it builds as before."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the chip"):
        get_transport("subprocess")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert get_transport("subprocess").name == "subprocess"
