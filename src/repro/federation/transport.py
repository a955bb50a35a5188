"""Transports: HOW a PartyUpdate crosses the party/server boundary.

The protocol says each party sends ONE message; a Transport decides
where the party side runs and how the message travels.  Every
implementation routes the update through the wire codec — encode on the
party side, decode on the server side — so serialization sits on the
hot path in ALL modes and ``meta["encoded_bytes"]`` is the measured
(not estimated) wire size of each update:

  InProcessTransport : parties run serially in the caller's process
                       (the reference semantics; codec round-trip only).
  ThreadTransport    : parties fan out over a thread pool.  JAX dispatch
                       is thread-safe and the jitted fits release the
                       GIL, so independent parties overlap on CPU.
  SubprocessTransport: each party's local round runs in its OWN worker
                       process (spawned interpreters); the encoded
                       PartyUpdate bytes are literally what crosses the
                       process boundary — the paper's cross-silo
                       deployment shape, one process per silo.
  SocketTransport    : federation/net.py — updates cross REAL TCP
                       connections, streamed into the server's running
                       vote aggregate with deadline/quorum straggler
                       semantics.  The only transport with a
                       ``stream_round`` (``streams = True``).

Every transport is a context manager, and a party failure mid-round
must never leak execution resources: the subprocess pool is TERMINATED
(not drained) when a party raises, so no spawned interpreter outlives
the round it was serving (regression-tested in tests/test_transport.py).

Seed contract: parties receive PRECOMPUTED keys (the serial schedule
played forward by the session), so fan-out order never changes any
party's randomness and every transport is bit-identical to the
in-process loop at a fixed seed (test-enforced in
tests/test_transport.py).
"""
from __future__ import annotations

import pickle
from concurrent.futures import ThreadPoolExecutor
from typing import Any, List, Optional, Protocol, Sequence

import numpy as np

from repro.federation.codec import decode_update, encode_update
from repro.federation.messages import PartyUpdate


class Transport(Protocol):
    """Pluggable party-execution + message-passing backend."""
    name: str

    def run_round(self, parties: Sequence[Any], keys: Sequence[Any],
                  X_public, num_queries: int,
                  engine) -> List[PartyUpdate]:
        """Runs every party's local round (one precomputed key each) and
        returns the DECODED updates, in party order.  Each update's
        ``meta["encoded_bytes"]`` records its measured wire size.
        ``engine=None`` lets every party run under its OWN bound engine
        (the heterogeneous session path); an explicit engine overrides
        all bindings."""
        ...

    def close(self) -> None:
        """Releases any resources the transport holds across rounds.
        Idempotent; per-round resources must already be cleaned up by
        ``run_round`` itself (even when a party raises)."""
        ...


class TransportBase:
    """Context-manager plumbing shared by every transport: ``close`` is
    idempotent and guaranteed on ``with`` exit, success or failure.
    Per-ROUND resources (pools, sockets) are the run methods' own
    responsibility — they clean up in ``finally`` so a crashing party
    can never leak workers, with or without the ``with``."""

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False


def _decode_annotated(buf: bytes) -> PartyUpdate:
    upd = decode_update(buf)
    upd.meta["encoded_bytes"] = len(buf)
    return upd


def _encoded_round(party, key, X_public, num_queries, engine) -> bytes:
    upd, _ = party.local_round(key, X_public, num_queries, engine)
    return encode_update(upd)


class InProcessTransport(TransportBase):
    """Serial in-process reference: today's semantics plus the codec
    round-trip, so in-process and cross-process servers see byte-wise
    identical updates."""
    name = "inprocess"

    def __init__(self, parallelism: Optional[int] = None):
        if parallelism not in (None, 1):
            raise ValueError("the inprocess transport is serial; use "
                             "transport=\"thread\" or \"subprocess\" "
                             "for parallelism > 1")
        self.parallelism = 1

    def run_round(self, parties, keys, X_public, num_queries, engine):
        return [_decode_annotated(
                    _encoded_round(p, k, X_public, num_queries, engine))
                for p, k in zip(parties, keys)]


class ThreadTransport(TransportBase):
    """Concurrent parties in one interpreter.  Engines and learners are
    stateless (jit caches are internally synchronized), so sharing them
    across workers is safe; results are collected in party order."""
    name = "thread"

    def __init__(self, parallelism: Optional[int] = None):
        self.parallelism = parallelism

    def run_round(self, parties, keys, X_public, num_queries, engine):
        workers = self.parallelism or len(parties)
        ex = ThreadPoolExecutor(max_workers=workers)
        try:
            futs = [ex.submit(_encoded_round, p, k, X_public,
                              num_queries, engine)
                    for p, k in zip(parties, keys)]
            return [_decode_annotated(f.result()) for f in futs]
        finally:
            # a failed party must not make the round run the REMAINING
            # parties to completion before raising: drop queued work
            # (running threads finish their current party and exit)
            ex.shutdown(wait=False, cancel_futures=True)


def _subprocess_worker(blob: bytes) -> bytes:
    """Runs in a spawned interpreter: unpickle the silo, run its local
    round, return the codec-encoded PartyUpdate."""
    party, key, X_public, num_queries, engine = pickle.loads(blob)
    return _encoded_round(party, key, X_public, num_queries, engine)


class SubprocessTransport(TransportBase):
    """One worker process per party (spawn start method).  Workers
    re-import and re-jit, so cold cost is high — this transport exists
    to make the cross-silo deployment real, not to win single-host
    benchmarks.  CPU hosts only: an accelerator belongs to the one
    process that first touches JAX, so a parent on a TPU backend holds
    the chip and the spawned parties would fail on the TPU library's
    lock or hang; the constructor refuses that case up front.

    Cleanup contract: when any party raises, the whole worker pool is
    terminated on the spot — the old executor-based round left the
    remaining interpreters running (and kept training dropped parties)
    until their queues drained."""
    name = "subprocess"

    def __init__(self, parallelism: Optional[int] = None):
        import jax
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "SubprocessTransport cannot run on a TPU backend: this "
                "process already holds the chip, so every spawned party "
                "would fail on the TPU library's lock or hang.  Run the "
                "parties in this process (transport 'inprocess', "
                "'thread' or 'socket') or as separate hosts.")
        self.parallelism = parallelism

    def run_round(self, parties, keys, X_public, num_queries, engine):
        import multiprocessing
        workers = self.parallelism or len(parties)
        Xpub = np.asarray(X_public)
        blobs = [pickle.dumps((p, np.asarray(k), Xpub, num_queries,
                               engine))
                 for p, k in zip(parties, keys)]
        ctx = multiprocessing.get_context("spawn")
        pool = ctx.Pool(processes=workers)
        done = False
        try:
            encoded = pool.map(_subprocess_worker, blobs)
            pool.close()
            pool.join()
            done = True
            return [_decode_annotated(b) for b in encoded]
        finally:
            if not done:
                # a party failed: kill every worker interpreter NOW
                # instead of letting them finish (or start) the other
                # parties' rounds
                pool.terminate()
                pool.join()


_TRANSPORTS = {"inprocess": InProcessTransport, "thread": ThreadTransport,
               "subprocess": SubprocessTransport}


def get_transport(transport, parallelism: Optional[int] = None) -> Transport:
    """Transport instance from a name ("inprocess" | "thread" |
    "subprocess" | "socket") or pass-through of an instance."""
    if isinstance(transport, str):
        if transport == "socket":
            # net.py imports this module; resolve lazily to avoid the
            # cycle while keeping one registry entry point
            from repro.federation.net import SocketTransport
            return SocketTransport(parallelism=parallelism)
        if transport not in _TRANSPORTS:
            raise ValueError(f"unknown transport {transport!r}; "
                             f"available: "
                             f"{sorted([*_TRANSPORTS, 'socket'])}")
        return _TRANSPORTS[transport](parallelism=parallelism)
    if parallelism is not None:
        raise ValueError("parallelism= only applies when the transport "
                         "is given by name")
    return transport
