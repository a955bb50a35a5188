"""The federation under test: one FedKT round (Algorithm 1) through the
program's session over localhost TCP, as its ``local`` role runs it
(``FedKTSession`` with a ``SocketTransport``; the silos on threads of
this process, the coordinator folding each update as it lands).

Set-up builds the deployment's rows, splits them over the silos with
the program's own Dirichlet partition (both from the configuration's
``deployment_seed``; ``bench/fedkt_data.py``) and runs one untimed
round, which compiles every program the timed rounds use.  The window
then runs whole rounds back to back on the run's seed, which draws the
protocol's randomness.  Each round's answers (every silo's party
labels, its students, the coordinator's labels and the final student)
are kept and compared with the configuration's plain reference once
the window has closed.
"""
from __future__ import annotations

import threading
import time
from types import SimpleNamespace

import numpy as np

import fedkt_data


class Federation:
    def __init__(self, cfg, kinds, seed, devs):
        import jax

        from repro.configs.base import FedKTConfig
        from repro.core.partition import dirichlet_partition
        from repro.federation.bindings import PartyBinding
        from repro.launch import federate

        sz = cfg["sizes"]
        if cfg["assumed"].get("matmul_precision"):
            jax.config.update("jax_default_matmul_precision",
                              cfg["assumed"]["matmul_precision"])
        self.sizes = sz
        self.kinds = list(kinds)
        self.seed = seed
        self.cfg = cfg
        self.data = fedkt_data.data(cfg)
        self.party_indices = dirichlet_partition(
            self.data["y_train"], sz["silos"], sz["beta"],
            cfg["deployment_seed"])
        self.fcfg = FedKTConfig(
            num_parties=sz["silos"], num_partitions=sz["partitions"],
            num_subsets=sz["subsets"], num_classes=2, beta=sz["beta"],
            privacy_level=sz["privacy"], gamma=sz["gamma"], seed=seed)
        args = SimpleNamespace(hidden=sz["nn_hidden"], steps=sz["nn_steps"],
                               trees=sz["trees"], depth=sz["depth"])
        self._bindings = [PartyBinding(federate.build_learner(k, args),
                                       engine=sz["engine"])
                          for k in self.kinds]
        self._final = federate.build_learner("nn", args)
        self.rounds = []            # per round: answers and host records
        self._tls = threading.local()

    # -- one round ------------------------------------------------------
    def _session(self):
        from repro.federation import FedKTSession, SocketTransport
        return FedKTSession(self._bindings, self.data, self.fcfg,
                            engine=self.sizes["engine"],
                            final_learner=self._final,
                            party_indices=[ix.copy() for ix in
                                           self.party_indices],
                            transport=SocketTransport(port=0))

    def _capture(self, session, labels):
        """Keeps each silo's party labels as its engine returns them:
        a wrapper on the silo's round names the silo, a wrapper on its
        engine records the labels (already host arrays in the party)."""
        tls = self._tls
        for party in session.parties:
            eng = party.engine
            if not getattr(eng, "_bench_wrapped", False):
                inner = eng.label_queries

                def label_queries(*a, _inner=inner, **kw):
                    out = _inner(*a, **kw)
                    labels.setdefault(tls.pid, []).append(
                        np.asarray(out[0]))
                    return out

                eng.label_queries = label_queries
                eng._bench_wrapped = True
            inner_round = party.local_round

            def local_round(*a, _inner=inner_round, _pid=party.party_id,
                            **kw):
                tls.pid = _pid
                return _inner(*a, **kw)

            party.local_round = local_round

    def round(self):
        t0 = time.perf_counter()
        session = self._session()
        labels = {}
        self._capture(session, labels)
        res = session.run()
        wall = time.perf_counter() - t0
        sock = res.meta.get("socket", {})
        failed = bool(sock.get("failed") or sock.get("dropped")
                      or len(labels) != len(self.kinds))
        self.rounds.append({"result": res, "party_labels": labels,
                            "wall_s": wall, "failed": failed,
                            "parties_s": res.meta["seconds"]["parties"],
                            "server_s": res.meta["seconds"]["server"]})
        return self.rounds[-1]

    # -- after the window -----------------------------------------------
    def answers(self, k):
        import jax
        r = self.rounds[k]
        res = r["result"]
        (dom,) = res.by_domain.values()
        return {
            "party_labels": {i: r["party_labels"][i]
                             for i in range(len(self.kinds))},
            "students": {i: [jax.tree.map(np.asarray, st) for st in sts]
                         for i, sts in dom["student_states"].items()},
            "server_labels": np.asarray(dom["labels"]),
            "final": jax.tree.map(np.asarray, res.final_state),
        }

    def free(self):
        """Drops every device array the program holds, keeping the
        answers of the rounds on the host."""
        import jax
        kept = [{"answers": self.answers(k), "failed": r["failed"]}
                for k, r in enumerate(self.rounds)]
        self.rounds = kept
        self._bindings = self._final = None
        jax.clear_caches()


def build(cfg, wl, seed, devs, seconds):
    from common import seed32
    del seconds
    fed = Federation(cfg, wl["traffic"]["learners"], seed32(seed), devs)
    fed.round()                   # the untimed warm-up round
    fed.rounds.clear()
    return fed


def reference_inputs(fed, ref):
    return ref.RoundInputs(fed.cfg, fed.kinds, fed.seed)


def check(fed, ref, seed, win, params, precision=None):
    """Compares one round of the window, drawn from the seed, stage by
    stage with the reference; every other round must give the same
    answers (a round is a function of its seed).  ``precision`` puts
    the reference at that precision in the program's place (the
    control)."""
    del win, params
    inp = reference_inputs(fed, ref)
    rounds = fed.rounds
    if precision is not None:
        answers = ref.play_round(inp, precision)
    else:
        k = np.random.default_rng(seed + 2).integers(len(rounds))
        answers = rounds[k]["answers"]
    diff = ref.compare(inp, answers)
    if precision is None:
        base = answers
        same = all(
            np.array_equal(r["answers"]["server_labels"],
                           base["server_labels"])
            and all(np.array_equal(a, b) for i in base["party_labels"]
                    for a, b in zip(r["answers"]["party_labels"][i],
                                    base["party_labels"][i]))
            for r in rounds)
        diff["rounds_differ"] = 0.0 if same else 1.0
    return diff
