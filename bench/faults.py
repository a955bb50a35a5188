"""Faults planted in the program under a run of a federation cell, to
show that ``correct`` comes out false.  Each takes ``mp``, anything
with ``setattr(obj, name, value)`` that undoes its patches afterwards:
pytest's ``monkeypatch`` in the tests, ``Patch`` in
``bench/readings.py --fault`` at the cell's own size on the chip."""


class Patch:
    """A minimal ``monkeypatch``: set attributes, then undo them."""

    def __init__(self):
        self._undo = []

    def setattr(self, obj, name, value):
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        for obj, name, old in reversed(self._undo):
            setattr(obj, name, old)
        self._undo.clear()
        return False


def state_unchanged(mp):
    """Every MLP fit returns the weights it started from."""
    import jax

    from repro.core.learners import NNLearner

    def fit_body(self, key, X, y, mask):
        return self.net.init(jax.random.fold_in(key, 1))
    mp.setattr(NNLearner, "_fit_body", fit_body)


def half_rows_left_out(mp):
    """Every teacher fits on the first half of its rows only."""
    from repro.federation.engines import VmapEngine
    inner = VmapEngine.fit_teachers

    def fit_teachers(self, keys, learner, datasets):
        half = [(X[:len(X) // 2], y[:len(y) // 2]) for X, y in datasets]
        return inner(self, keys, learner, half)
    mp.setattr(VmapEngine, "fit_teachers", fit_teachers)


def silo_not_folded(mp):
    """The coordinator drops one silo's update instead of folding it
    (the exchange between silos left out)."""
    from repro.federation.aggregate import StreamingVoteAggregate
    inner = StreamingVoteAggregate.add

    def add(self, update):
        if update.party_id != 0:
            inner(self, update)
    mp.setattr(StreamingVoteAggregate, "add", add)


def answer_altered(mp):
    """Every seventh party label flips where the silo's vote makes it."""
    from repro.federation.engines import VmapEngine
    inner = VmapEngine.label_queries

    def label_queries(self, *a, **kw):
        labels, gap = inner(self, *a, **kw)
        return labels.at[::7].set(1 - labels[::7]), gap
    mp.setattr(VmapEngine, "label_queries", label_queries)


FEDKT = {f.__name__: f for f in (state_unchanged, half_rows_left_out,
                                silo_not_folded, answer_altered)}
