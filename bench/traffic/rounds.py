"""Rounds back to back: one federation round after another on the same
seed, for as long as the window lasts.  A round starts only while the
window is open, so the span of the whole rounds can overrun it by part
of one round; the round time is that span over the rounds in it.
"""
from __future__ import annotations

import time


def run(sut, params, seed, seconds, annotate):
    del params, seed
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        with annotate("bench.round"):
            sut.round()
        n += 1
    span = time.perf_counter() - t0
    return {"attempted": n,
            "failed": sum(1 for r in sut.rounds if r["failed"]),
            "window_s": span,
            "span_s": span,
            "round_wall_s": [r["wall_s"] for r in sut.rounds],
            "parties_s": [r["parties_s"] for r in sut.rounds],
            "server_s": [r["server_s"] for r in sut.rounds]}


def end_to_end(win):
    return {"round_s": win["span_s"] / win["attempted"]}
