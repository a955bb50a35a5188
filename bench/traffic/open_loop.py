"""Open-loop traffic: independent users send requests on a seeded
Poisson schedule at a fixed rate, whether or not earlier ones have
finished.

Each request is timed from the moment it was due, so a stall that
delays later submissions counts against them.  Requests due inside
the window are the attempted ones; once the window closes nothing new
is sent, and the engine runs on until every attempted request is
done, for at most ``drain_s`` seconds more.  A request that never gets
its first token counts as failed, and as missing every latency limit.
"""
from __future__ import annotations

import math
import time

import numpy as np

import traffic_gen


def _count(params, seconds):
    return max(1, int(round(params["rate_rps"] * seconds)))


def plan(params, seed, seconds, vocab):
    """(due offsets in s, prompts, output budgets) of the requests due
    inside the window."""
    n = _count(params, seconds)
    rng = np.random.default_rng(seed + 3)
    gaps = rng.permutation(traffic_gen.exponential_gaps(
        n, params["rate_rps"]))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompts, outs = traffic_gen.requests(n, params, seed, vocab)
    keep = due < seconds
    return ([float(d) for d in due[keep]],
            [p for p, k in zip(prompts, keep) if k],
            [o for o, k in zip(outs, keep) if k])


def warm_lengths(params, seconds):
    return traffic_gen.prompt_lengths(_count(params, seconds), params)


def run(sut, params, seed, seconds, annotate):
    eng = sut.engine
    due, prompts, outs = plan(params, seed, seconds, sut.vocab)
    clock = eng.clock
    sent = []                                  # (due_abs, state)
    steps = 0
    t0 = clock()
    stop = t0 + seconds + params["drain_s"]
    nxt = 0
    while True:
        now = clock()
        while nxt < len(due) and t0 + due[nxt] <= now:
            st = eng.submit(prompts[nxt], outs[nxt])
            sent.append((t0 + due[nxt], st))
            nxt += 1
        if eng.scheduler.idle:
            if nxt >= len(due):
                break
            time.sleep(min(max(t0 + due[nxt] - now, 0.0), 0.002))
            continue
        if now > stop:
            break
        with annotate("bench.engine_step"):
            eng.step()
        steps += 1
    t_stop = clock()

    ttft, itl, served = [], [], []
    admitted = [(st.prompt, list(st.tokens)) for _, st in sent
                if st.token_times]
    for due_abs, st in sent:
        if st.status != "done":
            ttft.append(math.inf)
            continue
        ttft.append((st.token_times[0] - due_abs) * 1e3)
        itl.extend((np.diff(st.token_times) * 1e3).tolist())
        served.append((st.prompt, list(st.tokens)))
    return {
        "attempted": len(sent),
        "failed": sum(1 for x in ttft if math.isinf(x)),
        "window_s": seconds,
        "span_s": t_stop - t0,
        "ttft_ms": ttft,
        "itl_ms": itl,
        "late_s": [st.t_submit - d for d, st in sent],
        "steps": steps,
        "served": served,
        "admitted": admitted,
        "tokens": sum(len(t) for _, t in served),
    }


def end_to_end(win):
    """A request that never got its first token waited at least the
    whole run; that bound stands in for its infinite time to first
    token."""
    import common
    bound = win["span_s"] * 1e3
    ttft = [min(x, bound) for x in win["ttft_ms"]]
    return {"ttft_p95_ms": common.percentile(ttft, 95),
            "itl_p95_ms": common.percentile(win["itl_ms"], 95)}
