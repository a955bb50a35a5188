"""Closed-loop traffic: a fixed number of clients, each sending its next
request as soon as its previous one has finished (offline batch
generation).  Request sizes run through one fixed set, as many entries
as clients, pass after pass, so every seed sends the same sizes.  The
window is cut at ``seconds``: what counts is every output token
emitted inside it.
"""
from __future__ import annotations

import numpy as np

import traffic_gen


def warm_lengths(params, seconds):
    return traffic_gen.prompt_lengths(params["clients"], params)


def run(sut, params, seed, seconds, annotate):
    eng = sut.engine
    gen = traffic_gen.cycled_requests(params["clients"], params, seed,
                                      sut.vocab)
    # a client's next request is made before the window, so making it
    # costs the window nothing
    ahead = [next(gen) for _ in range(2 * params["clients"])]
    clock = eng.clock
    sent = []
    t0 = clock()
    end = t0 + seconds
    for _ in range(params["clients"]):
        sent.append(eng.submit(*ahead.pop(0)))
    steps = 0
    while clock() < end:
        with annotate("bench.engine_step"):
            finished = eng.step()
        steps += 1
        for _ in finished:
            sent.append(eng.submit(*ahead.pop(0)))
            ahead.append(next(gen))
    t_stop = clock()
    tokens, prompts_in, positions = 0, [], []
    for st in sent:
        times = np.asarray(st.token_times)
        inside = (times >= t0) & (times < t_stop)
        tokens += int(inside.sum())
        if st.t_admit is not None and t0 <= st.t_admit < t_stop:
            prompts_in.append(st.plen)
        # decode token k (k >= 1) is made at position plen + k - 1
        positions.extend(st.plen + k - 1 for k in np.nonzero(inside)[0]
                         if k >= 1)
    served = [(st.prompt, list(st.tokens)) for st in sent
              if st.status == "done"]
    return {
        "attempted": len(served),
        "failed": 0,
        "window_s": t_stop - t0,
        "span_s": t_stop - t0,
        "tokens_in_window": tokens,
        "window_prompts": prompts_in,
        "window_positions": positions,
        "admitted": [(st.prompt, list(st.tokens)) for st in sent
                     if st.token_times],
        "in_flight": sum(1 for st in sent if st.status != "done"),
        "steps": steps,
        "served": served,
        "tokens": sum(len(t) for _, t in served),
    }


def end_to_end(win):
    return {"tok_per_s": win["tokens_in_window"] / win["window_s"]}
