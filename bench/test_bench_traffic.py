"""The generators give the same traffic for a seed, and the same sizes
for every seed."""
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
import common  # noqa: E402
import traffic_gen  # noqa: E402

CHAT = common.load_json(BENCH / "traffic" / "chat.json")
OFFLINE = common.load_json(BENCH / "traffic" / "offline.json")


def _open_loop():
    return common.traffic("open_loop")


def test_open_loop_schedule_repeats_for_a_seed():
    a = _open_loop().plan(CHAT, 2 ** 31 + 17, 45, 200_064)
    b = _open_loop().plan(CHAT, 2 ** 31 + 17, 45, 200_064)
    assert a[0] == b[0] and a[2] == b[2]
    assert all(np.array_equal(x, y) for x, y in zip(a[1], b[1]))


def test_open_loop_seeds_share_sizes_not_order():
    a = _open_loop().plan(CHAT, 1, 45, 200_064)
    b = _open_loop().plan(CHAT, 2, 45, 200_064)
    pa = sorted(len(p) for p in a[1])
    pb = sorted(len(p) for p in b[1])
    assert [len(p) for p in a[1]] != [len(p) for p in b[1]]
    # the window may cut a request or two from the end of the schedule
    n = min(len(pa), len(pb))
    assert abs(len(pa) - len(pb)) <= 2
    assert abs(np.median(pa[:n]) - np.median(pb[:n])) <= 16
    assert a[0][0] == 0.0 and max(a[0]) < 45


def test_lengths_follow_the_mix():
    n = 225
    pl = traffic_gen.lognormal_set(n, CHAT["prompt"])
    assert pl.min() >= CHAT["prompt"]["min"]
    assert pl.max() <= CHAT["prompt"]["max"]
    assert abs(np.median(pl) - CHAT["prompt"]["median"]) <= 2
    gaps = traffic_gen.exponential_gaps(n, CHAT["rate_rps"])
    assert abs(gaps.mean() * CHAT["rate_rps"] - 1) < 0.05


def test_closed_loop_passes_repeat_the_size_set():
    k = OFFLINE["clients"]
    gen = traffic_gen.cycled_requests(k, OFFLINE, 5, 1000)
    first = [next(gen) for _ in range(2 * k)]
    a = sorted(len(p) for p, _ in first[:k])
    b = sorted(len(p) for p, _ in first[k:])
    assert a == b
    assert [len(p) for p, _ in first[:k]] != [len(p) for p, _ in first[k:]]
    assert all(OFFLINE["output"]["min"] <= o <= OFFLINE["output"]["max"]
               for _, o in first)


def test_warm_lengths_cover_every_prompt_sent():
    ol = _open_loop()
    _, prompts, _ = ol.plan(CHAT, 99, 45, 1000)
    assert {len(p) for p in prompts} <= set(ol.warm_lengths(CHAT, 45))
