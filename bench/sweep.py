"""Finds the knee of an open-loop serving cell once: the cell's traffic
at several fixed rates, one window each, in one process (the model is
built and warmed up once).  Prints one JSON line per rate.

    python bench/sweep.py --workload phi4-mini-3.8b.chat --seed <n> \
        --seconds 30 --rates 3,5,7,9

Not part of a run: the cell itself offers load at the fixed rate in
its mix file, set at about four fifths of the knee this finds.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
# libtpu's own logs would go to a fixed /tmp path, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import common as C  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    sys.path.insert(0, str(C.ROOT / "src"))
    C.enable_cache()
    wl = C.workload(args.workload)
    cfg = C.config(wl["config"])
    devs = C.require_tpu(wl["chips"])
    kind = C.traffic(wl["traffic"]["kind"])
    warm = sorted({n for r in rates for n in kind.warm_lengths(
        dict(wl["traffic"], rate_rps=r), args.seconds)})
    srv = C.system(cfg["system"]).Server(cfg, wl, args.seed, devs, warm)
    for rate in rates:
        params = dict(wl["traffic"], rate_rps=rate)
        t0 = time.perf_counter()
        win = kind.run(srv, params, args.seed, args.seconds,
                       lambda name: _null())
        done = [x for x in win["ttft_ms"] if x != float("inf")]
        late_first = sum(1 for x in win["ttft_ms"]
                         if x > 1e3 * args.seconds)
        print(json.dumps({
            "rate_rps": rate, "attempted": win["attempted"],
            "failed": win["failed"],
            "completed_rps": len(done) / win["span_s"],
            "span_s": win["span_s"], "tokens": win["tokens"],
            "ttft_ms": C.summary(win["ttft_ms"]),
            "itl_ms": C.summary(win["itl_ms"]),
            "late_s": C.summary(win["late_s"]),
            "first_token_after_window": late_first,
            "wall_s": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"device": C.device_info(devs)}))
    return 0


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    sys.exit(main())
