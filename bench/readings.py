"""Reads the numbers that ``correct`` compares over many seeds in one
process, for the program and for the control (the reference at the
next precision down in the program's place), to set each limit from.

    python bench/readings.py --workload <cell> --seeds <n> \
        --control <k> --seconds <s> [--first-seed <n>] \
        [--fault <name>,...]

Each seed gets its own inputs or weights and a short window of the
cell's own traffic; the first ``--control`` seeds also read the
control.  With ``--fault``, each named fault of ``bench/faults.py`` is
planted in the program instead and a whole run of the cell is made
under it on each seed, at the cell's own size.  One JSON line per seed.
Not part of a run.
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

CONTROL = {"fedkt": "bfloat16", "lm_serve": "fp8"}


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(C.ROOT / "src"))
    if args.fault:
        return faults(args)
    C.enable_cache()
    wl = C.workload(args.workload)
    cfg = C.config(wl["config"])
    devs = C.require_tpu(wl["chips"])
    sysmod = C.system(cfg["system"])
    kind = C.traffic(wl["traffic"]["kind"])
    ref = C.reference(wl["config"])
    srv = None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        if cfg["system"] == "lm_serve":
            if srv is None:
                srv = sysmod.build(cfg, wl, seed, devs, args.seconds)
            else:
                srv.reseed(seed)
            sut = srv
        else:
            sut = sysmod.build(cfg, wl, seed, devs, args.seconds)
        win = kind.run(sut, wl["traffic"], seed, args.seconds,
                       lambda name: _null())
        if cfg["system"] != "lm_serve":
            sut.free()
        got = sysmod.check(sut, ref, seed, win, wl["traffic"])
        row = {"seed": seed, "program": got,
               "attempted": win["attempted"], "failed": win["failed"]}
        if i < args.control:
            row["control"] = sysmod.check(sut, ref, seed, win, wl["traffic"],
                                          precision=CONTROL[cfg["system"]])
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
    print(json.dumps({"device": C.device_info(devs)}))
    return 0


def faults(args):
    """Whole runs of the cell with each fault planted, on each seed."""
    import jax

    import faults as F
    import run

    for name in args.fault.split(","):
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            t0 = time.perf_counter()
            with F.Patch() as mp:
                F.FEDKT[name](mp)
                jax.clear_caches()     # no program traced before the fault
                out = run.run_cell(args.workload, seed, args.seconds, 0,
                                   t_start=t0)
            print(json.dumps({"fault": name, "seed": seed,
                              "correct": out["correct"],
                              "checks": out["checks"],
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
