"""Runs one benchmark cell once, on the chip JAX finds, and prints one
JSON result line as the last line of standard output.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  It puts ``src`` on ``sys.path`` itself and
keeps JAX's compile cache where the program's
``launch/compile_cache`` puts it.  It exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell needs, or
when the program is not beside ``bench/``.

Set-up (``setup_s``) runs from process start to the window: imports,
data or weights from the seed, compile or cache load, warm-up.  The
window then drives the cell's traffic for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Afterwards the program's state is freed and what the window
produced is compared with the configuration's plain reference; each
number compared is printed beside its limit, last on standard error
and last in the result line (``checks``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
# libtpu's own logs would go to a fixed /tmp path, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import common as C  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description="run one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, cell, cfg, wl, win, trace, peaks):
        self.cell = cell
        self.config = cfg
        self.workload = wl
        self.window = win
        self.trace = trace
        self.peaks = peaks


def run_cell(cell, seed, seconds, trace, *, chip=True, patch=None,
             t_start=None):
    """One run of ``cell``; returns the result line's object.

    ``chip=False`` skips the look for a TPU (tests on the CPU); a
    ``patch(cfg, wl)`` may shrink the cell for them.  Everything else
    is the run the benchmark makes."""
    t_start = T_START if t_start is None else t_start
    spec = C.benchmark_spec()
    wl = C.workload(cell)
    cfg = C.config(wl["config"])
    if patch is not None:
        cfg, wl = patch(cfg, wl)
    if not (C.ROOT / "src" / "repro").is_dir():
        raise C.BenchError(f"no program: {C.ROOT / 'src' / 'repro'} is "
                           "missing beside bench/")
    if str(C.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(C.ROOT / "src"))
    import jax

    C.enable_cache()
    if chip:
        devs = C.require_tpu(wl["chips"])
    else:
        devs = jax.devices()[:wl["chips"]]
    clock = C.CompileClock()
    sysmod = C.system(cfg["system"])
    kind = C.traffic(wl["traffic"]["kind"])
    ref = C.reference(wl["config"])
    sut = sysmod.build(cfg, wl, seed, devs, seconds)
    setup_s = time.perf_counter() - t_start
    before = clock.snapshot()
    C.log("setup", setup_s=setup_s, **before)

    tdir = C.BENCH / ".trace" / cell
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans, not every call
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    else:
        def annotate(name):
            return contextlib.nullcontext()
    try:
        win = kind.run(sut, wl["traffic"], seed, seconds, annotate)
    finally:
        if trace:
            jax.profiler.stop_trace()
    after = clock.snapshot()
    device = C.device_info(devs)
    in_window = after["compiles"] - before["compiles"]
    C.log("window", attempted=win["attempted"], failed=win["failed"],
          span_s=win["span_s"], compiles_in_window=in_window,
          **{k: C.summary(v) for k, v in win.items()
             if k in ("ttft_ms", "itl_ms", "late_s", "round_wall_s",
                      "parties_s", "server_s")})

    e2e, per_layer = C.cell_metrics(spec, cell)
    units = C.units(spec)
    metrics = {}
    breakdown = None
    if not trace:
        vals = kind.end_to_end(win)
        vals["setup_s"] = setup_s
        for m in e2e:
            metrics[m] = {"value": vals[m], "unit": units[m]}
    else:
        import trace_reduce
        summ = trace_reduce.reduce(tdir, [d.id for d in devs])
        ctx = Context(cell, cfg, wl, win, summ,
                      C.peaks(devs[0].device_kind) if chip else None)
        for m in per_layer:
            v = C.metric_reader(m).read(ctx)
            if v is not None:
                metrics[m] = {"value": v, "unit": units[m]}
        device["busy_s"] = summ.busy_s
        device["window_s"] = win["span_s"]
        breakdown = summ.breakdown()
        C.log("trace", busy_s=summ.busy_s, window_s=win["span_s"],
              modules=summ.module_table(12))
        shutil.rmtree(tdir, ignore_errors=True)

    sut.free()
    t_check = time.perf_counter()
    values = sysmod.check(sut, ref, seed, win, wl["traffic"])
    limits = wl["limits"]
    C.log("check", seconds=time.perf_counter() - t_check,
          **{k: v for k, v in values.items() if k not in limits})
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None):
    args = parse(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds, args.trace)
    except C.BenchError as e:
        print(f"bench: FAIL: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(out, allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
