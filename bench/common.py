"""Shared pieces of the benchmark harness: file lookup by name, the
device check, compile accounting, statistics and the result line.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

  bench/workloads/<cell>.json   configuration, traffic mix, limits
  bench/traffic/<mix>.json      the mix's parameters and generator kind
  bench/configs/<config>.json   sizes, source, reduced/assumed, system
  bench/configs/<config>.py     the configuration's plain reference
  bench/systems/<system>.py     builds the system under test
  bench/traffic/<kind>.py       the generator: drives the window
  bench/metrics/<metric>.py     reads one per-layer metric
"""
from __future__ import annotations

import importlib.util
import json
import math
import statistics
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """A run that cannot produce a result line (no chip, bad files)."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Imports a file by path; names such as ``device_idle.round`` are
    not valid module names, so the import system cannot find them."""
    if not path.is_file():
        raise BenchError(f"missing file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec(root: Path = ROOT):
    return load_json(root / "BENCHMARK.json")


def workload(name: str):
    """A cell's file, its traffic mix (``bench/traffic/<mix>.json``)
    read in under ``traffic``, the mix's name kept as ``mix``."""
    wl = load_json(BENCH / "workloads" / f"{name}.json")
    mix = wl["traffic"]
    wl["traffic"] = dict(load_json(BENCH / "traffic" / f"{mix}.json"),
                         mix=mix)
    return wl


def config(name: str):
    return load_json(BENCH / "configs" / f"{name}.json")


def reference(config_name: str):
    return load_module(BENCH / "configs" / f"{config_name}.py",
                       "ref_" + config_name)


def system(name: str):
    return load_module(BENCH / "systems" / f"{name}.py", "sys_" + name)


def traffic(kind: str):
    return load_module(BENCH / "traffic" / f"{kind}.py", "traffic_" + kind)


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py", "metric_" + name)


def cell_metrics(spec, cell: str):
    """(end_to_end names, per_layer names) that ``cell`` reports."""
    def applies(m):
        return "workloads" not in m or cell in m["workloads"]
    e2e = [m["name"] for m in spec["end_to_end"] if applies(m)]
    per_layer = [m["name"] for m in spec["per_layer"]
                 if applies(m) and m["moves"] in e2e]
    return e2e, per_layer


def units(spec):
    return {m["name"]: m["unit"]
            for m in spec["end_to_end"] + spec["per_layer"]}


def seed32(seed: int) -> int:
    """The run's seed as JAX keys hold it: PRNGKey keeps 32 bits when
    64-bit mode is off, so larger seeds are folded, never truncated
    silently into another seed's key."""
    return seed % (2 ** 32)


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------
def require_tpu(chips: int):
    """The devices a cell may use; raises on another platform or too
    few chips.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(
            f"needs a TPU, but JAX found platform {devs[0].platform!r} "
            f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def device_info(devs):
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devs]
    peak = [p for p in peak if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peak) if peak else None}


def peaks(kind: str):
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def enable_cache():
    """The program's persistent compile cache, with every program
    written to it: below JAX's default one-second threshold small
    programs would compile again in every run."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileClock:
    """Counts backend compiles and persistent-cache hits (JAX's own
    monitoring events), so a compile inside the window shows."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return {"compiles": self.compiles, "compile_s": self.seconds,
                "cache_hits": self.hits}


# ---------------------------------------------------------------------------
# Statistics
# ---------------------------------------------------------------------------
def percentile(xs, q: float):
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sample."""
    s = sorted(xs)
    k = max(0, math.ceil(q / 100.0 * len(s)) - 1)
    return s[k]


def summary(xs):
    if not xs:
        return {"n": 0}
    return {"n": len(xs), "median": statistics.median(xs),
            "p95": percentile(xs, 95), "max": max(xs)}


def log(tag: str, **kw):
    print(f"[{tag}] " + json.dumps(kw, default=float), file=sys.stderr,
          flush=True)
