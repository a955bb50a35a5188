"""Reduces a JAX profiler trace (``.xplane.pb``) of the window to what
the per-layer metrics read.

A TPU device plane (``/device:TPU:<id>``) has a line of XLA module
executions (one event per call of a jitted program) and a line of XLA
op executions inside them.  The reduction:

  * busy time: the union of op intervals on each chip, averaged over
    the chips used;
  * every op, grouped by the module (jitted program) whose execution
    interval holds it: count and device seconds, kernels (custom calls,
    which is how Pallas kernels appear) apart from XLA's own ops.  The
    op line nests a loop body's ops inside the loop's own event, so op
    seconds are summed per op, never per program;
  * module executions: count and device seconds per program name;
  * idle gaps: the longest stretches with no op on the device, each
    named by the innermost host span (the benchmark's own
    ``TraceAnnotation``s and the runtime's) that holds its midpoint.

All Pallas kernels of this program are named ``_kernel``, so a kernel
is told apart by the program that holds it.
"""
from __future__ import annotations

import bisect
import collections
import re
from dataclasses import dataclass, field
from pathlib import Path

KERNEL_MARKS = ("custom-call", "custom_call", "pallas", "_kernel",
                "mosaic")


def _stats(ev):
    try:
        return {str(k): str(v) for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def is_kernel(name, stats):
    text = " ".join([name] + [stats.get(k, "") for k in
                              ("long_name", "hlo_category", "tf_op",
                               "category")]).lower()
    return any(m in text for m in KERNEL_MARKS)


def op_base(name):
    """``%fusion.3 = f32[8] fusion(...)`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0]


def module_base(name):
    """``jit_prefill(12)`` -> ``jit_prefill``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def union_length(intervals):
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals, lo, hi):
    """Idle stretches (start, end) inside [lo, hi] between intervals."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


@dataclass
class Summary:
    busy_s: float = 0.0
    span_s: float = 0.0
    chips: int = 0
    # (module, op, is_kernel) -> [count, seconds]
    ops: dict = field(default_factory=dict)
    # module -> [count, seconds]
    modules: dict = field(default_factory=dict)
    idle: list = field(default_factory=list)      # [(name, seconds)]

    def module_calls(self, pattern):
        rx = re.compile(pattern)
        return sum(c for m, (c, _) in self.modules.items() if rx.search(m))

    def module_seconds(self, pattern):
        """Device seconds of the executions of programs matching
        ``pattern`` (the op line nests a loop's body inside the loop's
        own event, so summing ops would count a loop twice)."""
        rx = re.compile(pattern)
        return sum(s for m, (_, s) in self.modules.items() if rx.search(m))

    def kernel_seconds(self, pattern):
        rx = re.compile(pattern)
        return sum(s for (m, _, k), (_, s) in self.ops.items()
                   if k and rx.search(m))

    def module_table(self, n):
        rows = sorted(self.modules.items(), key=lambda kv: -kv[1][1])
        return [[m, c, s] for m, (c, s) in rows[:n]]

    def breakdown(self):
        by_op = collections.Counter()
        for (m, op, k), (_, s) in self.ops.items():
            by_op[f"{m}/{op}"] += s
        return {"device_ops": [[n, s] for n, s in by_op.most_common(10)],
                "idle_gaps": self.idle[:10]}


def _device_planes(pd, device_ids):
    planes = {}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) in device_ids:
            planes[int(m.group(1))] = plane
    return planes


def _host_spans(pd):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name))
    spans.sort()
    return spans


def _name_gap(spans, starts, t):
    """Innermost host span holding time t."""
    best = None
    i = bisect.bisect_right(starts, t)
    for s, e, name in reversed(spans[max(0, i - 2000):i]):
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no host span"


def reduce(trace_dir, device_ids):
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    return reduce_profile(pd, device_ids)


def reduce_profile(pd, device_ids):
    planes = _device_planes(pd, set(device_ids))
    summ = Summary(chips=len(planes))
    ops = collections.defaultdict(lambda: [0, 0.0])
    modules = collections.defaultdict(lambda: [0, 0.0])
    all_gaps = []
    busy = []
    for plane in planes.values():
        lines = {line.name: list(line.events) for line in plane.lines}
        mod_ev = sorted(lines.get("XLA Modules", []),
                        key=lambda e: e.start_ns)
        op_ev = lines.get("XLA Ops", [])
        if not op_ev:
            op_ev = mod_ev
        starts = [e.start_ns for e in mod_ev]
        for ev in mod_ev:
            rec = modules[module_base(ev.name)]
            rec[0] += 1
            rec[1] += ev.duration_ns * 1e-9
        ivals = []
        for ev in op_ev:
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            mod = (module_base(mod_ev[i].name) if i >= 0 and
                   ev.start_ns <= mod_ev[i].start_ns
                   + mod_ev[i].duration_ns else "no module")
            st = _stats(ev)
            rec = ops[(mod, op_base(ev.name), is_kernel(ev.name, st))]
            rec[0] += 1
            rec[1] += ev.duration_ns * 1e-9
            ivals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
        if ivals:
            busy.append(union_length(ivals) * 1e-9)
            lo = min(s for s, _ in ivals)
            hi = max(e for _, e in ivals)
            summ.span_s = max(summ.span_s, (hi - lo) * 1e-9)
            all_gaps.extend(gaps(ivals, lo, hi))
    summ.busy_s = sum(busy) / len(busy) if busy else 0.0
    summ.ops = dict(ops)
    summ.modules = dict(modules)
    spans = _host_spans(pd)
    starts = [s for s, _, _ in spans]
    named = collections.Counter()
    for a, b in sorted(all_gaps, key=lambda g: g[0] - g[1])[:2000]:
        named[_name_gap(spans, starts, (a + b) / 2)] += (b - a) * 1e-9
    summ.idle = [[n, s] for n, s in named.most_common(10)]
    return summ
