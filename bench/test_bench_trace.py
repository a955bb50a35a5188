"""The trace reduction (bench/trace_reduce.py): on hand-made planes, and
on a small trace recorded on a TPU v5e and kept under bench/testdata."""
import sys
from dataclasses import dataclass, field
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
import trace_reduce as T  # noqa: E402

# one admission (prefill of a 256 bucket, insert) and three decode steps
# of the chat engine, TPU v5 lite, python tracer off; xz-compressed
RECORDED = BENCH / "testdata" / "serve_steps.xplane.pb.xz"


@dataclass
class Ev:
    name: str
    start_ns: int
    duration_ns: int
    stats: list = field(default_factory=list)


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


@dataclass
class Profile:
    planes: list


def _profile():
    mods = [Ev("jit_prefill(3)", 0, 100), Ev("jit_decode(4)", 150, 50),
            Ev("jit_decode(4)", 300, 50)]
    ops = [Ev("fusion.1", 0, 40), Ev("custom-call.2", 40, 60),
           Ev("fusion.7", 150, 50), Ev("fusion.7", 300, 50)]
    dev = Plane("/device:TPU:0", [Line("XLA Modules", mods),
                                  Line("XLA Ops", ops)])
    other = Plane("/device:TPU:1", [Line("XLA Ops", [Ev("x", 0, 999)])])
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.engine_step", -10, 400), Ev("admit", 100, 50),
        Ev("sample", 210, 80)])])
    return Profile([dev, other, host])


def test_union_and_gaps():
    assert T.union_length([(0, 10), (5, 20), (30, 40)]) == 30
    assert T.gaps([(0, 10), (5, 20), (30, 40)], 0, 50) == [(20, 30),
                                                            (40, 50)]


def test_reduce_hand_made_planes():
    s = T.reduce_profile(_profile(), [0])
    assert s.chips == 1                          # TPU:1 is not ours
    assert s.busy_s == pytest.approx(200e-9)
    assert s.module_calls(r"jit_decode") == 2
    assert s.module_calls(r"jit_prefill") == 1
    assert s.module_seconds(r"jit_decode") == pytest.approx(100e-9)
    assert s.kernel_seconds(r"jit_prefill") == pytest.approx(60e-9)
    assert s.kernel_seconds(r"jit_decode") == 0
    # idle 100-150 under "admit", 200-300 under "sample"
    assert dict((n, v) for n, v in s.idle) == pytest.approx(
        {"admit": 50e-9, "sample": 100e-9})
    b = s.breakdown()
    assert b["device_ops"][0][0] == "jit_decode/fusion.7"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_kernel_marks():
    assert T.is_kernel("custom-call.3", {})
    assert T.is_kernel("fusion.2", {"long_name": "tpu_custom_call(...)"})
    assert not T.is_kernel("fusion.2", {"long_name": "add(f32[8])"})
    assert T.module_base("jit_prefill(12)") == "jit_prefill"


def test_recorded_tpu_trace():
    import lzma

    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(lzma.decompress(
        RECORDED.read_bytes()))
    s = T.reduce_profile(pd, [0])
    assert s.chips == 1
    assert 0 < s.busy_s <= s.span_s
    assert s.module_calls(r"jit_decode") == 3
    assert s.module_calls(r"jit_prefill") == 1
    assert s.module_calls(r"jit_insert_cache") == 1
    decode = s.module_seconds(r"jit_decode")
    assert decode > 0
    assert s.kernel_seconds(r"jit_prefill") > 0      # flash attention
    # decode attends through XLA, not the kernel
    assert s.kernel_seconds(r"jit_decode") < 1e-3 * decode
    assert s.idle and all(v > 0 for _, v in s.idle)
