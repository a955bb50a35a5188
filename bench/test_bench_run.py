"""bench/run.py refuses to run anywhere but on a TPU with the program
beside it, printing no result line."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
ARGS = ["--workload", "phi4-mini-3.8b.chat", "--seed", str(2 ** 31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_off_a_tpu(tmp_path):
    cache = tmp_path / "cache"
    r = _run(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    r = _run(tmp_path, {})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no program" in r.stderr
