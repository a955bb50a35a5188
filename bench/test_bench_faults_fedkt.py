"""The federation cells' check catches a broken round: each fault below
is planted in the program under a whole run of the cell (shrunk to a
size a CPU test can hold, the look for a chip skipped), and ``correct``
has to come out false.  The control (the reference in bfloat16 in the
program's place) has to fail the cell's limits too."""
import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
import common  # noqa: E402
import run  # noqa: E402
from faults import (answer_altered, half_rows_left_out,  # noqa: E402
                    silo_not_folded, state_unchanged)

SEED = 2 ** 31 + 1234


def small(cfg, wl):
    cfg = json.loads(json.dumps(cfg))
    cfg["sizes"].update(rows=2400, trees=3, depth=3, nn_steps=20)
    return cfg, wl


def run_small(cell):
    import jax
    jax.clear_caches()                 # no fit compiled before the fault
    return run.run_cell(cell, SEED, 0.5, 0, chip=False, patch=small,
                        t_start=time.perf_counter())


FAULTS = [("fedkt-adult.trees", state_unchanged),
          ("fedkt-adult.trees", half_rows_left_out),
          ("fedkt-adult.trees", silo_not_folded),
          ("fedkt-adult.trees", answer_altered)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_makes_the_run_incorrect(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run_small(cell)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", ["fedkt-adult.trees"])
def test_sound_small_run_is_correct(cell):
    out = run_small(cell)
    assert out["correct"] is True, out["checks"]


@pytest.mark.parametrize("cell", ["fedkt-adult.trees"])
def test_bfloat16_control_fails_the_limits(cell):
    wl = common.workload(cell)
    cfg, wl = small(common.config(wl["config"]), wl)
    sysmod = common.system(cfg["system"])
    fed = sysmod.Federation(cfg, wl["traffic"]["learners"],
                            common.seed32(SEED), None)
    got = sysmod.check(fed, common.reference(wl["config"]), SEED, None,
                       wl["traffic"], precision="bfloat16")
    assert any(got[k] > lim for k, lim in wl["limits"].items()), got
