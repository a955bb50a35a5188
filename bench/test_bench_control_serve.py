"""The serving cells' control: the reference with fp8 weights and bf16
activations, put in the program's place, has to fail the cells' limit
on the widest logit gap.  Run here at the published widths with two
layers and an 8,192-row vocabulary, a size a CPU test can hold; on the
chip it was read at the cell's own size (PERF.md)."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
import common  # noqa: E402

SEED = 2 ** 31 + 99


@pytest.fixture(scope="module")
def gaps():
    cfg = dict(common.config("phi4-mini-3.8b"), num_hidden_layers=2,
               vocab_size=8192)
    ref = common.reference("phi4-mini-3.8b")
    params = common.system("lm_serve").make_params(cfg, SEED)
    rng = np.random.default_rng(SEED)
    reqs = [(rng.integers(0, 8192, (n,)).astype(np.int32),
             list(rng.integers(0, 8192, (48,))))
            for n in (40, 64, 24)]
    return (np.concatenate(ref.served_gaps(cfg, params, reqs)),
            np.concatenate(ref.served_gaps(cfg, params, reqs,
                                           control=True)))


@pytest.mark.parametrize("cell", ["phi4-mini-3.8b.chat",
                                  "phi4-mini-3.8b.offline"])
def test_fp8_control_fails_the_limit(gaps, cell):
    _, control = gaps
    limit = common.workload(cell)["limits"]["max_logit_gap"]
    assert control.max() > limit


def test_gaps_are_never_negative(gaps):
    served, control = gaps
    assert served.min() >= 0 and control.min() >= 0
