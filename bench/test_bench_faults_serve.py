"""The serving cells' check catches a broken engine: each fault below is
planted in the program under a whole run of a serving cell (the model
shrunk to the program's smoke sizes, the look for a chip skipped), and
``correct`` has to come out false."""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
import run  # noqa: E402

SEED = 2 ** 31 + 4321


def small(cfg, wl):
    cfg = dict(cfg, hidden_size=256, intermediate_size=512,
               num_hidden_layers=2, num_attention_heads=8,
               num_key_value_heads=4, head_dim=32, vocab_size=512,
               program_preset="smoke",
               engine={"num_slots": 4, "cache_len": 128, "max_batch": 4})
    t = dict(wl["traffic"])
    t.update(prompt={"median": 16, "sigma": 0.6, "min": 4, "max": 48},
             output={"median": 8, "sigma": 0.7, "min": 2, "max": 16},
             check_tokens=400)
    if t["kind"] == "open_loop":
        t.update(rate_rps=20.0, drain_s=20)
    else:
        t.update(clients=8)
    return cfg, dict(wl, traffic=t)


def run_small(cell):
    import jax
    jax.clear_caches()
    return run.run_cell(cell, SEED, 2, 0, chip=False, patch=small,
                        t_start=time.perf_counter())


def cache_unchanged(mp):
    """Every decode step hands back the KV cache it was given."""
    from repro.core import distill
    inner = distill.make_decode_step

    def make_decode_step(model):
        step = inner(model)

        def decode(params, token, cache, pos):
            nxt, _ = step(params, token, cache, pos)
            return nxt, cache
        return decode
    mp.setattr(distill, "make_decode_step", make_decode_step)


def half_batch_left_out(mp):
    """A prefill bucket computes only its first half of rows; the rest
    get the first row's prompt."""
    from repro.core import distill
    inner = distill.make_bucket_prefill_step

    def make_bucket_prefill_step(model):
        step = inner(model)

        def prefill(params, tokens, plens):
            b = tokens.shape[0]
            if b > 1:
                keep = tokens[: b // 2]
                tokens = keep[(jnp_arange(b) % (b // 2))]
            return step(params, tokens, plens)
        return prefill
    mp.setattr(distill, "make_bucket_prefill_step",
               make_bucket_prefill_step)


def jnp_arange(n):
    import jax.numpy as jnp
    return jnp.arange(n)


def token_altered(mp):
    """Every fifth token the engine emits is replaced by its successor."""
    from repro.serving.engine import Engine
    inner = Engine._emit
    count = [0]

    def emit(self, req, token, now, done):
        count[0] += 1
        if count[0] % 5 == 0:
            token = (token + 1) % self.model.cfg.vocab_size
        return inner(self, req, token, now, done)
    mp.setattr(Engine, "_emit", emit)


FAULTS = [("phi4-mini-3.8b.offline", cache_unchanged),
          ("phi4-mini-3.8b.offline", half_batch_left_out),
          ("phi4-mini-3.8b.chat", token_altered)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_makes_the_run_incorrect(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run_small(cell)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", ["phi4-mini-3.8b.chat",
                                  "phi4-mini-3.8b.offline"])
def test_sound_small_run_is_correct(cell):
    out = run_small(cell)
    assert out["correct"] is True, out["checks"]
