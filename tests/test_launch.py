"""Entry points: the compile-cache placement every ``main()`` applies,
and ``federate.main`` / ``serve.main`` driven in process with argv
lists."""
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.launch import compile_cache, federate, serve
from repro.serving import Engine

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restores JAX's cache directory after a test moves it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                               cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, JAX already uses it: the
    helper reports it and sets no directory of its own."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_federate_main_runs_a_local_round_in_process(monkeypatch,
                                                     tmp_path):
    """``federate.main(argv)`` runs the local role without touching
    sys.argv and returns the RoundResult: every party of a mixed fleet
    arrived over TCP, none failed or dropped."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    res = federate.main([
        "local", "--parties", "3", "--learners", "nn,rf,gbdt",
        "--engine", "vmap", "--privacy", "L2", "--n-train", "600",
        "--steps", "20", "--subsets", "2", "--trees", "4", "--depth", "3",
        "--port", "0"])
    sock = res.meta["socket"]
    assert sorted(sock["arrived"]) == [0, 1, 2]
    assert sock["failed"] == {} and sock["dropped"] == []
    assert math.isfinite(res.epsilon)
    assert 0.0 <= res.accuracy <= 1.0


SERVE_SMOKE = ["--smoke", "--concurrent", "3", "--slots", "2",
               "--prompt-len", "12", "--max-tokens", "4"]


def test_serve_main_engine_path_serves_bf16_weights(monkeypatch,
                                                    tmp_path):
    """``serve.main`` takes the engine path with weights held in the
    compute dtype, compiles nothing after warmup, and serves exactly
    the tokens the same weights give when held in float32."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    run = serve.main(["--arch", "phi4-mini-3.8b"] + SERVE_SMOKE)
    assert run.path == "engine" and run.reason == ""
    assert {leaf.dtype for leaf in jax.tree.leaves(run.params)} == \
        {jnp.dtype(run.model.cfg.dtype)}
    assert run.engine.compile_counts() == run.warm_counts
    assert [r.num_tokens for r in run.results] == [4, 4, 4]

    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), run.params)
    eng = Engine(run.model, f32, num_slots=2, cache_len=256)
    ref = eng.serve(run.prompts, 4)
    assert [r.tokens for r in ref] == [r.tokens for r in run.results]


def test_serve_main_reports_the_serial_fallback(monkeypatch, tmp_path,
                                                capsys):
    """A config the engine refuses still serves, but ``main`` says so
    and returns ``path == "serial"`` with the reason."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    run = serve.main(["--arch", "recurrentgemma-2b"] + SERVE_SMOKE)
    assert run.path == "serial" and "recurrent" in run.reason
    assert run.engine is None
    assert "FALLBACK" in capsys.readouterr().out
