"""Chip smoke: the system's two user paths, once each, on one TPU chip.

    python chip_smoke.py

Runs from the root of a checkout, in ONE process (a TPU belongs to the
process that first touches JAX), and needs no environment: it puts
``src`` on ``sys.path`` itself and places JAX's compilation cache with
``repro.launch.compile_cache``.

  federation  one FedKT round (the paper's Algorithm 1) through
              ``repro.launch.federate.main``: 5 silos of a mixed
              nn/rf/gbdt fleet on the vmap engine, L2 (party-side
              Laplace) privacy, Adult-sized data (48,842 rows), full
              quorum over localhost TCP.
  serve       the distilled-student serving tier at published widths
              through ``repro.launch.serve.main``: phi4-mini-3.8b
              (random bf16 weights from a seed), 4 streams x 16 tokens
              through the continuous-batching engine.

Every phase prints its facts on lines of its own (wall and compile
seconds, peak device bytes, resolved kernel impl, accuracy, tokens).
The script fails — non-zero exit, no result line — when JAX finds no
TPU, when a phase raises, or when a check does not hold.  On success
its last line is the one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# what FEDERATION_ARGV gives on a CPU host (JAX 0.9.0, xla kernel
# path) at the same seed; the chip runs the Pallas kernels, whose float
# sums may differ in the last bits
CPU_FEDERATION_ACCURACY = 0.6482148706190632

FEDERATION_ARGV = [
    "local", "--parties", "5", "--learners", "nn,rf,gbdt,nn,rf",
    "--engine", "vmap", "--privacy", "L2", "--n-train", "48842",
    "--partitions", "2", "--subsets", "3", "--seed", "0"]
SERVE_ARGV = [
    "--arch", "phi4-mini-3.8b", "--concurrent", "4", "--slots", "4",
    "--prompt-len", "128", "--max-tokens", "16", "--cache-len", "256"]


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def fact(phase, **kw):
    print(f"[{phase}] " + json.dumps(kw, default=str), flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit is
    counted at its retrieval time) and counts cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def reset(self):
        self.seconds, self.hits = 0.0, 0


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def compile_with_kernel(jitted, *args):
    """The compiled program of ``jitted`` at ``args``; fails unless it
    holds a Pallas TPU kernel."""
    compiled = jitted.lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{jitted.__name__}: compiled program has no tpu_custom_call")
    return compiled


def phase_federation(dev, clock):
    import jax
    import jax.numpy as jnp

    from repro.core.voting import teacher_vote
    from repro.federation import query_budget
    from repro.kernels import ops
    from repro.launch import federate

    impl = ops.resolve_impl("auto")
    check(impl == "kernel", f"ops impl 'auto' resolves to {impl!r} on "
          f"{dev.platform}, not 'kernel'")

    clock.reset()
    t0 = time.perf_counter()
    res = federate.main(FEDERATION_ARGV)
    wall = time.perf_counter() - t0
    sock = res.meta.get("socket", {})
    arrived = sock.get("arrived", [])
    check(len(arrived) == 5, f"{len(arrived)}/5 parties arrived")
    check(not sock.get("failed"), f"parties failed: {sock.get('failed')}")
    check(not sock.get("dropped") and not res.meta.get("dropped_parties"),
          f"parties dropped: {sock.get('dropped')}")
    eps = res.epsilon
    check(eps is not None and math.isfinite(eps), f"epsilon {eps}")
    acc = float(res.accuracy)
    check(acc > 0.5, f"accuracy {acc} <= 0.5")

    # the party vote step at this round's shapes: t teachers x T queries
    args = federate.build_parser().parse_args(FEDERATION_ARGV)
    session = federate.build_session(args, "inprocess")
    T, _ = query_budget(session.cfg, len(session.data["X_public"]))
    def party_vote(preds, key):
        return teacher_vote(preds, 2, gamma=args.gamma, key=key).labels

    compile_with_kernel(jax.jit(party_vote),
                        jnp.zeros((args.subsets, T), jnp.int32),
                        jax.random.PRNGKey(0))
    fact("federation", wall_s=wall, compile_s=clock.seconds,
         cache_hits=clock.hits, peak_bytes_in_use=peak_bytes(dev),
         impl=impl, accuracy=acc, cpu_accuracy=CPU_FEDERATION_ACCURACY,
         epsilon=eps, arrived=len(arrived), queries=T,
         party_seconds=res.meta.get("seconds"))


def phase_serve(dev, clock):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.models import transformer

    clock.reset()
    t0 = time.perf_counter()
    run = serve.main(SERVE_ARGV)
    wall = time.perf_counter() - t0
    check(run.path == "engine",
          f"serving took the {run.path} path, not the engine: "
          f"{run.reason}")
    cfg = run.model.cfg
    check(cfg.num_layers == 32 and cfg.d_model == 3072
          and cfg.vocab_size == 200_064, f"not the published config: {cfg}")
    eng, vocab = run.engine, cfg.vocab_size
    check(len(run.results) == 4, f"{len(run.results)} streams came back")
    for r in run.results:
        check(r.num_tokens == 16, f"request {r.rid}: {r.num_tokens} tokens")
        check(all(0 <= t < vocab for t in r.tokens),
              f"request {r.rid}: token outside [0, {vocab})")
    counts = eng.compile_counts()
    check(counts == run.warm_counts,
          f"recompiled after warmup: {run.warm_counts} -> {counts}")
    compile_s = clock.seconds

    # prefill logits of the served prompts, one 128-token bucket
    def prefill_logits(params, tokens, plens):
        h, _, _ = run.model.hidden(params, {"tokens": tokens},
                                   mode="prefill", remat=False)
        last = h[jnp.arange(h.shape[0]), plens - 1]
        return transformer.logits_fn(cfg, params, last[:, None])[:, 0]

    blen = 128
    toks = np.zeros((len(run.prompts), blen), np.int32)
    plens = np.array([len(p) for p in run.prompts], np.int32)
    for i, p in enumerate(run.prompts):
        toks[i, :len(p)] = p
    fn = compile_with_kernel(jax.jit(prefill_logits), run.params, toks,
                             plens)
    logits = np.asarray(fn(run.params, toks, plens))
    check(logits.shape == (len(run.prompts), vocab),
          f"prefill logits shape {logits.shape}")
    check(np.isfinite(logits).all(), "prefill logits are not finite")
    first = [r.tokens[0] for r in run.results]
    agree = int(sum(int(a) == b for a, b in
                    zip(logits.argmax(-1), first)))
    fact("serve", wall_s=wall, init_s=run.times.get("init_s"),
         warmup_s=run.times.get("warmup_s"),
         serve_s=run.times.get("wall_s"), compile_s=compile_s,
         cache_hits=clock.hits, peak_bytes_in_use=peak_bytes(dev),
         param_dtype=cfg.param_dtype, streams=len(run.results),
         tokens=sum(r.num_tokens for r in run.results),
         compile_counts=counts,
         first_tokens_matching_prefill_argmax=f"{agree}/{len(first)}")


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: FAIL: no src/repro beside {ROOT}; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    fact("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devs), jax=jax.__version__, compile_cache=cache)
    clock = CompileClock()
    for name, phase in (("federation", phase_federation),
                        ("serve", phase_serve)):
        try:
            phase(dev, clock)
        except Exception as e:
            traceback.print_exc()
            print(f"chip_smoke: FAIL in phase {name}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
