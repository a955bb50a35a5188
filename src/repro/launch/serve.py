"""Serving CLI: thin front-end over the continuous-batching engine.

The engine itself lives in ``repro.serving`` (scheduler, slot cache,
prefill/decode split); this module only parses flags, applies the
deployment environment hygiene, builds the model, and drives traffic.

Closed loop (submit everything, drain):

  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
      --smoke --concurrent 8 --max-tokens 32

Open loop (seeded Poisson arrivals at --arrival req/s, the pattern
serve_bench measures):

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-27b \
      --smoke --concurrent 16 --arrival 4 --slots 4

Environment: allocator and logging knobs must be in the environment
BEFORE XLA initialises its backends, so ``main`` sets them from flags
first and only then imports the model stack.  What it sets:

  --host-devices N   sets XLA_FLAGS=--xla_force_host_platform_device_count
                     (multi-device CPU topology for mesh dry-runs)
  TF_CPP_MIN_LOG_LEVEL defaults to 2 (mute absl chatter)
  TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD defaults to 2**38 (mute large-
                     alloc warnings for weight-sized host buffers)
  the persistent compilation cache directory (launch/compile_cache.py)

Weights are served in the model's compute dtype (``cfg.dtype``, bf16
for the published configs — the dtype their checkpoints ship in) and
are created by one jitted init, so no float32 copy of the whole stack
ever exists: phi4-mini's 3.84 B parameters take 7.7 GB in bf16 and
would take 15.3 GB of a 16 GiB chip in float32.  Every matmul casts its
weight to the compute dtype anyway, so the served tokens equal those
of float32-held weights.

Models the engine refuses (recurrent state, encoder-decoder) fall back
to the fixed-batch serial path; ``main`` prints the reason and returns
``path == "serial"``, so a caller that needs the engine can tell.
"""
from __future__ import annotations

import argparse
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


def __getattr__(name):
    # back-compat: launch.serve.serve_batch moved to repro.serving
    if name in ("serve_batch", "effective_tokens"):
        from repro import serving
        return getattr(serving, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclass
class ServeRun:
    """What one ``main`` call served.  ``path`` is "engine" (continuous
    batching) or "serial" (the fixed-batch fallback, ``reason`` says
    why); ``results`` holds the engine's StreamResults or the serial
    path's stats.  Times are host seconds: ``init_s`` builds the
    weights, ``warmup_s`` compiles every bucket, ``wall_s`` serves."""
    path: str
    reason: str
    results: Any
    model: Any
    params: Any
    prompts: List[Any]
    engine: Any = None
    warm_counts: Optional[Dict[str, int]] = None
    times: Dict[str, float] = field(default_factory=dict)


def _apply_env(args):
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    os.environ.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                          str(2 ** 38))
    if args.host_devices:
        flag = ("--xla_force_host_platform_device_count="
                f"{args.host_devices}")
        os.environ["XLA_FLAGS"] = \
            (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()


def _percentile(xs, q):
    return sorted(xs)[min(len(xs) - 1, int(q * len(xs)))] if xs else 0.0


def _drive(eng, prompts, max_tokens, arrival, seed):
    """Submit ``prompts`` and run to drain.  arrival <= 0: closed loop
    (all at once).  arrival > 0: open loop — seeded exponential
    inter-arrival gaps at ``arrival`` req/s, submitted as engine steps
    pass their deadline."""
    import numpy as np
    if arrival <= 0:
        for p in prompts:
            eng.submit(p, max_tokens)
        return eng.run()
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / arrival, len(prompts))
    t0 = eng.clock()
    deadlines = list(zip(t0 + np.cumsum(gaps), prompts))
    results = []
    while deadlines or not eng.scheduler.idle:
        now = eng.clock()
        while deadlines and deadlines[0][0] <= now:
            _, p = deadlines.pop(0)
            eng.submit(p, max_tokens)
        if eng.scheduler.idle and deadlines:
            time.sleep(min(max(deadlines[0][0] - now, 0.0), 0.01))
            continue                      # idle-wait for next arrival
        results.extend(eng.step())
    return sorted(results, key=lambda r: r.rid)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="max prompt length; lengths are drawn "
                         "uniformly from [1, this] per request")
    ap.add_argument("--max-tokens", "--gen", type=int, default=16,
                    dest="max_tokens")
    ap.add_argument("--concurrent", type=int, default=4,
                    help="number of request streams to serve")
    ap.add_argument("--arrival", type=float, default=0.0,
                    help="open-loop Poisson arrival rate in req/s "
                         "(0 = closed loop: submit all up front)")
    ap.add_argument("--slots", type=int, default=4,
                    help="engine KV-cache slots (concurrent decodes)")
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--eos", type=int, default=None,
                    help="EOS token id for early stream termination")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--serial", action="store_true",
                    help="force the fixed-batch serve_batch path")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="XLA host-platform device count (see hygiene "
                         "notes in the module docstring)")
    args = ap.parse_args(argv)
    _apply_env(args)                      # BEFORE the jax import below

    import jax
    import numpy as np

    from repro import checkpoint as ckpt_lib
    from repro.configs import ARCH_IDS, get_config, get_smoke
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import Model
    from repro.serving import Engine, serve_batch

    enable_compile_cache()
    if args.arch not in ARCH_IDS:
        ap.error(f"unknown arch {args.arch!r} (choose from {ARCH_IDS})")
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(param_dtype=cfg.dtype)
    model = Model(cfg)
    times = {}
    t0 = time.perf_counter()
    if args.checkpoint:
        like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        params = jax.tree.map(lambda a, t: a.astype(t.dtype),
                              ckpt_lib.restore(args.checkpoint, like), like)
    else:
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    times["init_s"] = time.perf_counter() - t0

    rng = np.random.default_rng(args.seed)
    lens = rng.integers(1, args.prompt_len + 1, args.concurrent)
    prompts = [rng.integers(0, cfg.vocab_size, (int(n),)).astype(np.int32)
               for n in lens]

    try:
        if args.serial:
            raise NotImplementedError("--serial")
        eng = Engine(model, params, num_slots=args.slots,
                     cache_len=args.cache_len, eos_id=args.eos)
    except NotImplementedError as why:
        # recurrent / encoder-decoder configs: fixed-batch fallback
        print(f"FALLBACK: serial fixed-batch path, not the engine ({why})")
        P = args.prompt_len
        batch = np.stack([np.resize(p, P) for p in prompts])
        extra = {}
        if cfg.is_encoder_decoder:
            import jax.numpy as jnp
            extra["frames"] = jnp.asarray(rng.normal(
                0, 1, (len(prompts), cfg.encoder_seq_len, cfg.d_model)),
                jnp.dtype(cfg.dtype))
        tokens, stats = serve_batch(model, params, batch,
                                    args.max_tokens, extra=extra,
                                    eos_id=args.eos)
        print("generated:", tokens[:, :8], "...")
        return ServeRun("serial", str(why), stats, model, params, prompts,
                        times=times)

    t0 = time.perf_counter()
    warm = eng.warmup(buckets=[p.shape[0] for p in prompts])
    times["warmup_s"] = time.perf_counter() - t0
    t0 = eng.clock()
    results = _drive(eng, prompts, args.max_tokens, args.arrival,
                     args.seed)
    wall = times["wall_s"] = eng.clock() - t0
    toks = sum(r.num_tokens for r in results)
    lats = [t for r in results for t in r.timing["token_latencies"]]
    print(f"{len(results)} streams, {toks} tokens in {wall:.2f}s "
          f"({toks / max(wall, 1e-9):.1f} tok/s aggregate)")
    print(f"per-token latency p50 {_percentile(lats, .5)*1e3:.1f}ms "
          f"p95 {_percentile(lats, .95)*1e3:.1f}ms; "
          f"compile counts {eng.compile_counts()}")
    for r in results[:4]:
        print(f"  req {r.rid} plen {r.prompt_len}: {r.tokens[:8]} ...")
    return ServeRun("engine", "", results, model, params, prompts,
                    engine=eng, warm_counts=warm, times=times)


if __name__ == "__main__":
    main()
