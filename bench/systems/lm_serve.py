"""The serving tier under test: the program's continuous-batching
``serving.Engine`` over one decoder model, driven through
``Engine.submit`` / ``Engine.step``.

Set-up makes the weights from the seed on the device in one jitted
call, in the program's parameter layout and the served dtype, builds
the engine and warms up the prompt buckets and batch sizes that the
cell's traffic can send, and no others.
"""
from __future__ import annotations

import numpy as np


def _layer_shapes(c):
    D, H, KV, dh, F = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    return {
        "attn": {"wq": (D, H * dh), "wk": (D, KV * dh),
                 "wv": (D, KV * dh), "wo": (H * dh, D)},
        "ffn": {"w_up": (D, F), "w_down": (F, D), "w_gate": (D, F)},
    }


def make_params(c, seed):
    """Random weights in the program's layout (``periods.b0`` stacked
    over the layers, tied embeddings), bf16, made on the device."""
    import jax
    import jax.numpy as jnp

    from common import seed32

    Lyr, D, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    shapes = _layer_shapes(c)
    dt = jnp.bfloat16

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, 16))

        def dense(shape):
            return (jax.random.normal(next(keys), (Lyr,) + shape,
                                      jnp.float32)
                    * shape[0] ** -0.5).astype(dt)

        def norm(shape):
            return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                  jnp.float32)).astype(dt)

        block = {g: {k: dense(s) for k, s in grp.items()}
                 for g, grp in shapes.items()}
        block["norm1"] = {"scale": norm((Lyr, D))}
        block["norm2"] = {"scale": norm((Lyr, D))}
        return {"embed": {"table": (0.02 * jax.random.normal(
                    next(keys), (V, D), jnp.float32)).astype(dt)},
                "head_blocks": [], "tail": [],
                "periods": {"b0": block},
                "final_norm": {"scale": norm((D,))}}

    params = init(jax.random.PRNGKey(seed32(seed)))
    jax.block_until_ready(params)
    return params


def program_config(c):
    """The program's model config for this configuration file, checked
    size by size against it."""
    from repro.configs import get_config, get_smoke
    get = get_smoke if c.get("program_preset") == "smoke" else get_config
    mc = get(c["program_arch"])
    mc = mc.replace(param_dtype=mc.dtype)
    want = {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
            "num_layers": c["num_hidden_layers"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim_": c["head_dim"], "vocab_size": c["vocab_size"],
            "rope_theta": c["rope_theta"],
            "rope_pct": c["partial_rotary_factor"],
            "norm_eps": c["rms_norm_eps"],
            "tie_embeddings": c["tie_word_embeddings"],
            "dtype": c["torch_dtype"], "param_dtype": c["torch_dtype"]}
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise ValueError(f"program config {got} is not the file's {want}")
    return mc


class Server:
    def __init__(self, cfg, wl, seed, devs, warm_lengths):
        from repro.models import Model
        from repro.serving import Engine

        self.cfg = cfg
        self.vocab = cfg["vocab_size"]
        self.model = Model(program_config(cfg))
        self.params = make_params(cfg, seed)
        e = cfg["engine"]
        self.engine = Engine(self.model, self.params,
                             num_slots=e["num_slots"],
                             cache_len=e["cache_len"],
                             max_batch=e["max_batch"])
        self.engine.warmup(buckets=warm_lengths)

    def reseed(self, seed):
        """New weights from another seed under the same engine (the
        readings of many seeds in one process)."""
        import gc
        self.params = self.engine.params = None
        gc.collect()
        self.params = self.engine.params = make_params(self.cfg, seed)

    def free(self):
        """Drops the engine (its KV cache and compiled steps); the
        weights stay, since the reference reads them."""
        import gc

        import jax
        self.engine = None
        jax.clear_caches()
        gc.collect()


def build(cfg, wl, seed, devs, seconds):
    import common
    kind = common.traffic(wl["traffic"]["kind"])
    warm = kind.warm_lengths(wl["traffic"], seconds)
    return Server(cfg, wl, seed, devs, warm)


def sample(served, seed, min_tokens):
    """The requests the check reads: the longest one, then others drawn
    from the seed, until ``min_tokens`` served tokens are covered."""
    order = np.random.default_rng(seed + 5).permutation(len(served))
    longest = max(range(len(served)), key=lambda i: len(served[i][1]))
    pick, n = [longest], len(served[longest][1])
    for i in order:
        if n >= min_tokens:
            break
        if i != longest:
            pick.append(int(i))
            n += len(served[i][1])
    return [served[i] for i in pick]


def check(srv, ref, seed, win, params, precision=None):
    """Widest gap, over the sampled served tokens, between the
    reference's best logit and its logit of the served token (with
    ``precision="fp8"``: of the token the fp8 control puts first)."""
    if not win["served"]:
        return {"max_logit_gap": float("inf"), "tokens_checked": 0}
    reqs = sample(win["served"], seed, params["check_tokens"])
    gaps = ref.served_gaps(srv.cfg, srv.params, reqs,
                           control=precision == "fp8")
    allg = np.concatenate(gaps)
    return {"max_logit_gap": float(allg.max()),
            "tokens_checked": int(allg.size)}
