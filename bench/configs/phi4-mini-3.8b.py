"""Plain float32 reference of the phi4-mini-3.8b decoder as the
configuration file states it: 32 pre-norm blocks of GQA attention
(24 query heads, 8 key/value heads of 128, head h reads key/value head
h // 3) with rotary positions over the whole head (rotate-half, theta
1e4) and a SwiGLU MLP of 8,192, RMS norms (eps 1e-6), embeddings tied
to the output head.  Imports nothing of the program; reads the
weights the benchmark made from the seed.

It runs one block at a time, each block's weights widened to float32
and every matmul at the highest precision, so the whole stack never
sits on the chip in float32.  ``lowp`` runs the same blocks with every
weight matrix in fp8 (e4m3, one scale per output column) and bf16
activations: the control of ``correct``, the step below the bf16 the
configuration serves in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HP = jax.lax.Precision.HIGHEST
VOCAB_CHUNK = 25_008          # 200,064 = 8 x 25,008


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _rope(x, theta):
    """x (B, L, N, dh); rotate-half over the whole head."""
    B, L, N, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


FP8_MAX = 448.0


def _quant(w):
    """fp8 e4m3 with one scale per output column, widened to bf16."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / FP8_MAX
    q = (w / jnp.maximum(s, 1e-30)).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * s).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("dims", "theta", "eps",
                                             "lowp"))
def block(x, p, *, dims, theta, eps, lowp):
    D, H, KV, dh = dims
    B, L, _ = x.shape
    if lowp:
        dt, prec = jnp.bfloat16, None
        w = {k: _quant(v) for k, v in p["attn"].items()}
        f = {k: _quant(v) for k, v in p["ffn"].items()}
    else:
        dt, prec = jnp.float32, HP
        w = {k: v.astype(jnp.float32) for k, v in p["attn"].items()}
        f = {k: v.astype(jnp.float32) for k, v in p["ffn"].items()}

    def mm(a, b):
        return jnp.dot(a.astype(dt), b, precision=prec,
                       preferred_element_type=jnp.float32)

    h = _rms(x, p["norm1"]["scale"], eps)
    q = _rope(mm(h, w["wq"]).reshape(B, L, H, dh), theta)
    k = _rope(mm(h, w["wk"]).reshape(B, L, KV, dh), theta)
    v = mm(h, w["wv"]).reshape(B, L, KV, dh)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(dt), k.astype(dt),
                   precision=prec, preferred_element_type=jnp.float32)
    s = s * dh ** -0.5
    causal = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a.astype(dt), v.astype(dt),
                   precision=prec, preferred_element_type=jnp.float32)
    x = x + mm(o.reshape(B, L, H * dh), w["wo"])
    h = _rms(x, p["norm2"]["scale"], eps)
    g = mm(h, f["w_gate"])
    x = x + mm(jax.nn.silu(g) * mm(h, f["w_up"]), f["w_down"])
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, scale, *, eps):
    return _rms(x, scale, eps)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _head_chunk(h, table_chunk, *, lowp):
    if lowp:
        w = _quant(table_chunk.T)
        return jnp.dot(h.astype(jnp.bfloat16), w,
                       preferred_element_type=jnp.float32)
    return jnp.dot(h, table_chunk.astype(jnp.float32).T, precision=HP)


def _layer(params, i):
    return jax.tree.map(lambda a: a[i], params["periods"]["b0"])


def hidden(cfg, params, tokens, *, lowp=False):
    """Final-norm hidden states (B, L, D) of right-padded ``tokens``."""
    table = params["embed"]["table"]
    x = table[jnp.asarray(tokens)].astype(jnp.float32)
    dims = _dims(cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, _layer(params, i), dims=dims,
                  theta=float(cfg["rope_theta"]),
                  eps=float(cfg["rms_norm_eps"]), lowp=lowp)
    return _final(x, params["final_norm"]["scale"],
                  eps=float(cfg["rms_norm_eps"]))


def logits_at(cfg, params, h, *, lowp=False):
    """Logits (P, V) of hidden rows h (P, D), a vocab chunk at a time."""
    table = params["embed"]["table"]
    V = table.shape[0]
    step = VOCAB_CHUNK if V % VOCAB_CHUNK == 0 else V
    return jnp.concatenate([
        _head_chunk(h, table[a:a + step], lowp=lowp)
        for a in range(0, V, step)], axis=-1)


def served_gaps(cfg, params, requests, *, control=False, batch=4):
    """For each request (prompt, served tokens): the reference's best
    logit minus its logit of each served token, at the position that
    produced it.  With ``control`` the token read is the one the fp8
    control puts first, not the served one.  Returns one array per
    request; ``batch`` requests go through the blocks together."""
    out = []
    with jax.default_matmul_precision("highest"):
        for a in range(0, len(requests), batch):
            out.extend(_gaps(cfg, params, requests[a:a + batch], control))
    return out


def _gaps(cfg, params, requests, control):
    L = max(len(p) + len(t) - 1 for p, t in requests)
    toks = np.zeros((len(requests), L), np.int32)
    for r, (p, t) in enumerate(requests):
        seq = np.concatenate([p, t[:-1]])
        toks[r, :len(seq)] = seq
    h = hidden(cfg, params, toks)
    hl = hidden(cfg, params, toks, lowp=True) if control else None
    out = []
    for r, (p, t) in enumerate(requests):
        pos = np.arange(len(p) - 1, len(p) + len(t) - 1)
        lg = logits_at(cfg, params, h[r, pos])
        if control:
            pick = jnp.argmax(logits_at(cfg, params, hl[r, pos],
                                        lowp=True), -1)
        else:
            pick = jnp.asarray(np.asarray(t, np.int32))
        got = jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
        out.append(np.asarray(jnp.max(lg, -1) - got))
    return out
