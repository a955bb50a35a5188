"""Blocked (flash) attention Pallas kernel for TPU.

Targets the teacher-ensemble prefill workload (the dominant compute in
FedKT's knowledge-transfer phase): online-softmax attention tiled so the
working set (one q block, one kv block, f32 accumulators) lives in VMEM.

Layout: q (B, H, Sq, dh), k/v (B, KV, Skv, dh), GQA via index_map
(kv head = h // (H // KV)).  Grid (B, H, nq, nk) — nk innermost so the
running max / denominator / accumulator scratch carries across kv blocks
(TPU grid execution is sequential over the trailing axis).

Supports causal masking, sliding windows (gemma2/mixtral/recurrentgemma
local attention, and the long_500k SWA variant), gemma2 logit soft-capping,
and a ``q_offset`` for chunked prefill.

MXU alignment: block shapes default to (bq, dh) = (256, 128) and
(bk, dh) = (512, 128) — multiples of the 128-lane MXU tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, softcap, q_offset, bq, bk, nk):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, dh)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    iq = pl.program_id(2)
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + q_offset
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                   # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    # p is explicitly re-masked so fully-masked blocks contribute zero even
    # when m_new is still NEG_INF.
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v).astype(jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_offset",
                     "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    q_offset=0, block_q=256, block_k=512, interpret=False):
    """q: (B, H, Sq, dh); k, v: (B, KV, Skv, dh).  Returns (B, H, Sq, dh).

    Sq must divide by block_q and Skv by block_k (ops.py pads).
    """
    B, H, Sq, dh = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    g = H // KV
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    nq, nk = Sq // bq, Skv // bk
    assert Sq % bq == 0 and Skv % bk == 0

    kern = functools.partial(
        _kernel, scale=dh ** -0.5, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, bq=bq, bk=bk, nk=nk)

    return pl.pallas_call(
        kern,
        name="flash_attention",
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, iq, ik, g=g: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, iq, ik, g=g: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((bq, dh), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
