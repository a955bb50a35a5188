"""RWKV-6 (Finch) WKV recurrence Pallas kernel.

Per head, per step:
    o_t = r_t . (S + (u * k_t) v_t^T)
    S  <- diag(w_t) S + k_t v_t^T
with data-dependent decay w_t in (0,1) and a (dh, dh) matrix state S.

TPU formulation: grid (B, H, time-block) with time innermost; the (dh, dh)
f32 state lives in VMEM scratch and carries across time blocks, so HBM
traffic is one pass over (r, k, v, w) and one write of o.  dh = 64 means
the state is a single (64, 64) VREG-friendly tile; the in-chunk loop runs
rank-1 updates on the VPU.  Each step loads its (1, dh) rows straight
from the refs (Mosaic has no dynamic slice of a loaded value), so the
wrapper hands the kernel float32 operands; the per-channel columns a
rank-1 update needs come from a masked lane reduction against the
identity, which keeps every value 2-D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, slast_ref,
            s_ref, *, bs, ns, dh):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]

    eye = (jax.lax.broadcasted_iota(jnp.int32, (dh, dh), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dh, dh), 1))

    def col(row):                                # (1, dh) -> (dh, 1)
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    u = u_ref[0]                                 # (dh, 1)

    def step(t, s):
        row = pl.ds(t, 1)
        r = col(r_ref[0, 0, row, :])
        k = col(k_ref[0, 0, row, :])
        w = col(w_ref[0, 0, row, :])
        kv = k * v_ref[0, 0, row, :]             # (dh_k, dh_v)
        o_ref[0, 0, row, :] = jnp.sum(r * (s + u * kv), axis=0,
                                      keepdims=True)
        return w * s + kv

    s = jax.lax.fori_loop(0, bs, step, s_ref[...])
    s_ref[...] = s

    @pl.when(it == ns - 1)
    def _final():
        slast_ref[0, 0] = s


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def wkv6(r, k, v, w, u, s0, *, block_s=256, interpret=False):
    """r/k/v/w: (B, H, S, dh); u: (H, dh); s0: (B, H, dh, dh).

    Returns (o (B,H,S,dh), s_last (B,H,dh,dh) float32).
    """
    B, H, S, dh = r.shape
    bs = min(block_s, S)
    assert S % bs == 0
    ns = S // bs

    f32 = jnp.float32
    kern = functools.partial(_kernel, bs=bs, ns=ns, dh=dh)
    o, s_last = pl.pallas_call(
        kern,
        name="wkv6",
        grid=(B, H, ns),
        in_specs=[
            pl.BlockSpec((1, 1, bs, dh), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bs, dh), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bs, dh), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bs, dh), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, dh, 1), lambda b, h, it: (h, 0, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b, h, it: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bs, dh), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b, h, it: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dh), f32),
            jax.ShapeDtypeStruct((B, H, dh, dh), f32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), f32)],
        interpret=interpret,
    )(r.astype(f32), k.astype(f32), v.astype(f32), w.astype(f32),
      u.astype(f32)[:, :, None], s0.astype(f32))
    return o.astype(r.dtype), s_last
