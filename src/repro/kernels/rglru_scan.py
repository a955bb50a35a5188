"""RG-LRU linear-recurrence Pallas kernel (RecurrentGemma / Griffin).

Computes h_t = exp(log_a_t) * h_{t-1} + x_t along the sequence axis.
This is the serial bottleneck of the recurrent blocks; the TPU-native
formulation chunks time into VMEM-resident blocks: the grid walks
(batch, d-block, time-block) with the time axis innermost so the hidden
state carries across grid steps in VMEM scratch — HBM traffic is exactly
one read of (x, log_a) and one write of h, with no state round-trips.

Channel blocks are 128-lane aligned; the in-chunk recurrence runs on the
VPU via fori_loop over the (bs) time steps of the chunk, each step
loading and storing one (1, bd) row of the refs (Mosaic has no dynamic
slice of a loaded value, so the wrapper hands the kernel float32 rows).
h0 and h_last travel as (B, 1, D) so their blocks keep a full-extent
sublane axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, a_ref, h0_ref, h_ref, hlast_ref, carry_ref, *, bs, ns):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        carry_ref[...] = h0_ref[0]                       # (1, bd)

    def step(t, h):
        row = pl.ds(t, 1)
        h = jnp.exp(a_ref[0, row, :]) * h + x_ref[0, row, :]
        h_ref[0, row, :] = h
        return h

    h = jax.lax.fori_loop(0, bs, step, carry_ref[...])
    carry_ref[...] = h

    @pl.when(it == ns - 1)
    def _final():
        hlast_ref[0] = h


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_d", "interpret"))
def rglru_scan(x, log_a, h0, *, block_s=256, block_d=256, interpret=False):
    """x, log_a: (B, S, D); h0: (B, D).  Returns (h (B,S,D), h_last (B,D))."""
    B, S, D = x.shape
    bs, bd = min(block_s, S), min(block_d, D)
    assert S % bs == 0 and D % bd == 0
    ns, nd = S // bs, D // bd

    f32 = jnp.float32
    kern = functools.partial(_kernel, bs=bs, ns=ns)
    h, h_last = pl.pallas_call(
        kern,
        name="rglru_scan",
        grid=(B, nd, ns),
        in_specs=[
            pl.BlockSpec((1, bs, bd), lambda b, id_, it: (b, it, id_)),
            pl.BlockSpec((1, bs, bd), lambda b, id_, it: (b, it, id_)),
            pl.BlockSpec((1, 1, bd), lambda b, id_, it: (b, 0, id_)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bd), lambda b, id_, it: (b, it, id_)),
            pl.BlockSpec((1, 1, bd), lambda b, id_, it: (b, 0, id_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), f32),
            jax.ShapeDtypeStruct((B, 1, D), f32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), f32)],
        interpret=interpret,
    )(x.astype(f32), log_a.astype(f32), h0.astype(f32)[:, None])
    return h.astype(x.dtype), h_last[:, 0].astype(x.dtype)
