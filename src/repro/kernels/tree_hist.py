"""Blocked (node, feature, bin) histogram Pallas kernel — the tree-fit
hot path.

Every depth level of the histogram tree learners (trees.py) needs

    hist[k, n, f, b] = sum_i w[k, i] * [node_i == n] * [xb[i, f] == b]

for K weight channels (the C class-masked sample weights of a gini
tree, or the (g, h) gradient/hessian pair of a GBDT tree).  The naive
XLA lowering is one giant 1-D scatter-add over an (N, F) broadcast of
w — memory-bound and serialized by the scatter loop.

TPU-native reformulation (the ``vote_aggregate`` pattern): the grid
walks (feature-block, sample-block) with samples innermost.  Samples
ride the 128-wide lane axis of every operand — xb as (F, N), node as
(1, N), w as (K, N) — so no block is a 1-D or lane-sparse slice.  Each
step builds two one-hot operands on the VPU, the (num_nodes, bs) node
mask scaled by a weight channel and the (bf * B, bs) bin mask, and
contracts them over the sample axis with one MXU matmul per channel,
accumulating into the revisited output block (``pl.when`` zero-init on
the first sample step).  The bin mask needs row f*B + b to read
feature f: an (bf * B, bf) 0/1 expansion matmul replicates each
feature row B times, which keeps every intermediate 2-D and
tile-aligned.  Rows padded to the sample-block multiple ride at
w == 0, so they contribute exact zeros — the same invariant the
stacked (teacher-axis) fits rely on for padding rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HI = jax.lax.Precision.HIGHEST


def _kernel(xb_ref, node_ref, w_ref, out_ref, *, K, num_nodes, num_bins,
            bs, bf):
    i_s = pl.program_id(1)

    @pl.when(i_s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = bf * num_bins
    n_iota = jax.lax.broadcasted_iota(jnp.int32, (num_nodes, bs), 0)
    onehot_n = (node_ref[...] == n_iota).astype(jnp.float32)    # (n, bs)

    # expand[f*B + b, f'] = [f == f']; tgt[f*B + b] = b
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, bf), 0)
    lo = jax.lax.broadcasted_iota(jnp.int32, (rows, bf), 1) * num_bins
    hit = (r_iota >= lo) & (r_iota < lo + num_bins)
    expand = hit.astype(jnp.float32)
    tgt = jnp.sum(jnp.where(hit, r_iota - lo, 0), axis=1, keepdims=True)
    # bin ids are small integers: exact through the f32 matmul
    xrep = jax.lax.dot(expand, xb_ref[...].astype(jnp.float32),
                       precision=_HI)                           # (rows, bs)
    onehot_b = (xrep == tgt.astype(jnp.float32)).astype(jnp.float32)

    w_b = w_ref[...]                                            # (K, bs)
    contrib = []
    for k in range(K):                       # static channel unroll
        nck = onehot_n * w_b[k:k + 1, :]                        # (n, bs)
        contrib.append(jax.lax.dot_general(
            nck, onehot_b, (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32))   # (n, bf*B) on the MXU
    out_ref[...] += jnp.concatenate(contrib, axis=0)


@functools.partial(jax.jit, static_argnames=(
    "num_nodes", "num_bins", "block_s", "block_f", "interpret"))
def tree_hist(xb, node, w, *, num_nodes, num_bins, block_s=512,
              block_f=None, interpret=False):
    """xb: (N, F) int32 bins; node: (N,) int32; w: (K, N) f32 channel
    weights.  Returns (K, num_nodes, F, num_bins) f32 weighted counts.

    ``block_s`` must be a multiple of 128 (or cover N); a feature block
    smaller than F must be a multiple of 8 — the TPU tiling rules for
    the sample (lane) and feature (sublane) axes.
    """
    N, F = xb.shape
    K = w.shape[0]
    bs = min(block_s, N)
    bf = min(block_f or F, F)

    pad_s, pad_f = (-N) % bs, (-F) % bf
    if pad_s:  # padded samples ride at w == 0: exact-zero contribution
        xb = jnp.pad(xb, ((0, pad_s), (0, 0)))
        node = jnp.pad(node, (0, pad_s))
        w = jnp.pad(w, ((0, 0), (0, pad_s)))
    if pad_f:  # junk feature columns, sliced off below
        xb = jnp.pad(xb, ((0, 0), (0, pad_f)))
    ns, nf = (N + pad_s) // bs, (F + pad_f) // bf

    kern = functools.partial(_kernel, K=K, num_nodes=num_nodes,
                             num_bins=num_bins, bs=bs, bf=bf)
    out = pl.pallas_call(
        kern,
        grid=(nf, ns),
        in_specs=[
            pl.BlockSpec((bf, bs), lambda i_f, i_s: (i_f, i_s)),
            pl.BlockSpec((1, bs), lambda i_f, i_s: (0, i_s)),
            pl.BlockSpec((K, bs), lambda i_f, i_s: (0, i_s)),
        ],
        out_specs=pl.BlockSpec((K * num_nodes, bf * num_bins),
                               lambda i_f, i_s: (0, i_f)),
        out_shape=jax.ShapeDtypeStruct(
            (K * num_nodes, nf * bf * num_bins), jnp.float32),
        interpret=interpret,
    )(xb.T, node[None, :], w.astype(jnp.float32))
    out = out.reshape(K, num_nodes, nf * bf, num_bins)
    return out[:, :, :F]
