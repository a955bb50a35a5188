"""PATE vote-aggregation Pallas kernel — the paper's core operation.

Given M teacher predictions for T queries, computes per query the (noisy)
max-vote label plus the top-2 vote scores (needed by consistent voting and
by the Lemma-7 privacy bound q = Pr[M(d) != o*]).

The paper's setting has u <= 10 classes; scaled to per-token LM voting the
class axis is the vocabulary (32k-256k), so a dense (T, U) histogram never
fits on chip.  TPU-native reformulation: the grid walks (query-block,
class-block) with the class axis innermost; each step histogram-counts the
M teacher votes that fall inside the current class block (rank-1 compares
on the VPU, no HBM histogram), adds the Laplace noise block, and folds the
block's top-2 into running (best, second, argbest) accumulators: the
output blocks themselves, revisited along the class axis.
Output is O(T), not O(T*U).

Queries ride the 128-wide lane axis throughout: the class block is
(bu, bt) with classes on sublanes, noise arrives transposed as (U, T),
and every per-query statistic is a lane-dense (1, T) row — so no block
is 1-D or lane-sparse, whatever the class count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _block_top2(scores, bt, bu):
    """(m1 (1,bt), argmax (1,bt), m2 (1,bt)) of one (bu, bt) class
    block.  The argmax is the FIRST maximal class (a min over matching
    positions), and only that POSITION is masked for m2 (not every
    equal value), so exact ties yield m2 == m1 — matching the xla
    path's one_hot masking and the top_k semantics the Lemma-7 gap
    needs on clean integer counts."""
    m1 = jnp.max(scores, axis=0, keepdims=True)                  # (1,bt)
    pos = jax.lax.broadcasted_iota(jnp.int32, (bu, bt), 0)
    i1 = jnp.min(jnp.where(scores == m1, pos, bu), axis=0, keepdims=True)
    masked = jnp.where(pos == i1, NEG_INF, scores)
    m2 = jnp.max(masked, axis=0, keepdims=True)
    return m1, i1, m2


def _fold_top2(best, second, m1, m2):
    """Fold one block's (m1, m2) into running (best, second).  Returns
    (take, new_best, new_second); strictly-greater keeps the
    first-occurrence argmax."""
    take = m1 > best
    new_best = jnp.where(take, m1, best)
    new_second = jnp.maximum(jnp.where(take, best, m1), second)
    new_second = jnp.maximum(new_second, jnp.where(take, m2, NEG_INF))
    return take, new_best, new_second


def _kernel(preds_ref, noise_ref, label_ref, top1_ref, top2_ref,
            clean1_ref, clean2_ref, *, M, bt, bu):
    iu = pl.program_id(1)

    @pl.when(iu == 0)
    def _init():
        for ref in (top1_ref, top2_ref, clean1_ref, clean2_ref):
            ref[...] = jnp.full_like(ref, NEG_INF)
        label_ref[...] = jnp.zeros_like(label_ref)

    class_base = iu * bu
    ids = class_base + jax.lax.broadcasted_iota(jnp.int32, (bu, bt), 0)

    def count_one(m, counts):
        p = preds_ref[pl.ds(m, 1), :]             # (1, bt)
        return counts + (p == ids).astype(jnp.float32)

    counts = jax.lax.fori_loop(
        0, M, count_one, jnp.zeros((bu, bt), jnp.float32))

    # clean top-2 (pre-noise): the privacy accountant's gap input, from
    # the SAME histogram the noisy argmax consumes
    cm1, _, cm2 = _block_top2(counts, bt, bu)
    _, cbest, csecond = _fold_top2(clean1_ref[...], clean2_ref[...],
                                   cm1, cm2)
    clean1_ref[...] = cbest
    clean2_ref[...] = csecond

    # noisy top-2 of this class block
    scores = counts + noise_ref[...].astype(jnp.float32)
    m1, i1, m2 = _block_top2(scores, bt, bu)
    take, new_best, new_second = _fold_top2(top1_ref[...], top2_ref[...],
                                            m1, m2)
    label_ref[...] = jnp.where(take, class_base + i1, label_ref[...])
    top1_ref[...] = new_best
    top2_ref[...] = new_second


@functools.partial(jax.jit, static_argnames=(
    "num_classes", "block_t", "block_u", "interpret"))
def vote_aggregate(preds, noise, *, num_classes, block_t=128, block_u=512,
                   interpret=False):
    """preds: (M, T) int32; noise: (T, U) float32 (zeros for L0).

    Returns (labels (T,) int32, top1 (T,) f32, top2 (T,) f32,
    clean_top1 (T,) f32, clean_top2 (T,) f32) — the noisy argmax stats
    plus the pre-noise top-2 from the same single histogram pass.
    """
    M, T = preds.shape
    U = num_classes
    bt, bu = min(block_t, T), min(block_u, U)
    assert T % bt == 0 and U % bu == 0, (T, U, bt, bu)
    nt, nu = T // bt, U // bu

    kern = functools.partial(_kernel, M=M, bt=bt, bu=bu)
    row = pl.BlockSpec((1, bt), lambda it, iu: (0, it))
    outs = pl.pallas_call(
        kern,
        grid=(nt, nu),
        in_specs=[
            pl.BlockSpec((M, bt), lambda it, iu: (0, it)),
            pl.BlockSpec((bu, bt), lambda it, iu: (iu, it)),
        ],
        out_specs=[row] * 5,
        out_shape=[jax.ShapeDtypeStruct((1, T), jnp.int32)]
        + [jax.ShapeDtypeStruct((1, T), jnp.float32)] * 4,
        interpret=interpret,
    )(preds, noise.T)
    return tuple(o[0] for o in outs)
