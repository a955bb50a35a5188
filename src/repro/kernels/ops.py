"""Public jit'd wrappers around the Pallas kernels.

Every op has three implementations selected by ``impl``:
  - "kernel":            pl.pallas_call, TPU target
  - "kernel_interpret":  same kernel body executed in interpret mode
                         (CPU correctness validation)
  - "xla":               memory-bounded pure-jnp formulation (chunked /
                         associative-scan) used for CPU lowering & dry-run
"auto" resolves to "kernel" on TPU backends and "xla" elsewhere, so model
code calls one API everywhere.

The xla paths are *not* the naive oracles from ref.py: they are written to
bound peak memory (chunked q-block attention with rematerialized chunks,
associative-scan recurrences) so that the 32k-prefill dry-runs fit HBM.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import rglru_scan as _rg
from repro.kernels import tree_hist as _th
from repro.kernels import vote_aggregate as _va
from repro.kernels import wkv6 as _wk
from repro.kernels import ref

NEG_INF = -1e30

# Global chunking knobs.  The dry-run sets unroll=True so XLA cost
# analysis sees every chunk body (HloCostAnalysis counts a while body
# once regardless of trip count — measured, see EXPERIMENTS.md §Dry-run).
CONFIG = {"block_q": 512, "unroll": False}


def configure(**kw):
    CONFIG.update(kw)


def resolve_impl(impl: str) -> str:
    if impl != "auto":
        return impl
    return "kernel" if jax.default_backend() == "tpu" else "xla"


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


# ---------------------------------------------------------------------------
# Attention  (model-facing layout: (B, S, H, dh))
# ---------------------------------------------------------------------------
def attention(q, k, v, *, causal=True, window=0, softcap=0.0, q_offset=0,
              impl="auto", block_q=None):
    """Softmax attention.  q: (B,Sq,H,dh), k/v: (B,Skv,KV,dh).

    ``q_offset`` is the absolute position of q[:, 0]: a scalar, or a
    (B,) int32 vector of per-row offsets (continuous-batching decode —
    each batch row is an independent stream at its own position).
    Vector offsets are a decode-path feature: they require Sq == 1 and
    always take the xla path (the flash kernel's offset is scalar)."""
    per_row_offset = getattr(q_offset, "ndim", 0) == 1
    if per_row_offset and q.shape[1] != 1:
        raise NotImplementedError(
            "per-row q_offset is only supported for single-token decode "
            f"(Sq == 1); got Sq={q.shape[1]}")
    if block_q is None:
        # cap the chunk count so unrolled counting stays compile-cheap
        block_q = max(CONFIG["block_q"], q.shape[1] // 16)
    impl = resolve_impl(impl)
    if impl == "xla" or q.shape[1] == 1:
        return _attention_xla(q, k, v, causal=causal, window=window,
                              softcap=softcap, q_offset=q_offset,
                              block_q=block_q)
    interpret = impl == "kernel_interpret"
    # kernel layout (B, H, S, dh)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq, bk = 256, 512
    qt, sq = _pad_to(qt, bq, 2)
    kt, _ = _pad_to(kt, bk, 2)
    vt, _ = _pad_to(vt, bk, 2)
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              softcap=softcap, q_offset=q_offset,
                              block_q=bq, block_k=bk, interpret=interpret)
    return out[:, :, :sq].transpose(0, 2, 1, 3)


def _attention_xla(q, k, v, *, causal, window, softcap, q_offset, block_q):
    B, Sq, H, dh = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    g = H // KV

    def chunk_attn(q_blk, base):
        # q_blk: (B, bq, H, dh); base: absolute position of q_blk[0]
        bqn = q_blk.shape[1]
        qf = q_blk.astype(jnp.float32) * (dh ** -0.5)
        kf = k.astype(jnp.float32)
        # GQA: fold group into head dim without materializing repeats
        qf = qf.reshape(B, bqn, KV, g, dh)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf)       # (B,KV,g,bq,Skv)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        # qpos: (bqn,) for a scalar base, (B, bqn) for per-row offsets
        qpos = jnp.asarray(base)[..., None] + jnp.arange(bqn)
        kpos = jnp.arange(Skv)
        m = jnp.ones(qpos.shape + (Skv,), bool)
        if causal:
            m &= kpos <= qpos[..., None]
        if window > 0:
            m &= kpos > qpos[..., None] - window
        s = jnp.where(m[:, None, None] if m.ndim == 3
                      else m[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        # probs in compute dtype for the PV matmul (flash-kernel practice;
        # halves the dominant attention HBM term — §Perf iter 6)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, bqn, H, dh).astype(q.dtype)

    if Sq <= block_q:
        return chunk_attn(q, q_offset)

    bq = block_q
    nq, rem = divmod(Sq, bq)
    body = jax.checkpoint(chunk_attn)

    def scan_fn(_, it):
        q_blk, base = it
        return None, body(q_blk, base)

    q_main = q[:, :nq * bq].reshape(B, nq, bq, H, dh).transpose(1, 0, 2, 3, 4)
    bases = q_offset + jnp.arange(nq) * bq
    _, outs = jax.lax.scan(scan_fn, None, (q_main, bases),
                           unroll=CONFIG["unroll"])
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * bq, H, dh)
    if rem:
        out = jnp.concatenate(
            [out, body(q[:, nq * bq:], q_offset + nq * bq)], axis=1)
    return out


# ---------------------------------------------------------------------------
# RG-LRU recurrence
# ---------------------------------------------------------------------------
def rglru(x, log_a, h0=None, *, impl="auto"):
    """h_t = exp(log_a_t)*h_{t-1} + x_t.  x/log_a: (B,S,D), h0: (B,D).

    Returns (h (B,S,D), h_last (B,D))."""
    impl = resolve_impl(impl)
    B, S, D = x.shape
    if h0 is None:
        h0 = jnp.zeros((B, D), jnp.float32)
    if S == 1:  # decode step
        h = jnp.exp(log_a.astype(jnp.float32)) * h0[:, None] \
            + x.astype(jnp.float32)
        return h.astype(x.dtype), h[:, 0]
    if impl == "xla":
        a = jnp.exp(log_a.astype(jnp.float32))
        xf = x.astype(jnp.float32)

        def comb(c1, c2):
            a1, h1 = c1
            a2, h2 = c2
            return a1 * a2, a2 * h1 + h2

        # fold h0 into the first step
        xf = xf.at[:, 0].add(a[:, 0] * h0)
        af, hf = jax.lax.associative_scan(comb, (a, xf), axis=1)
        return hf.astype(x.dtype), hf[:, -1]
    interpret = impl == "kernel_interpret"
    bd = 256 if D % 256 == 0 else D
    bs = 256 if S % 256 == 0 else S
    return _rg.rglru_scan(x, log_a, h0, block_s=bs, block_d=bd,
                          interpret=interpret)


# ---------------------------------------------------------------------------
# RWKV-6 WKV
# ---------------------------------------------------------------------------
def wkv(r, k, v, w, u, s0=None, *, impl="auto"):
    """RWKV-6 recurrence.  r/k/v/w: (B,S,H,dh) model layout; u: (H,dh).

    s0: (B,H,dh,dh) f32.  Returns (o (B,S,H,dh), s_last (B,H,dh,dh))."""
    impl = resolve_impl(impl)
    B, S, H, dh = r.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, dh, dh), jnp.float32)
    if S == 1:  # decode step
        rf, kf, vf, wf = (t[:, 0].astype(jnp.float32) for t in (r, k, v, w))
        uf = u.astype(jnp.float32)
        kv = kf[..., :, None] * vf[..., None, :]
        o = jnp.einsum("bhk,bhkv->bhv", rf, s0 + uf[..., :, None] * kv)
        s = wf[..., :, None] * s0 + kv
        return o[:, None].astype(r.dtype), s
    if impl == "xla":
        return _wkv_xla_chunked(r, k, v, w, u, s0)
    interpret = impl == "kernel_interpret"
    # kernel layout (B, H, S, dh)
    rt, kt, vt, wt = (t.transpose(0, 2, 1, 3) for t in (r, k, v, w))
    bs = 256 if S % 256 == 0 else S
    o, s_last = _wk.wkv6(rt, kt, vt, wt, u, s0, block_s=bs,
                         interpret=interpret)
    return o.transpose(0, 2, 1, 3), s_last


def _wkv_xla_chunked(r, k, v, w, u, s0, chunk=128):
    """Time-chunked WKV with per-chunk remat.

    The naive full-sequence scan stores a (B,H,dh,dh) state residual per
    STEP for backward — 274 GB/device at rwkv6-7b train_4k (measured,
    EXPERIMENTS.md §Perf iter 2).  Chunking with jax.checkpoint keeps
    only per-chunk boundary states and recomputes inside the chunk."""
    B, S, H, dh = r.shape
    c = min(chunk, S)
    if S % c:
        return ref.wkv6_ref(r, k, v, w, u, s0)
    nc = S // c

    from repro.sharding.specs import constrain, DP

    def chunk_fn(s, xs):
        rc, kc, vc, wc = xs                      # (B, c, H, dh)
        o, s2 = ref.wkv6_ref(rc, kc, vc, wc, u, s)
        return s2, constrain(o, DP, None, "model", None)

    xs = tuple(constrain(t.reshape(B, nc, c, H, dh).swapaxes(0, 1),
                         None, DP, None, "model", None)
               for t in (r, k, v, w))
    s_last, outs = jax.lax.scan(jax.checkpoint(chunk_fn), s0, xs,
                                unroll=CONFIG["unroll"])
    return outs.swapaxes(0, 1).reshape(B, S, H, dh), s_last


# ---------------------------------------------------------------------------
# Vote aggregation
# ---------------------------------------------------------------------------
def _top2_of(scores, argmax_labels, num_classes):
    """(top1, top2) with only the argmax POSITION masked, so exact ties
    give top2 == top1 (top_k semantics)."""
    top1 = jnp.max(scores, axis=-1)
    masked = jnp.where(
        jax.nn.one_hot(argmax_labels, num_classes, dtype=bool),
        NEG_INF, scores)
    return top1, jnp.max(masked, axis=-1)


def _votes_kernel(preds, num_classes, noise, interpret):
    """The blocked kernel over 128-query blocks: T is padded up to the
    block (padded queries vote class 0 and are sliced off), so no query
    count ever forces a whole-axis block into VMEM."""
    T = preds.shape[1]
    if noise is None:
        noise = jnp.zeros((T, num_classes), jnp.float32)
    preds, _ = _pad_to(preds, 128, 1)
    noise, _ = _pad_to(noise, 128, 0)
    bu = 512 if num_classes % 512 == 0 else num_classes
    outs = _va.vote_aggregate(preds, noise, num_classes=num_classes,
                              block_t=128, block_u=bu, interpret=interpret)
    return tuple(o[:T] for o in outs)


def votes(preds, num_classes, noise=None, *, impl="auto"):
    """Max-vote labels + top-2 vote scores.

    preds: (M, T) int32; noise: optional (T, U) f32.
    Returns (labels (T,) i32, top1 (T,) f32, top2 (T,) f32)."""
    impl = resolve_impl(impl)
    if noise is None and num_classes > 2048:
        # LM-scale noise-free voting: O(M log M), no U-sized tensors
        return votes_sort(preds)
    if impl == "xla":
        labels, counts = ref.vote_aggregate_ref(preds, num_classes, noise)
        scores = counts.astype(jnp.float32)
        if noise is not None:
            scores = scores + noise
        top1, top2 = _top2_of(scores, labels, num_classes)
        return labels, top1, top2
    labels, top1, top2, _, _ = _votes_kernel(
        preds, num_classes, noise, impl == "kernel_interpret")
    return labels, top1, top2


def votes_with_clean(preds, num_classes, noise=None, *, impl="auto"):
    """Noisy max-vote labels + CLEAN top-2 from ONE histogram build.

    The party-side vote hot path needs both the noised argmax (the label
    it answers with) and the pre-noise gap (the Lemma-7 privacy input);
    building the (T, U) histogram once serves both.  Returns
    (labels, counts, clean_top1, clean_top2) where ``counts`` is the
    clean histogram on the xla path and None on the kernel paths (the
    blocked kernel never materializes it — it emits clean top-2
    directly) and on the LM-scale sort path."""
    impl = resolve_impl(impl)
    if noise is None and num_classes > 2048:
        labels, top1, top2 = votes_sort(preds)
        return labels, None, top1, top2
    if impl == "xla":
        clean_labels, counts = ref.vote_aggregate_ref(preds, num_classes)
        cf = counts.astype(jnp.float32)
        c1, c2 = _top2_of(cf, clean_labels, num_classes)
        if noise is None:
            return clean_labels, counts, c1, c2
        labels = jnp.argmax(cf + noise, axis=-1).astype(jnp.int32)
        return labels, counts, c1, c2
    labels, _, _, c1, c2 = _votes_kernel(
        preds, num_classes, noise, impl == "kernel_interpret")
    return labels, None, c1, c2


def votes_sort(preds):
    """Vocabulary-free max voting: mode along the teacher axis via sort.

    preds: (M, T) int32.  Returns (labels, top1, top2) like ``votes`` —
    but cost is O(M log M) per query with NO U-sized tensor, which is
    what the FedKT label step needs at LM scale (U = 200k vocab would
    make even the blocked histogram's noise input (T, U) infeasible).
    Noise-free (privacy level L0); DP label steps use the blocked kernel.
    Ties resolve to the smallest class id (matches ref argmax).
    """
    M, T = preds.shape
    s = jnp.sort(preds, axis=0)                       # (M, T)
    # run length ending at i: rl[i] = rl[i-1]+1 if equal else 1
    def body(carry, row):
        prev, rl = carry
        rl = jnp.where(row == prev, rl + 1, 1)
        return (row, rl), rl

    init = (jnp.full((T,), -1, preds.dtype), jnp.zeros((T,), jnp.int32))
    _, rls = jax.lax.scan(body, init, s)              # (M, T) run lengths
    # winner: value whose run is longest; first (smallest) on ties
    best = jnp.argmax(rls, axis=0)                    # last index of run
    labels = jnp.take_along_axis(s, best[None], axis=0)[0]
    top1 = jnp.max(rls, axis=0).astype(jnp.float32)
    # second: longest run among values != winner
    masked = jnp.where(s == labels[None], 0, rls)
    top2 = jnp.max(masked, axis=0).astype(jnp.float32)
    return labels.astype(jnp.int32), top1, top2


# ---------------------------------------------------------------------------
# Tree-fit histogram
# ---------------------------------------------------------------------------
def tree_hist(xb, node, w, *, num_nodes, num_bins, impl="auto",
              block_f=32):
    """Weighted (channel, node, feature, bin) histogram — the per-level
    build inside the histogram tree fits.

    xb: (N, F) int32 binned features; node: (N,) int32 tree position of
    each sample; w: (K, N) f32 channel weights.  Returns
    (K, num_nodes, F, num_bins) f32 counts; rows at w == 0 contribute
    exact zeros (the stacked-fit padding invariant).

    The xla path is NOT the scatter-add this replaces: it contracts a
    weighted (N, num_nodes*K) node/channel one-hot against per-feature-
    block (N, bf*num_bins) bin one-hots — a dense matmul XLA lowers
    without the serialized scatter loop or the (N, F) broadcast of w.
    """
    impl = resolve_impl(impl)
    if impl == "xla":
        return _tree_hist_xla(xb, node, w, num_nodes, num_bins, block_f)
    return _th.tree_hist(xb, node, w, num_nodes=num_nodes,
                         num_bins=num_bins, block_f=block_f,
                         interpret=impl == "kernel_interpret")


def _tree_hist_xla(xb, node, w, num_nodes, num_bins, block_f):
    N, F = xb.shape
    K = w.shape[0]
    nc = jax.nn.one_hot(node, num_nodes, dtype=jnp.float32)     # (N, n)
    ncw = w.astype(jnp.float32)[:, :, None] * nc[None]          # (K, N, n)

    def chunk(xc):  # (N, bf) -> (K, n, bf, B)
        ob = jax.nn.one_hot(xc, num_bins, dtype=jnp.float32)
        return jnp.einsum("kin,ifb->knfb", ncw, ob)

    if F <= block_f:
        return chunk(xb)
    # feature-blocked: bounds the (N, bf, B) one-hot to one block
    pad = (-F) % block_f
    xp = jnp.pad(xb, ((0, 0), (0, pad))) if pad else xb
    nf = (F + pad) // block_f
    xs = xp.reshape(N, nf, block_f).transpose(1, 0, 2)
    _, hs = jax.lax.scan(lambda c, xc: (c, chunk(xc)), None, xs,
                         unroll=CONFIG["unroll"])
    h = hs.transpose(1, 2, 0, 3, 4).reshape(K, num_nodes, nf * block_f,
                                            num_bins)
    return h[:, :, :F]


def node_hist(node, w, *, num_nodes, impl="auto"):
    """Weighted per-node histogram — the leaf builds of the tree fits.

    node: (N,) int32; w: (K, N) f32.  Returns (K, num_nodes) f32.  The
    leaf build IS a tree_hist with the node id as the single "feature"
    and the leaves as its bins, so both impls reuse that machinery."""
    out = tree_hist(node[:, None], jnp.zeros_like(node), w,
                    num_nodes=1, num_bins=num_nodes, impl=impl)
    return out[:, 0, 0, :]
