"""Operations and bytes the algorithms need, from their shapes.

These count the work an algorithm must do, not what one kernel does
to do it, so a kernel that replaces another is judged by the same
yardstick.  A multiply-add is two operations.  Bytes are what the
algorithm must read and write at least once: its inputs and its
outputs, at their true (unpadded) sizes.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other):
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k):
        return Work(self.flops * k, self.bytes * k)

    __rmul__ = __mul__

    def least_s(self, peak_flops, peak_bw):
        """The least time on a chip with these peaks, and its bound."""
        tc, tm = self.flops / peak_flops, self.bytes / peak_bw
        return (tc, "compute") if tc >= tm else (tm, "memory")


# ---------------------------------------------------------------------------
# Histogram trees
# ---------------------------------------------------------------------------
def tree_hist(n, f, k, nodes, bins, idx_bytes=4, w_bytes=4, out_bytes=4):
    """One weighted (channel, node, feature, bin) histogram over n rows:
    each row adds its k channel weights into one bin of each of its f
    features (one multiply-add each).  Reads the binned features, the
    node ids and the weights; writes the histogram."""
    return Work(flops=2.0 * n * f * k,
                bytes=(n * f * idx_bytes + n * idx_bytes + k * n * w_bytes
                       + k * nodes * f * bins * out_bytes))


def tree_fit(n, f, k, depth, bins):
    """One tree of ``depth`` levels: a histogram per level over every
    feature, then the leaf build (the node id as the one feature, the
    leaves as its bins)."""
    total = Work()
    for level in range(depth):
        total = total + tree_hist(n, f, k, 2 ** level, bins)
    return total + tree_hist(n, 1, k, 1, 2 ** depth)


def tree_predict(n, depth, k, f=1, idx_bytes=4, out_bytes=4):
    """One tree routing n rows: a comparison at each of ``depth``
    levels, then the leaf's k values added into the row's sums.  Reads
    the rows' binned features; writes the k sums."""
    return Work(flops=n * (depth + k),
                bytes=n * f * idx_bytes + n * k * out_bytes)


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------
def mlp_train(sizes, batch, steps):
    """Forward and backward of an MLP over ``steps`` batches: 6
    operations per weight per row (2 forward, 4 backward)."""
    weights = sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))
    return Work(flops=6.0 * weights * batch * steps, bytes=0.0)


def mlp_predict(sizes, rows):
    """A forward pass over ``rows``: 2 operations per weight per row."""
    weights = sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))
    return Work(flops=2.0 * weights * rows, bytes=0.0)


def votes(rows, voters):
    """Vote counts: each voter adds one to one class of each row."""
    return Work(flops=float(rows * voters), bytes=0.0)


# ---------------------------------------------------------------------------
# Decoder transformer
# ---------------------------------------------------------------------------
def attention(lengths, heads, kv_heads, head_dim, dtype_bytes=2):
    """Causal self-attention over prompts of the given true lengths:
    QK^T and PV over the causal triangle (L(L+1)/2 pairs, 2 matmuls,
    one multiply-add per head dim each); reads Q, K, V and writes O."""
    flops = sum(4.0 * heads * head_dim * L * (L + 1) / 2 for L in lengths)
    by = sum(dtype_bytes * head_dim * L * (2 * heads + 2 * kv_heads)
             for L in lengths)
    return Work(flops=flops, bytes=by)


def decoder_params(c):
    """Weights per layer and in the embedding of a dense GQA decoder
    (configuration-file keys)."""
    D, H, KV, dh, F = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    per_layer = D * H * dh * 2 + D * KV * dh * 2 + 3 * D * F
    return per_layer, c["vocab_size"] * D


def token_flops(c, context):
    """Model operations of one token at ``context`` earlier positions:
    every matmul (2 per weight), the output head, and attention to the
    ``context`` positions before it and itself."""
    per_layer, head = decoder_params(c)
    L = c["num_hidden_layers"]
    attn = 4.0 * c["num_attention_heads"] * c["head_dim"] * (context + 1)
    return 2.0 * per_layer * L + L * attn + 2.0 * head


def prompt_flops(c, length, head_positions=1):
    """A prefill of one prompt: every position through every layer;
    the output head only at the positions whose logits are read."""
    per_layer, head = decoder_params(c)
    L = c["num_hidden_layers"]
    attn = 4.0 * c["num_attention_heads"] * c["head_dim"] * \
        length * (length + 1) / 2
    return (2.0 * per_layer * L * length + L * attn
            + 2.0 * head * head_positions)
