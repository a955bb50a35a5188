"""flash_attention's share of its roofline: the least time of causal
attention over every admitted prompt at its true length
(bench/counts.py: QK and PV over the causal triangle, Q/K/V/O bytes),
over the device time of the kernels inside the prefill program (the
attention kernel is the only one there)."""

import counts

PREFILL = r"jit_prefill"


def read(ctx):
    if ctx.peaks is None:
        return None
    s = ctx.trace.kernel_seconds(PREFILL)
    lens = [len(p) for p, _ in ctx.window["admitted"]]
    if s <= 0 or not lens:
        return None
    c = ctx.config
    w = counts.attention(lens, c["num_attention_heads"],
                         c["num_key_value_heads"], c["head_dim"]) * \
        c["num_hidden_layers"]
    least, _ = w.least_s(ctx.peaks["bf16_flops"],
                         ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / s
