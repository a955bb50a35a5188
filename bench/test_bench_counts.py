"""bench/counts.py against shapes worked by hand."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import counts  # noqa: E402

PHI4 = {"hidden_size": 3072, "intermediate_size": 8192,
        "num_hidden_layers": 32, "num_attention_heads": 24,
        "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 200064}


def test_tree_hist_one_level():
    # 100 rows x 3 features x 2 channels: 600 multiply-adds; reads
    # 100*3*4 bins + 100*4 node ids + 2*100*4 weights, writes
    # 2*4*3*8*4 histogram bytes
    w = counts.tree_hist(100, 3, 2, nodes=4, bins=8)
    assert w.flops == 1200
    assert w.bytes == 1200 + 400 + 800 + 768


def test_tree_fit_adds_levels_and_leaves():
    w = counts.tree_fit(10, 2, 1, depth=2, bins=4)
    want = (counts.tree_hist(10, 2, 1, 1, 4) + counts.tree_hist(10, 2, 1, 2, 4)
            + counts.tree_hist(10, 1, 1, 1, 4))
    assert w == want


def test_mlp_train():
    # 14-16-16-2: 224 + 256 + 32 = 512 weights; 6 ops each per row
    w = counts.mlp_train((14, 16, 16, 2), batch=64, steps=100)
    assert w.flops == 6 * 512 * 64 * 100


def test_attention_causal_triangle():
    # one prompt of 4 tokens, 2 heads of 8, 1 kv head: 10 pairs,
    # 2 matmuls x 8 dims x 2 ops x 2 heads; bf16 Q, O (2 heads) and
    # K, V (1 head) of 4 x 8
    w = counts.attention([4], heads=2, kv_heads=1, head_dim=8)
    assert w.flops == 10 * 2 * 8 * 2 * 2
    assert w.bytes == 2 * 8 * 4 * (2 * 2 + 2 * 1)


def test_phi4_weights():
    per_layer, head = counts.decoder_params(PHI4)
    assert per_layer == 3072 * 3072 * 2 + 3072 * 1024 * 2 + 3 * 3072 * 8192
    assert per_layer * 32 + head == pytest.approx(3.836e9, rel=1e-3)


def test_token_and_prompt_flops_agree():
    # a prompt of n tokens with the head read at all n positions does
    # the work of its n tokens, each at its own context
    n = 7
    tok = sum(counts.token_flops(PHI4, pos) for pos in range(n))
    assert counts.prompt_flops(PHI4, n, head_positions=n) == \
        pytest.approx(tok, rel=1e-12)


def test_least_time_picks_the_bound():
    w = counts.Work(flops=197e12, bytes=819e9 * 2)
    assert w.least_s(197e12, 819e9) == (2.0, "memory")
    w = counts.Work(flops=197e12 * 3, bytes=819e9)
    assert w.least_s(197e12, 819e9) == (3.0, "compute")


def test_tree_predict():
    # 10 rows through 3 levels, adding 2 leaf values each; reads 10
    # rows of 4 binned features, writes 10 x 2 sums
    w = counts.tree_predict(10, 3, 2, f=4)
    assert w.flops == 10 * (3 + 2)
    assert w.bytes == 10 * 4 * 4 + 10 * 2 * 4


def test_mlp_predict_and_votes():
    assert counts.mlp_predict((14, 16, 16, 2), 10).flops == 2 * 512 * 10
    assert counts.votes(100, 6).flops == 600
