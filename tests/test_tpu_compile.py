"""Ahead-of-time compiles of the five Pallas kernels for a TPU v5e.

Each kernel is lowered through its ``ops.*`` entry point with
``impl="kernel"`` at the widths the system runs, and compiled by the
installed TPU compiler for a chip that is described, not attached.  No
chip is needed and nothing runs: the compiler refuses here what it would
refuse on the chip (tile-misaligned blocks, scoped-VMEM overflows,
primitives Mosaic cannot lower).

  vote_aggregate  the federation round's party vote: 3 teachers x 6,105
                  public queries (Adult-sized, 48,842 rows), 2 classes,
                  Laplace noise (privacy L2)
  tree_hist       that round's stacked tree fits: 2,048-row pow2 bucket,
                  14 features x 32 bins, 2 channels, vmapped over 20
                  trees; plus the 64-leaf leaf build
  flash_attention phi4-mini prefill: 24 query / 8 kv heads of dim 128,
                  4 x 128 tokens, bf16
  rglru_scan      recurrentgemma-2b: d = 2560, 2,048 steps
  wkv6            rwkv6-7b: 64 heads of 64 x 64 state, 512 steps

The four stacked tree programs are compiled the same way, at that
round's widths (6 models of 20 trees or 2 boosting rounds, depth 6, 14
features x 32 bins, a 2,048-row bucket, 6,105 queries), and must route
and bin rows without a gather: on the TPU each per-row lookup into a
small table is a serial gather, and a binary search a ``while`` of them.
So must the forests' stacked bootstrap draw, at the round's student
sizes (2 x 6,105 rows into 8,192) and at the 8,215-row forest silo's
teacher sizes (6 members of 2,738-2,739 rows into 4,096).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test worker
imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import trees as T
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _cases(chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    T, bucket = 6105, 2048
    hist = jax.vmap(lambda xb, node, w: ops.tree_hist(
        xb, node, w, num_nodes=32, num_bins=32, impl="kernel"))
    seq = s((1, 2048, 2560), jnp.bfloat16)
    rk = s((1, 512, 64, 64), jnp.bfloat16)
    return {
        "vote_aggregate": (
            lambda p, n: ops.votes_with_clean(p, 2, n, impl="kernel"),
            s((3, T), jnp.int32), s((T, 2), jnp.float32)),
        "tree_hist": (
            hist, s((20, bucket, 14), jnp.int32),
            s((20, bucket), jnp.int32), s((20, 2, bucket), jnp.float32)),
        "node_hist": (
            lambda node, w: ops.node_hist(node, w, num_nodes=64,
                                          impl="kernel"),
            s((bucket,), jnp.int32), s((2, bucket), jnp.float32)),
        "flash_attention": (
            lambda q, k, v: ops.attention(q, k, v, impl="kernel"),
            s((4, 128, 24, 128), jnp.bfloat16),
            s((4, 128, 8, 128), jnp.bfloat16),
            s((4, 128, 8, 128), jnp.bfloat16)),
        "rglru_scan": (
            lambda x, a: ops.rglru(x, a, impl="kernel"), seq, seq),
        "wkv6": (
            lambda r, k, v, w, u: ops.wkv(r, k, v, w, u, impl="kernel"),
            rk, rk, rk, rk, s((64, 64), jnp.float32)),
    }


@pytest.mark.parametrize("kernel", ["vote_aggregate", "tree_hist",
                                    "node_hist", "flash_attention",
                                    "rglru_scan", "wkv6"])
def test_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    fn, *args = _cases(one_chip)[kernel]
    compiled = jax.jit(fn).lower(*args).compile()
    print(kernel, compiled.memory_analysis())
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's own name, which a device trace shows for its op (the
    # leaf build runs the tree_hist kernel)
    name = "tree_hist" if kernel == "node_hist" else kernel
    assert re.search(rf"%{name}(\.\d+)? = .*custom-call", text)


def _tree_programs(chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    k, M, F, B, depth, n_trees, rounds, N = 6, 2048, 14, 32, 6, 20, 2, 6105
    X, edges = s((k, M, F), jnp.float32), s((k, F, B - 1), jnp.float32)
    y, Xq = s((k, M), jnp.int32), s((N, F), jnp.float32)

    def trees(n, C):
        return (s((k, n, 2 ** depth - 1), jnp.int32),
                s((k, n, 2 ** depth - 1), jnp.int32),
                s((k, n, 2 ** depth, C), jnp.float32))

    rf = T.RandomForest(num_trees=n_trees)

    def draw(sizes, bucket):
        return (lambda keys: rf.bootstrap_stacked(keys, sizes, bucket, F),
                s((len(sizes), 2), jnp.uint32))

    return {
        "bootstrap_stacked_students": draw((N, N), 8192),
        "bootstrap_stacked_teachers": draw((2739, 2738, 2738) * 2, 4096),
        "fit_forest_stacked": (
            lambda X, e, y, w, fm: T.fit_forest_stacked(
                X, e, y, w, fm, depth=depth, num_classes=2, impl="kernel"),
            X, edges, y, s((k, n_trees, M), jnp.float32),
            s((k, n_trees, F), jnp.float32)),
        "fit_gbdt_stacked": (
            lambda X, e, y, w: T.fit_gbdt_stacked(
                X, e, y, w, 0.3, num_rounds=rounds, depth=depth,
                impl="kernel"),
            X, edges, y, s((k, M), jnp.float32)),
        "predict_forest_stacked": (
            T.predict_forest_stacked, trees(n_trees, 2), Xq, edges),
        "predict_gbdt_stacked": (
            T.predict_gbdt_stacked, trees(rounds, 1), Xq, edges,
            s((), jnp.float32)),
    }


@pytest.mark.parametrize("program", ["bootstrap_stacked_students",
                                     "bootstrap_stacked_teachers",
                                     "fit_forest_stacked",
                                     "fit_gbdt_stacked",
                                     "predict_forest_stacked",
                                     "predict_gbdt_stacked"])
def test_tree_program_compiles_gather_free(program, one_chip,
                                           no_persistent_cache):
    fn, *args = _tree_programs(one_chip)[program]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert not re.findall(r" gather\(", text)
    # the boosting lax.scan is the one loop; binning has none
    loops = 1 if program == "fit_gbdt_stacked" else 0
    assert len(re.findall(r" while\(", text)) == loops
