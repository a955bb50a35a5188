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

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
    assert "tpu_custom_call" in compiled.as_text()
