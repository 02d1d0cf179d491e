"""Compile the served paged-attention kernels for a described TPU v5e.

The rest of the suite runs the Pallas kernels in interpret mode on the
CPU, which accepts block shapes the chip's compiler refuses.  These
cases compile the decode and chunked-prefill kernels with
``interpret=False`` at qwen3-8b's published attention widths against a
v5e topology that JAX describes without a chip attached, so a tiling or
SMEM regression fails here rather than on the chip.  Nothing runs: a
compile that passes is not a chip run.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this module.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas, paged_prefill_attention_pallas)

# qwen3-8b attention widths (configs/qwen3_8b.py), the engine's default
# block size, and a 16K-token pool (1024 blocks + the NULL row): the
# narrow pools' (R, KV) f32 scales must fit SMEM at that size.
H, KV, D, T = 32, 8, 128, 16
POOL_ROWS = 1 + 1024
TABLE_WIDTH = 64                     # max_seq 1024 / T
# (slots, query tokens per slot): a batch-8 decode tick and one
# 128-token prefill chunk.
SHAPES = {"decode": (8, 1), "prefill": (1, 128)}
POOL_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kv_dtype", sorted(POOL_DTYPES))
@pytest.mark.parametrize("mode", sorted(SHAPES))
def test_paged_kernel_compiles_for_v5e(mode, kv_dtype, one_chip,
                                       no_persistent_cache):
    B, Q = SHAPES[mode]
    pool_dtype = POOL_DTYPES[kv_dtype]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q_shape = (B, H, D) if mode == "decode" else (B, Q, H, D)
    args = [spec(q_shape, jnp.bfloat16),
            spec((POOL_ROWS, T, KV, D), pool_dtype),
            spec((POOL_ROWS, T, KV, D), pool_dtype),
            spec((B, TABLE_WIDTH), jnp.int32),
            spec((B,), jnp.int32)]
    if kv_dtype != "bf16":
        args += [spec((POOL_ROWS, KV), jnp.float32)] * 2
    fn = (paged_attention_pallas if mode == "decode"
          else paged_prefill_attention_pallas)
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
