"""Compile the served paged-attention kernels for a described TPU v5e.

The rest of the suite runs the Pallas kernels in interpret mode on the
CPU, which accepts block shapes the chip's compiler refuses.  These
cases compile the decode and chunked-prefill kernels with
``interpret=False`` at qwen3-8b's published attention widths against a
v5e topology that JAX describes without a chip attached, so a tiling or
SMEM regression fails here rather than on the chip.  The served decode
and prefill programs are compiled whole as well, to hold the layer scan
to updating the donated pool in place.  Nothing runs: a compile that
passes is not a chip run.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this module.
"""

import dataclasses
import functools
import re
from types import SimpleNamespace

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


# The served cell's pool (bench/configs/qwen3-8b-9l.json): 32 slots of
# max_seq 9216 over 6,144 blocks + the NULL row, one 128-token chunk.
STEP_SLOTS, STEP_ROWS, STEP_TABLE_WIDTH, STEP_CHUNK = 32, 1 + 6144, 576, 128
STEP_LAYERS = 3
_COPY_OPS = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")
_ARRAY = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|bf16|f16|f32|s64|f64)"
                    r"\[([0-9,]*)\]")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "f64": 8}


def _bulk_moves(hlo_text: str, limit: int) -> list:
    """Every instruction, fused or not, that copies, slices or updates a
    slice and whose result holds an array of ``limit`` bytes or more."""
    found = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z-]+)\(", line)
        if not m or m.group(3) not in _COPY_OPS:
            continue
        for dtype, dims in _ARRAY.findall(m.group(2)):
            n = _ITEMSIZE[dtype]
            for d in filter(None, dims.split(",")):
                n *= int(d)
            if n >= limit:
                found.append(f"{m.group(1)} {m.group(3)} {n} B")
                break
    return found


@pytest.mark.parametrize("kv_dtype", sorted(POOL_DTYPES))
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_served_step_updates_pool_in_place(program, kv_dtype, one_chip,
                                           monkeypatch, no_persistent_cache):
    """The served decode program and prefill-chunk program of a 3-layer
    qwen3-8b, pool donated as the engine donates it: the layer scan
    carries the stacked pool (and a narrow pool's scales), so no copy,
    slice or slice update of one layer's pool leaf or more appears, and
    the temporaries stay below one layer's K leaf (slicing each layer
    out for the kernel and restacking the scan's output cost two whole
    stacked leaves)."""
    from repro.configs import get_config
    from repro.kernels.paged_attention import ops
    from repro.models import get_model
    from repro.parallel.sharding import PlacementPlan
    from repro.serving.layout import PagedLayout
    from repro.serving.sampler import SamplerConfig

    monkeypatch.setattr(ops, "resolve_interpret", lambda interpret: False)
    cfg = dataclasses.replace(get_config("qwen3-8b"), n_layers=STEP_LAYERS,
                              param_dtype="bfloat16")
    model = get_model(cfg)
    quantized = kv_dtype != "bf16"
    manager = SimpleNamespace(
        plan=SimpleNamespace(quantized=quantized, kv_dtype=kv_dtype),
        state_plan=None, state=None, has_blocks=True)
    layout = PagedLayout(paged_attn="kernel", kv_dtype=kv_dtype)
    placement = PlacementPlan()
    if program == "decode":
        step = layout.make_step(model, SamplerConfig(), manager, placement)
    else:
        layout.attn_impl = "kernel"
        step = layout.make_prefill_step(model, SamplerConfig(), manager,
                                        placement)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: spec(x.shape, x.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool_dtype = POOL_DTYPES[kv_dtype]
    leaf = spec((STEP_LAYERS, STEP_ROWS, T, KV, D), pool_dtype)
    cache = {"k": leaf, "v": leaf}
    if quantized:
        scale = spec((STEP_LAYERS, STEP_ROWS, 1, KV, 1), jnp.float32)
        cache = {"pool": cache, "scale": {"k": scale, "v": scale}}
    B, i32 = STEP_SLOTS, jnp.int32
    tables = spec((B, STEP_TABLE_WIDTH), i32)
    if program == "decode":
        rest = (spec((B, 1), i32), spec((B,), i32), spec((B,), i32))
    else:
        rest = (spec((), i32), spec((1, STEP_CHUNK), i32), spec((1,), i32),
                spec((1,), i32), spec((1,), i32))
    compiled = step.lower(params, cache, tables, *rest).compile()
    one_layer = STEP_ROWS * T * KV * D * jnp.dtype(pool_dtype).itemsize
    assert _bulk_moves(compiled.as_text(), one_layer) == []
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer
