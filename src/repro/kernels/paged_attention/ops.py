"""Public wrapper: block-table-aware paged decode attention.

Unlike the flash wrapper there is no GQA repeat here at all: the kernel
streams one whole pool block per step and each kv head's ``G`` query
heads share its ``(T, D)`` slice, so the pool is never copied
``H / Hkv`` times.  ``interpret=None`` compiles the kernel on a TPU and
interprets it elsewhere (``repro.kernels.backend``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.backend import resolve_interpret
from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas, paged_prefill_attention_pallas)


def _on_each_device(kernel, *args):
    """Call ``kernel(*args)``.  A Mosaic kernel is a one-device program
    that XLA cannot partition, so when the caller traces under a
    multi-device mesh (``PlacementPlan.tracing``) it runs on every
    device over replicated operands and returns a replicated result."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and mesh.size > 1:
        kernel = jax.shard_map(kernel, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False)
    return kernel(*args)


def _check_scales(k_pool, k_scale, v_scale):
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if k_scale is not None:
        R, _T, KV, _D = k_pool.shape
        want = (R, KV)
        if tuple(k_scale.shape) != want or tuple(v_scale.shape) != want:
            raise ValueError(f"scale shape mismatch: want {want}, got "
                             f"k {k_scale.shape}, v {v_scale.shape}")


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    k_scale=None, v_scale=None, interpret=None):
    """Decode attention straight off a paged KV block pool.

    q: (B, H, D) — one query token per slot.
    k_pool, v_pool: (R, T, KV, D) — the physical block pool (row 0 is
        the NULL block; its contents are write-garbage by design).
    tables: (B, nb) int — physical pool row of each logical block.
    lengths: (B,) int — valid positions per slot (the engine passes
        ``positions + 1``: the current token's K/V is already appended).
    k_scale, v_scale: (R, KV) f32 — per-block absmax scales when the
        pool stores a narrow dtype (int8/fp8); each streamed block is
        dequantized in-kernel at the gather path's exact rounding site.

    Returns (B, H, D) in q's dtype.  Every block the table references
    inside ``lengths[b]`` must be a real (non-NULL) block — the
    allocator's up-front reservation guarantees it.
    """
    B, H, D = q.shape
    R, T, KV, Dk = k_pool.shape
    if H % KV != 0:
        raise ValueError(f"H={H} must be a multiple of KV={KV}")
    if Dk != D or v_pool.shape != k_pool.shape:
        raise ValueError(f"pool/query shape mismatch: q {q.shape}, "
                         f"k {k_pool.shape}, v {v_pool.shape}")
    _check_scales(k_pool, k_scale, v_scale)
    return _on_each_device(
        functools.partial(paged_attention_pallas,
                          interpret=resolve_interpret(interpret)),
        q, k_pool, v_pool, tables.astype(jnp.int32),
        lengths.astype(jnp.int32), k_scale, v_scale)


def paged_prefill_attention(q, k_pool, v_pool, tables, lengths, *,
                            k_scale=None, v_scale=None, interpret=None):
    """Multi-token (qlen > 1) prefill attention off the paged pool — the
    chunked-prefill / speculative-decoding query mode.

    q: (B, Q, H, D) — Q consecutive query tokens per slot, causally
        masked: query position qi attends kv positions <= start + qi.
    k_pool, v_pool: (R, T, KV, D) — the chunk's K/V must already be
        appended at positions [start, start + Q).
    tables: (B, nb) int — physical pool row of each logical block.
    lengths: (B,) int — ``start + Q`` valid positions per slot.
    k_scale, v_scale: (R, KV) f32 — per-block scales for narrow pools.

    Returns (B, Q, H, D) in q's dtype.  Q == 1 is bit-identical to
    :func:`paged_attention` (same block layout, masks, and roundings).
    """
    B, Q, H, D = q.shape
    R, T, KV, Dk = k_pool.shape
    if H % KV != 0:
        raise ValueError(f"H={H} must be a multiple of KV={KV}")
    if Dk != D or v_pool.shape != k_pool.shape:
        raise ValueError(f"pool/query shape mismatch: q {q.shape}, "
                         f"k {k_pool.shape}, v {v_pool.shape}")
    _check_scales(k_pool, k_scale, v_scale)
    return _on_each_device(
        functools.partial(paged_prefill_attention_pallas,
                          interpret=resolve_interpret(interpret)),
        q, k_pool, v_pool, tables.astype(jnp.int32),
        lengths.astype(jnp.int32), k_scale, v_scale)
