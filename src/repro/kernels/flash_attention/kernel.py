"""Blocked causal attention (FlashAttention-style) Pallas TPU kernel.

Ladder mapping: the (block_q x block_k) tiling is the *explicit data
caching* step applied to attention (the O(S^2) score matrix never
materializes in HBM); the sequential k-block grid dim with VMEM-resident
(m, l, acc) running stats is the *customized pipelining* step (Mosaic
overlaps the k-block DMA with the MXU work); (batch*heads, q-blocks) are
*parallel* grid dims (PE duplication).

Grid: (B*H, S/block_q, S/block_k), k innermost (sequential).
Scratch (VMEM, per (bh, qi) stream): m (bq, 1), l (bq, 1), acc (bq, D).
Masked logits use -1e30; with ascending k-blocks every causal row sees its
diagonal block before any fully-masked block, so exp underflows to exact 0
and no NaN guard is needed (documented in tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                 *, scale: float, causal: bool, block_q: int, block_k: int,
                 offset: int = 0):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _block():
        q = q_ref[0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0].astype(jnp.float32)          # (bk, D)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        if causal:
            # query row r sits at absolute kv position r + offset (the
            # chunked-prefill case: S_kv = prefix + S_q, offset = S_kv -
            # S_q; offset == 0 is the classic square mask).
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows + offset, s, NEG_INF)

        m_prev = m_ref[...]                       # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                    # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)           # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip blocks strictly above the (offset) diagonal
        @pl.when(ki * block_k <= qi * block_q + block_q - 1 + offset)
        def _():
            _block()
    else:
        _block()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret",
                     "n_heads", "n_kv_heads"),
)
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool, n_heads: int = 0,
                           n_kv_heads: int = 0):
    """q: (B*H, S, D) -> (B*H, S, D), same dtype as q.

    GQA runs on the grid, not on copied data: with ``n_heads`` /
    ``n_kv_heads`` given, k and v are the UN-repeated (B*Hkv, S_kv, D)
    streams and each q stream's k-block index map points at its kv
    group's stream (``(b // H) * Hkv + (b % H) // G``) — the kernel body
    is untouched, so the output is bit-identical to feeding it repeated
    K/V, without ever materializing the H/Hkv copies.  Defaulting both
    to 0 keeps the legacy H == Hkv contract.

    ``S_kv >= S_q`` is allowed (the chunked-prefill query mode): the
    causal mask shifts by ``offset = S_kv - S_q``, i.e. query row r
    attends kv positions ``<= r + offset`` — with S_kv == S_q this is
    the classic square causal mask, unchanged.
    """
    BH, S, D = q.shape
    Skv = k.shape[1]
    H = n_heads or BH
    Hkv = n_kv_heads or H
    assert H % Hkv == 0 and BH % H == 0, (BH, H, Hkv)
    group = H // Hkv
    BHkv = (BH // H) * Hkv
    assert Skv >= S, (S, Skv)
    assert k.shape == v.shape == (BHkv, Skv, D), (q.shape, k.shape, v.shape)
    block_q = min(block_q, S)
    block_k = min(block_k, Skv)
    assert S % block_q == 0 and Skv % block_k == 0, (S, Skv, block_q, block_k)
    scale = 1.0 / (D ** 0.5)

    grid = (BH, S // block_q, Skv // block_k)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, offset=Skv - S)

    def kv_stream(b):
        return (b // H) * Hkv + (b % H) // group

    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j: (kv_stream(b), j, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j: (kv_stream(b), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        # NaN guard for rectangular causal: offset >= 0 keeps every query
        # row's diagonal block in range, so l > 0 always holds here too.
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        **kw,
    )(q, k, v)
