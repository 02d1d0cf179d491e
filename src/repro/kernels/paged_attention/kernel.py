"""Block-table-aware paged attention — the gather-free O6 step.

The paged serving rung's original step re-materializes a dense
``(B, max_seq, ...)`` view of every KV leaf from the block pool on every
decode tick (``serving/paged.BlockPagingPlan.gather``) just so dense
attention can read it — O(B * max_seq) HBM traffic per generated token.
This kernel is the *explicit data caching* / *scratchpad reorganization*
answer: it consumes the pool, the block tables and the per-slot lengths
directly, so the only KV bytes moved are the blocks each slot's table
actually references.

Ladder mapping: streaming K/V one physical block at a time with
VMEM-resident ``(m, l, acc)`` online-softmax state is the same blocked
discipline as ``kernels/flash_attention`` (explicit caching +
pipelining); the batch grid dim is PE duplication.  GQA is handled
without materializing repeated K/V: each kv head's ``G = H // KV`` query
heads attend one shared ``(T, D)`` slice of the streamed block.

One kernel serves every query length.  A slot carries ``Q >= 1`` query
tokens (decode is ``Q == 1``; chunked prefill and speculative verify are
``Q > 1``), laid out per kv head as ``G * Q`` g-major rows: row ``r`` is
query position ``r % Q`` of query head ``h * G + r // Q``.

Tiling.  The TPU block-shape rule wants the last two dims of every block
divisible by (8, 128) or equal to the array's.  So every block spans the
full trailing dims: the query block is a slot's whole ``(KV, G*Q, D)``
tile, and the KV block is one whole ``(T, KV, D)`` pool row, so one DMA
moves a physical block with every kv head in it.  The kv-head loop runs
inside the kernel and reads head ``h`` as ``block[:, h, :]``.  (Viewing
the pool as ``(R, T, KV*D)`` instead makes XLA copy the whole pool into
that layout once the pool passes ~64 MB.)

Grid: ``(B, 2 * nb)`` with the block walk innermost (sequential).  The
walk is TWO passes over the slot's block list, phase = j // nb:

  phase 0 — online-softmax statistics: running row-max ``m`` (exact)
            and rescaled denominator ``l``;
  phase 1 — the weighted-value accumulation, with the probabilities
            rounded to the query dtype before the PV product.

The two-pass structure keeps the kernel's rounding sites those of the
dense decode path: bf16 scores (einsum output dtype), f32 mask and
softmax, probabilities rounded back to bf16 before the PV product, one
final output round.  Phase 1 applies the same roundings in the same
order, so kernel-path logits track the gather-path logits to
reduction-order noise instead of bf16-rounding noise.

The block tables and lengths ride in as scalar-prefetch operands (flat,
so SMEM holds them unpadded) and the ``BlockSpec`` index maps turn a
*logical* block index ``j % nb`` into the *physical* pool row
``tables[b, j % nb]`` before the DMA is issued — the indirection happens
in the index map, never as a gathered copy.  The pool is the model's
layer-stacked ``(L, R, T, KV, D)`` leaf, read through its free
``(L * R, T, KV, D)`` view: layer ``l``'s rows start at ``l * R``, so
the wrapper offsets the tables by that much and the kernel DMAs layer
``l``'s blocks straight out of the stack.  A layer scan hands the kernel
the whole stack it carries: no per-layer slice of the pool is built for
the call, nor a restacked copy after it.  The index map stays a
one-layer pool's: on a v5e at 32 decode slots, a 5-D block indexed by a
prefetched layer cost 7% more kernel time, and adding a prefetched
``l * R`` inside the map 3%.  Narrow pools add that layer's ``(R, KV)``
f32 scales as two more flat scalar-prefetch operands.

Masking uses -1e30 like the flash kernel.  Query position ``qi`` attends
kv positions ``idx < length - (Q - 1 - qi)`` with ``length = start + Q``
valid positions; for ``Q == 1`` that is ``idx < length``.  Blocks
entirely past ``lengths[b]`` are skipped (their table entries may be the
NULL block; its DMA is cheap and its values are never read).  Every
row's limit is at least 1, so logical block 0 (walked first) always
gives each row a valid score — ``m`` is real before any fully-masked
block, whose ``exp(-1e30 - m)`` is then exactly 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dequant(raw, s, dt):
    """Per-block dequant, bit-matching ``serving.kvquant.dequantize``
    (kept inline so the kernel package stays import-free of serving):
    f32 multiply by the block's absmax scale, ONE round to the compute
    dtype, then the f32 widening every score path applies anyway."""
    return (raw.astype(jnp.float32) * s).astype(dt).astype(jnp.float32)


def _paged_kernel(tables_ref, lens_ref, *refs, scale: float,
                  block_size: int, n_blocks: int, n_kv: int, q_len: int,
                  layer_rows: int, quantized: bool):
    if quantized:
        (kscale_ref, vscale_ref, q_ref, k_ref, v_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        kscale_ref = vscale_ref = None
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    jj = j % n_blocks                # logical block within the pass
    phase = j // n_blocks            # 0: (m, l) stats; 1: PV accumulate
    dt = q_ref.dtype

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]
    if quantized:
        # The tables hold rows of the stacked pool's (L * R, T, KV, D)
        # view; the scales are one layer's (R, KV): the row in the layer.
        row = jax.lax.rem(tables_ref[b * n_blocks + jj], layer_rows)
    # Skip blocks entirely past this slot's valid prefix (no compute;
    # the NULL-block rows inactive table tails point at are never read).
    in_range = jj * block_size < length

    def head_slice(ref, scale_ref, h):
        """Kv head ``h``'s (T, D) slice of the streamed block, in f32;
        narrow pools dequantize with this block's scalar scale, read
        from SMEM through the same table indirection the DMA used."""
        raw = ref[0, :, h, :]
        if scale_ref is None:
            return raw.astype(jnp.float32)
        return _dequant(raw, scale_ref[row * n_kv + h], dt)

    def scores(h):
        """Masked f32 scores (G*Q, T) of kv head ``h``, rounded like
        the dense path: the qk product and the scale multiply round to
        the query dtype before the f32 mask/softmax."""
        q = q_ref[0, h].astype(jnp.float32)              # (G*Q, D)
        s = jax.lax.dot_general(
            q, head_slice(k_ref, kscale_ref, h), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (G*Q, T)
        s = (s.astype(dt) * scale).astype(dt).astype(jnp.float32)
        idx = jj * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        limit = length
        if q_len > 1:
            qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % q_len
            limit = length - (q_len - 1 - qi)
        return jnp.where(idx < limit, s, NEG_INF)

    @pl.when((phase == 0) & in_range)
    def _stats():
        for h in range(n_kv):
            s = scores(h)
            m_prev = m_ref[h]                            # (G*Q, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new

    @pl.when((phase == 1) & in_range)
    def _accumulate():
        for h in range(n_kv):
            s = scores(h)
            v = head_slice(v_ref, vscale_ref, h)         # (T, D)
            p = jnp.exp(s - m_ref[h]) / jnp.maximum(l_ref[h], 1e-30)
            # Round the probabilities to the query dtype — the dense
            # path's ``softmax(s).astype(dt)`` — so the PV product sees
            # identical inputs to the gather step's einsum.
            p = p.astype(dt).astype(jnp.float32)
            acc_ref[h] += jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                   k_scale=None, v_scale=None, layer=None,
                                   *, interpret: bool):
    """q: (B, Q, H, D) — Q query tokens per slot, causally masked against
    a paged KV prefix whose last Q positions ARE those tokens;
    k_pool/v_pool: the layer-stacked pool (L, R, T, KV, D) with ``layer``
    an int32 scalar naming the layer to read, or one layer's pool
    (R, T, KV, D) with ``layer`` None (the L == 1 stack); tables: (B, nb)
    int32; lengths: (B,) int32 = start + Q valid positions per slot (the
    chunk's K/V already appended); k_scale/v_scale: that layer's (R, KV)
    f32 per-block absmax scales when the pool is narrow.  Returns
    (B, Q, H, D) in q's dtype."""
    if k_pool.ndim == 4:
        assert layer is None, "a one-layer pool takes no layer index"
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    B, Q, H, D = q.shape
    L, R, T, KV, Dk = k_pool.shape
    assert Dk == D and v_pool.shape == k_pool.shape, (q.shape, k_pool.shape)
    assert H % KV == 0, (H, KV)
    GQ = H // KV * Q
    nb = tables.shape[1]
    assert tables.shape == (B, nb) and lengths.shape == (B,), (
        tables.shape, lengths.shape)
    quantized = k_scale is not None
    # Layer ``layer``'s block r is row ``layer * R + r`` of the stack's
    # (L * R, T, KV, D) view (a free reshape).
    rows = tables + jnp.asarray(layer, jnp.int32) * R
    operands = (rows.reshape(B * nb), lengths)
    if quantized:
        assert k_scale.shape == (R, KV) and v_scale.shape == (R, KV), (
            k_scale.shape, v_scale.shape)
        operands += (k_scale.reshape(R * KV), v_scale.reshape(R * KV))

    # g-major rows per kv head: (B, Q, H, D) -> (B, KV, G*Q, D).
    qr = q.transpose(0, 2, 1, 3).reshape(B, KV, GQ, D)
    q_map = lambda b, j, *_: (b, 0, 0, 0)                    # noqa: E731
    # ONE physical pool block, all kv heads, selected through the table.
    kv_map = lambda b, j, tbl, *_: (                             # noqa: E731
        tbl[b * nb + j % nb], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(operands),
        grid=(B, 2 * nb),
        in_specs=[
            pl.BlockSpec((1, KV, GQ, D), q_map),
            pl.BlockSpec((1, T, KV, D), kv_map),
            pl.BlockSpec((1, T, KV, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, KV, GQ, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((KV, GQ, 1), jnp.float32),
            pltpu.VMEM((KV, GQ, 1), jnp.float32),
            pltpu.VMEM((KV, GQ, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=1.0 / (D ** 0.5), block_size=T, n_blocks=nb,
        n_kv=KV, q_len=Q, layer_rows=R, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, GQ, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands, qr, k_pool.reshape(L * R, T, KV, D),
      v_pool.reshape(L * R, T, KV, D))
    return out.reshape(B, H, Q, D).transpose(0, 2, 1, 3)


def paged_attention_pallas(q, k_pool, v_pool, tables, lengths,
                           k_scale=None, v_scale=None, layer=None, *,
                           interpret: bool):
    """Decode: q (B, H, D), one query token per slot — the ``Q == 1``
    case of :func:`paged_prefill_attention_pallas`.  Returns (B, H, D)."""
    return paged_prefill_attention_pallas(
        q[:, None], k_pool, v_pool, tables, lengths, k_scale, v_scale,
        layer, interpret=interpret)[:, 0]
