"""Attention: GQA with rope / qk-norm, chunked prefill, cached decode.

Memory discipline follows the paper's ladder: the *naive* (O0) formulation
materializes the full (S, S) score tensor; the production path is the
*chunked* formulation (O1 explicit caching + O2 pipelining via ``lax.scan``
over query blocks) which keeps a (q_chunk, S) working set — the jnp analog
of the Pallas flash kernel in ``repro/kernels/flash_attention``, which no
model path calls.  Every path here is XLA except the paged serving hooks
(:func:`paged_decode_attention`, :func:`paged_chunk_prefill_attention`),
which call the block-table Pallas kernel in
``repro/kernels/paged_attention`` — compiled on a TPU, interpreted on
other backends.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import PDef, rms_norm, rope
from repro.parallel.sharding import constrain


def attn_defs(d: int, n_heads: int, n_kv: int, head_dim: int,
              qk_norm: bool = False) -> dict:
    defs = {
        "wq": PDef((d, n_heads, head_dim), ("embed", "heads", None)),
        "wk": PDef((d, n_kv, head_dim), ("embed", "kv", None)),
        "wv": PDef((d, n_kv, head_dim), ("embed", "kv", None)),
        "wo": PDef((n_heads, head_dim, d), ("heads", None, "embed")),
    }
    if qk_norm:
        defs["q_norm"] = PDef((head_dim,), (None,), "ones")
        defs["k_norm"] = PDef((head_dim,), (None,), "ones")
    return defs


def _project_qkv(params, x, positions, *, qk_norm: bool, rope_theta: float,
                 use_rope: bool = True):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if use_rope:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    return q, k, v


def _heads_shardable(n_heads: int) -> bool:
    """True when the merged head count divides the mesh axes mapped to
    "heads" (ambient sharder; True on CPU/no-mesh)."""
    from repro.parallel.sharding import get_sharder
    s = get_sharder()
    if s is None:
        return True
    tp = 1
    for ax in s.rules.get("heads", ()):
        tp *= s.mesh_sizes.get(ax, 1)
    return tp <= 1 or n_heads % tp == 0


def _gqa_scores(q, k, scale):
    """q: (B, qc, KV, G, dh); k: (B, S, KV, dh) -> (B, KV, G, qc, S)."""
    return jnp.einsum("bqhgk,bshk->bhgqs", q, k) * scale


def attention(params, x, positions, *, n_heads, n_kv, head_dim,
              causal=True, qk_norm=False, rope_theta=1e4, q_chunk=1024,
              kv_x=None, kv_positions=None, use_rope=True, unroll=False,
              scores_dtype=jnp.float32):
    """Chunked multi-head attention.

    ``kv_x`` switches to cross-attention (keys/values from encoder states,
    no causal mask, no rope on kv side unless positions given).
    x: (B, S, d) -> (B, S, d).
    """
    B, S, d = x.shape
    dt = x.dtype
    scale = head_dim ** -0.5
    group = n_heads // n_kv

    if kv_x is None:
        q, k, v = _project_qkv(params, x, positions, qk_norm=qk_norm,
                               rope_theta=rope_theta, use_rope=use_rope)
        kv_pos = positions
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", kv_x, params["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", kv_x, params["wv"].astype(dt))
        if qk_norm:
            q = rms_norm(q, params["q_norm"])
            k = rms_norm(k, params["k_norm"])
        if use_rope:
            q = rope(q, positions, rope_theta)
            if kv_positions is not None:
                k = rope(k, kv_positions, rope_theta)
        kv_pos = kv_positions

    # Layout selection: when the merged head count divides the TP axis,
    # use the MERGED-heads discipline (Megatron): expand KV heads to the
    # full H once, so q / k / v / scores / probs / o are ALL sharded on the
    # same "heads" axis and the attention path needs zero resharding.  (A
    # split (KV, G) layout forces the SPMD partitioner into involuntary
    # full rematerialization between the heads-sharded projections and any
    # score sharding — EXPERIMENTS §Perf measures the difference.)
    #
    # When heads DON'T divide (llama4's 40, smollm's 15 on a 16-way axis),
    # expansion would replicate k/v AND the compute; instead keep the
    # grouped GQA math with the query-SEQUENCE dim sharded end-to-end
    # (sequence parallelism): scores, probs and o all shard over qc, so
    # the quadratic work still spreads across the TP axis.
    merged = _heads_shardable(n_heads)
    S_kv = k.shape[1]

    if merged:
        if group > 1:
            k = jnp.repeat(k, group, axis=2)              # (B, Skv, H, dh)
            v = jnp.repeat(v, group, axis=2)
        k = constrain(k, "batch", None, "heads", None)
        v = constrain(v, "batch", None, "heads", None)
        q = constrain(q, "batch", None, "heads", None)
    else:
        k = constrain(k, "batch", None, "kv", None)
        v = constrain(v, "batch", None, "kv", None)
        q = constrain(q, "batch", "q_seq", None, None)

    n_chunks = max(1, S // q_chunk)
    qc = S // n_chunks if S % n_chunks == 0 else S
    if S % qc != 0:
        n_chunks, qc = 1, S

    if merged:
        q = q.reshape(B, n_chunks, qc, n_heads, head_dim).swapaxes(0, 1)
    else:
        q = q.reshape(B, n_chunks, qc, n_kv, group, head_dim).swapaxes(0, 1)
    qpos = positions.reshape(B, n_chunks, qc).swapaxes(0, 1) \
        if positions is not None else None
    kvp = (kv_pos if kv_pos is not None
           else jnp.broadcast_to(jnp.arange(S_kv)[None], (B, S_kv)))

    def _softmax(s):
        if s.dtype == jnp.float32:
            return jax.nn.softmax(s, axis=-1).astype(dt)
        # bf16 logits: subtract the (f32) rowmax, exponentiate in bf16,
        # normalize with an f32 sum — the flash-kernel numerics
        m = jnp.max(s.astype(jnp.float32), axis=-1, keepdims=True)
        e = jnp.exp(s - m.astype(s.dtype))
        z = jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True)
        return (e / z.astype(s.dtype)).astype(dt)

    def block_merged(q_blk, qp_blk):
        s = jnp.einsum("bqhk,bshk->bhqs", q_blk, k) * scale
        s = s.astype(scores_dtype)   # f32 faithful; bf16 = §Perf knob
        s = constrain(s, "batch", "heads", "q_seq", None)
        if causal:
            mask = qp_blk[:, None, :, None] >= kvp[:, None, None, :]
            s = jnp.where(mask, s, -1e30)
        p = _softmax(s)
        o = jnp.einsum("bhqs,bshk->bqhk", p, v)           # (B,qc,H,dh)
        return constrain(o, "batch", "q_seq", "heads", None)

    def block_grouped(q_blk, qp_blk):
        q_blk = constrain(q_blk, "batch", "q_seq", None, None, None)
        s = jnp.einsum("bqhgk,bshk->bhgqs", q_blk, k) * scale
        s = s.astype(scores_dtype)
        s = constrain(s, "batch", "kv", None, "q_seq", None)
        if causal:
            mask = (qp_blk[:, None, None, :, None]
                    >= kvp[:, None, None, None, :])
            s = jnp.where(mask, s, -1e30)
        p = _softmax(s)
        o = jnp.einsum("bhgqs,bshk->bqhgk", p, v)         # (B,qc,KV,G,dh)
        o = constrain(o, "batch", "q_seq", "kv", None, None)
        return o.reshape(o.shape[0], o.shape[1], n_heads, head_dim)

    block = block_merged if merged else block_grouped

    if n_chunks == 1:
        out = block(q[0], None if qpos is None else qpos[0])
        out = out[None]
    else:
        # Remat per q-chunk: the backward pass recomputes one chunk's
        # scores at a time instead of keeping all of them resident.
        from repro.models.loops import map_or_unroll
        blk = jax.checkpoint(lambda args: block(*args))
        out = map_or_unroll(blk, (q, qpos), unroll=unroll)

    out = out.swapaxes(0, 1).reshape(B, S, n_heads, head_dim)
    out = constrain(out, "batch", None, "heads", None)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))


def init_kv_cache(batch, max_seq, n_kv, head_dim, dtype=jnp.bfloat16):
    shape = (batch, max_seq, n_kv, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_spec(batch, max_seq, n_kv, head_dim, dtype=jnp.bfloat16):
    shape = (batch, max_seq, n_kv, head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def decode_attention(params, x, cache, positions, *, n_heads, n_kv, head_dim,
                     qk_norm=False, rope_theta=1e4, cross=False,
                     update_cache=True):
    """Single-token attention against a KV cache.

    This is the DENSE decode-attention implementation — one of the two
    pluggable decode hooks a model's ``decode_step`` can run: dense
    attention over a per-slot ``(B, S_max, ...)`` cache view (this
    function; the paged serving rung feeds it a gathered view), or
    :func:`paged_decode_attention`, which consumes a paged block pool +
    block tables directly and never builds the dense view at all.

    x: (B, 1, d); positions: (B,) current index per sequence.
    cache: {"k","v"} of (B, S_max, KV, dh), sequence-sharded for long ctx.
    Returns (out (B, 1, d), new_cache).
    """
    B, T, d = x.shape
    dt = x.dtype
    scale = head_dim ** -0.5
    group = n_heads // n_kv

    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(dt))
    if qk_norm:
        q = rms_norm(q, params["q_norm"])
    q = rope(q, positions[:, None], rope_theta)

    if cross or not update_cache:
        ck, cv = cache["k"], cache["v"]
        new_cache = cache
    else:
        k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(dt))
        v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(dt))
        if qk_norm:
            k = rms_norm(k, params["k_norm"])
        k = rope(k, positions[:, None], rope_theta)
        b_idx = jnp.arange(B)
        ck = cache["k"].at[b_idx, positions].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[b_idx, positions].set(v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}

    ck = constrain(ck, "batch", "kv_seq", "kv", None)
    cv = constrain(cv, "batch", "kv_seq", "kv", None)
    S = ck.shape[1]

    qg = q.reshape(B, T, n_kv, group, head_dim)
    s = jnp.einsum("bthgk,bshk->bhgts", qg, ck.astype(dt)) * scale
    s = s.astype(jnp.float32)
    kv_pos = jnp.arange(S)[None]
    valid = kv_pos <= positions[:, None] if not cross \
        else jnp.ones((B, S), bool)
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bhgts,bshk->bthgk", p, cv.astype(dt))
    o = o.reshape(B, T, n_heads, head_dim)
    out = jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(dt))
    return out, new_cache


def chunk_prefill_attention(params, x, cache, positions, *, n_heads, n_kv,
                            head_dim, qk_norm=False, rope_theta=1e4):
    """Multi-token (prompt-chunk) attention against a dense KV cache —
    the qlen > 1 sibling of :func:`decode_attention`.

    x: (B, C, d) — C consecutive prompt tokens per slot.
    positions: (B, C) — each token's absolute cache index.  Rows past
    the prompt (the padded tail of the final chunk) carry clipped
    positions; their K/V writes land at future positions that are
    rewritten in-graph before first read (the engine's standing garbage
    invariant) and their outputs are discarded by the caller.
    Returns (out (B, C, d), new_cache).  Row arithmetic is identical to
    the single-token path (per-row projections, rope, masked f32
    softmax over the same cache rows), which is what keeps chunked
    prefill bit-identical to feeding the prompt one token at a time.
    """
    B, C, d = x.shape
    dt = x.dtype
    scale = head_dim ** -0.5
    group = n_heads // n_kv

    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(dt))
    if qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    q = rope(q, positions, rope_theta)
    k = rope(k, positions, rope_theta)

    b_idx = jnp.arange(B)[:, None]
    ck = cache["k"].at[b_idx, positions].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[b_idx, positions].set(v.astype(cache["v"].dtype))

    ck = constrain(ck, "batch", "kv_seq", "kv", None)
    cv = constrain(cv, "batch", "kv_seq", "kv", None)
    S = ck.shape[1]

    qg = q.reshape(B, C, n_kv, group, head_dim)
    s = jnp.einsum("bthgk,bshk->bhgts", qg, ck.astype(dt)) * scale
    s = s.astype(jnp.float32)
    kv_pos = jnp.arange(S)[None, None]
    valid = kv_pos <= positions[:, :, None]              # (B, C, S)
    s = jnp.where(valid[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bhgts,bshk->bthgk", p, cv.astype(dt))
    o = o.reshape(B, C, n_heads, head_dim)
    out = jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(dt))
    return out, {"k": ck, "v": cv}


def _unpack_paged(kvs):
    """(ck, cv) or (ck, cv, sk, sv) from the paged kv-leaf tuple."""
    if len(kvs) == 2:
        return kvs[0], kvs[1], None, None
    ck, cv, sk, sv = kvs
    return ck, cv, sk, sv


def _quant_block_write(blk, sblk, write_fn, valid, kv_dtype, dt):
    """Shared requant-on-append discipline for ONE window of pool
    blocks: dequantize the stored window (same rounding site as the
    kernel/gather), apply ``write_fn`` to install the new bf16 K/V,
    zero positions outside ``valid`` so stale garbage never inflates the
    absmax, then re-derive the scale and re-quantize.  Returns
    (quantized window, new scales)."""
    from repro.serving import kvquant

    wide = kvquant.dequantize(blk, sblk, dt)
    wide = jnp.where(valid, write_fn(wide), 0)
    # token + head-dim axes of the (..., T, KV, dh) window
    sx = (wide.ndim - 3, wide.ndim - 1)
    s = kvquant.block_scale(wide, sx, kv_dtype)
    return kvquant.quantize(wide, s, kv_dtype), s


def paged_chunk_prefill_attention(params, x, kvs, tables, positions,
                                  lengths, *, n_heads, n_kv, head_dim,
                                  qk_norm=False, rope_theta=1e4,
                                  kv_dtype="bf16", start=None):
    """Prompt-chunk attention straight off the paged block pool — the
    qlen > 1 sibling of :func:`paged_decode_attention`.

    x: (B, C, d); kvs: (k, v) pool leaves (R, T, KV, dh) — or
    (k, v, k_scale, v_scale) with (R, 1, KV, 1) scales for narrow
    pools; tables: (B, nb); positions: (B, C) absolute index per chunk
    token (clipped for the padded tail — those writes go to
    in-reservation blocks or the NULL block, both write-garbage-safe);
    lengths: (B,) UNCLIPPED ``start + C`` so the kernel's per-row causal
    limits stay exact for the real rows even when the padded tail clips;
    ``start`` (B,) anchors the narrow pools' requant window.
    Returns (out (B, C, d), new kv-leaf tuple).
    """
    from repro.kernels.paged_attention.ops import paged_prefill_attention

    B, C, d = x.shape
    dt = x.dtype
    ck, cv, sk, sv = _unpack_paged(kvs)
    T = ck.shape[1]
    nb = tables.shape[1]

    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(dt))
    if qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    q = rope(q, positions, rope_theta)
    k = rope(k, positions, rope_theta)

    if sk is None:
        rows = jnp.take_along_axis(tables, positions // T, axis=1)  # (B, C)
        offs = positions % T
        ck = ck.at[rows, offs].set(k.astype(ck.dtype))
        cv = cv.at[rows, offs].set(v.astype(cv.dtype))
        o = paged_prefill_attention(q, ck, cv, tables, lengths)
        out = jnp.einsum("bthk,hkd->btd", o.astype(dt),
                         params["wo"].astype(dt))
        return out, (ck, cv)

    # Narrow pool: a chunk spans at most ceil(C/T)+1 logical blocks, so
    # read-modify-write exactly that window per slot.  Window entries
    # past the table horizon redirect to the NULL block (never clip to a
    # real row — a duplicate write there would corrupt live state; NULL
    # absorbs duplicates by design).
    from repro.serving.paged import NULL_BLOCK

    nt = min((C - 1) // T + 2, nb)
    jb_first = (start // T).astype(jnp.int32)             # (B,)
    jbs = jb_first[:, None] + jnp.arange(nt)[None]        # (B, nt)
    rows = jnp.where(
        jbs < nb,
        jnp.take_along_axis(tables, jnp.clip(jbs, 0, nb - 1), axis=1),
        NULL_BLOCK)
    bi = jnp.arange(B)[:, None]
    wi = jnp.clip(positions // T - jb_first[:, None], 0, nt - 1)
    woff = positions % T
    abs_idx = jbs[:, :, None] * T + jnp.arange(T)[None, None]  # (B, nt, T)
    valid = (abs_idx < lengths[:, None, None])[..., None, None]

    ck, nsk = _quant_block_write(
        ck[rows], sk[rows],
        lambda w: w.at[bi, wi, woff].set(k.astype(dt)), valid, kv_dtype, dt)
    cv, nsv = _quant_block_write(
        cv[rows], sv[rows],
        lambda w: w.at[bi, wi, woff].set(v.astype(dt)), valid, kv_dtype, dt)
    ck = kvs[0].at[rows].set(ck)
    cv = kvs[1].at[rows].set(cv)
    sk = sk.at[rows].set(nsk)
    sv = sv.at[rows].set(nsv)

    o = paged_prefill_attention(q, ck, cv, tables, lengths,
                                k_scale=sk[:, 0, :, 0],
                                v_scale=sv[:, 0, :, 0])
    out = jnp.einsum("bthk,hkd->btd", o.astype(dt), params["wo"].astype(dt))
    return out, (ck, cv, sk, sv)


def paged_decode_attention(params, x, kvs, tables, positions, *, n_heads,
                           n_kv, head_dim, qk_norm=False, rope_theta=1e4,
                           kv_dtype="bf16"):
    """Gather-free decode attention against a paged KV block pool.

    The paged-decode counterpart of :func:`decode_attention` (the other
    pluggable hook): instead of a per-slot dense cache view it takes the
    raw pool leaves plus each slot's block table, appends the current
    token's K/V into the slot's active block IN PLACE — one (KV, dh)
    vector per slot, O(B) traffic, not the O(B * max_seq) dense gather —
    and runs the block-table-aware Pallas kernel, which walks the table
    and streams only the blocks each slot's table references.

    x: (B, 1, d); kvs: (k, v) pool leaves (R, T, KV, dh), row 0 the
    NULL block — or (k, v, k_scale, v_scale) with (R, 1, KV, 1) scales
    for narrow pools, which re-quantize the slot's ACTIVE block around
    the append (dequantize, write, mask the unwritten tail, rescale);
    tables: (B, nb); positions: (B,) current index per slot.  Inactive
    slots point every table entry at the NULL block, whose contents are
    write-garbage by design — their outputs are discarded by the
    engine.  Returns (out (B, 1, d), new kv-leaf tuple).
    """
    from repro.kernels.paged_attention.ops import paged_attention

    B, _, d = x.shape
    dt = x.dtype
    ck, cv, sk, sv = _unpack_paged(kvs)
    T = ck.shape[1]

    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(dt))
    if qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    q = rope(q, positions[:, None], rope_theta)
    k = rope(k, positions[:, None], rope_theta)

    # In-place append: position p lives in logical block p // T at
    # offset p % T; the table maps it to a physical pool row.
    row = jnp.take_along_axis(tables, (positions // T)[:, None],
                              axis=1)[:, 0]
    off = positions % T

    if sk is None:
        ck = ck.at[row, off].set(k[:, 0].astype(ck.dtype))
        cv = cv.at[row, off].set(v[:, 0].astype(cv.dtype))
        o = paged_attention(q[:, 0], ck, cv, tables, positions + 1)
        out = jnp.einsum("bhk,hkd->bd", o.astype(dt),
                         params["wo"].astype(dt))
        return out[:, None], (ck, cv)

    bi = jnp.arange(B)
    valid = (jnp.arange(T)[None, :] <= off[:, None])[..., None, None]
    nckb, nsk = _quant_block_write(
        ck[row], sk[row],
        lambda w: w.at[bi, off].set(k[:, 0].astype(dt)), valid, kv_dtype, dt)
    ncvb, nsv = _quant_block_write(
        cv[row], sv[row],
        lambda w: w.at[bi, off].set(v[:, 0].astype(dt)), valid, kv_dtype, dt)
    ck = ck.at[row].set(nckb)
    cv = cv.at[row].set(ncvb)
    sk = sk.at[row].set(nsk)
    sv = sv.at[row].set(nsv)

    o = paged_attention(q[:, 0], ck, cv, tables, positions + 1,
                        k_scale=sk[:, 0, :, 0], v_scale=sv[:, 0, :, 0])
    out = jnp.einsum("bhk,hkd->bd", o.astype(dt), params["wo"].astype(dt))
    return out[:, None], (ck, cv, sk, sv)
