"""Decoder-only transformer (dense + MoE): train forward, loss, decode.

Structure notes (scale posture):
  * Layers are scan-stacked (``jax.lax.scan`` over a (L, ...) param tree) —
    compile time and HLO size are O(1) in depth (88/96-layer configs).
  * Per-layer remat (``jax.checkpoint``) bounds activation memory to one
    layer's inputs; policy from ``ArchConfig.remat``.
  * Vocab is padded to a multiple of 256 so the TP axis always divides it.
  * Loss uses chunked cross-entropy (no full (B, S, V) f32 logits tensor).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.layers import (
    PDef, chunked_cross_entropy, init_params, mlp_apply, mlp_defs,
    param_axes, rms_norm, rms_norm_defs, stack_defs,
)
from repro.parallel.sharding import constrain

VOCAB_PAD = 256


def padded_vocab(v: int) -> int:
    return (v + VOCAB_PAD - 1) // VOCAB_PAD * VOCAB_PAD


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------

def block_defs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    defs = {
        "attn_norm": rms_norm_defs(d),
        "attn": attn.attn_defs(d, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim, cfg.qk_norm),
        "mlp_norm": rms_norm_defs(d),
    }
    if cfg.n_experts:
        defs["moe"] = moe_mod.moe_defs(
            d, cfg.n_experts, cfg.expert_d_ff,
            shared_d_ff=cfg.d_ff if cfg.shared_expert else 0,
        )
    else:
        defs["mlp"] = mlp_defs(d, cfg.d_ff, cfg.mlp_kind)
    return defs


def model_defs(cfg: ArchConfig) -> dict:
    vp = padded_vocab(cfg.vocab)
    return {
        "embedding": PDef((vp, cfg.d_model), ("vocab", "embed"), "small"),
        "lm_head": PDef((cfg.d_model, vp), ("embed", "vocab")),
        "final_norm": rms_norm_defs(cfg.d_model),
        "layers": stack_defs(block_defs(cfg), cfg.n_layers),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def block_apply(cfg: ArchConfig, params, h, positions):
    """One decoder block. h: (B, S, d)."""
    a = attn.attention(
        params["attn"], rms_norm(h, params["attn_norm"]), positions,
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        causal=True, qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
        q_chunk=cfg.q_chunk, unroll=cfg.unroll_layers,
        scores_dtype=jnp.dtype(cfg.scores_dtype),
    )
    h = h + a
    hn = rms_norm(h, params["mlp_norm"])
    if cfg.n_experts:
        moe_fn = (moe_mod.moe_apply_grouped if cfg.moe_local_dispatch
                  else moe_mod.moe_apply)
        m, aux = moe_fn(
            params["moe"], hn, n_experts=cfg.n_experts, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor,
        )
    else:
        m, aux = mlp_apply(params["mlp"], hn, cfg.mlp_kind), 0.0
    return h + m, aux


def forward(cfg: ArchConfig, params, tokens, *, extra_embeds=None):
    """tokens (B, S) -> (hidden (B, S, d), aux).  ``extra_embeds``
    (B, P, d) is prepended (VLM patches / audio frames)."""
    dt = jnp.dtype(cfg.compute_dtype)
    emb = params["embedding"].astype(dt)
    h = emb[tokens]
    if extra_embeds is not None:
        h = jnp.concatenate([extra_embeds.astype(dt), h], axis=1)
    B, S, _ = h.shape
    h = constrain(h, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(carry, layer_params):
        h, aux = carry
        h, a = block_apply(cfg, layer_params, h, positions)
        return (h, aux + a), None

    from repro.models.remat import resolve_policy, wrap_layer_body
    body_fn = wrap_layer_body(body, resolve_policy(cfg))
    from repro.models.loops import scan_or_unroll
    (h, aux), _ = scan_or_unroll(body_fn, (h, jnp.zeros((), jnp.float32)),
                                 params["layers"], unroll=cfg.unroll_layers)
    h = rms_norm(h, params["final_norm"])
    return h, aux


def lm_loss(cfg: ArchConfig, params, batch):
    """batch: {"tokens": (B,S), "labels": (B,S)} (+ optional "frames" /
    "patches" (B,P,d) prepended; loss is over the text positions only)."""
    extra = batch.get("frames", batch.get("patches"))
    h, aux = forward(cfg, params, batch["tokens"], extra_embeds=extra)
    if extra is not None:
        h = h[:, extra.shape[1]:]
    loss = chunked_cross_entropy(
        h, params, batch["labels"],
        chunk=min(cfg.loss_chunk, batch["labels"].shape[1]),
        compute_dtype=jnp.dtype(cfg.compute_dtype),
        unroll=cfg.unroll_layers,
    )
    return loss + 0.01 * aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def cache_spec(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> dict:
    per = attn.kv_cache_spec(batch, max_seq, cfg.n_kv_heads, cfg.head_dim,
                             dtype)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cfg.n_layers,) + s.shape, s.dtype),
        per,
    )


def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> dict:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec(cfg, batch, max_seq, dtype))


def _decode_layers(cfg: ArchConfig, params, kv_leaves, tokens, attn_body,
                   last=None, all_rows=False, in_place=False):
    """Shared decode skeleton: embed -> scan layers -> final norm ->
    logits.  ``attn_body`` is the pluggable decode-attention hook applied
    per layer — dense attention on a per-slot cache view
    (:func:`decode_step`), or the paged Pallas kernel on the raw block
    pool (:func:`paged_decode_step`); ``kv_leaves`` is the TUPLE of
    matching stacked-over-layers cache leaves it consumes and rewrites —
    (k, v) for bf16 pools, (k, v, k_scale, v_scale) for narrow pools —
    and the same-arity tuple of new leaves comes back out.

    By default the leaves are scanned as ``xs``/``ys``: ``attn_body``
    gets layer l's slice of each and returns its new slice.  The paged
    steps pass ``in_place=True``: the stacked leaves ride in the scan's
    CARRY and ``attn_body(layer_params, hn, l, *stacked)`` gets the layer
    index, writes its tokens at ``[l, ...]`` and hands the kernel the
    whole stack.  A Pallas kernel is a custom call: as ``xs``, each
    layer's pool would be copied out as its operand, and the ``ys``
    restacked into a fresh pool that the donated input cannot alias.
    The carried pool is updated in place.

    ``tokens`` may carry C >= 1 positions per row (chunked prefill).
    ``last`` (B,) selects the logits row per slot — the chunk's final
    REAL prompt token, so a padded final chunk still emits the right
    first token; ``None`` keeps the decode path's row 0 untouched.
    ``all_rows`` returns logits at EVERY row (B, C, vocab_padded) for
    speculative verify — projected one row at a time so each (B, d) @
    (d, vocab) matmul is the exact shape the decode path runs (same
    reduction, bit-identical logits per row)."""
    dt = jnp.dtype(cfg.compute_dtype)
    h = params["embedding"].astype(dt)[tokens]           # (B, C, d)

    def layer(h, layer_params, *kvs):
        a, new_kvs = attn_body(layer_params,
                               rms_norm(h, layer_params["attn_norm"]), *kvs)
        h = h + a
        hn = rms_norm(h, layer_params["mlp_norm"])
        if cfg.n_experts:
            m, _ = moe_mod.moe_apply(
                layer_params["moe"], hn, n_experts=cfg.n_experts,
                top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
            )
        else:
            m = mlp_apply(layer_params["mlp"], hn, cfg.mlp_kind)
        return h + m, tuple(new_kvs)

    from repro.models.loops import scan_or_unroll
    if in_place:
        layer_ids = jnp.arange(kv_leaves[0].shape[0], dtype=jnp.int32)
        (h, new_leaves), _ = scan_or_unroll(
            lambda carry, xs: (layer(carry[0], xs[0], xs[1], *carry[1]),
                               None),
            (h, tuple(kv_leaves)), (params["layers"], layer_ids),
            unroll=cfg.unroll_layers)
    else:
        h, new_leaves = scan_or_unroll(
            lambda h, xs: layer(h, *xs), h,
            (params["layers"],) + tuple(kv_leaves), unroll=cfg.unroll_layers)
    h = rms_norm(h, params["final_norm"])
    if all_rows:
        w = params["lm_head"].astype(dt)
        logits = jnp.stack(
            [(h[:, j] @ w).astype(jnp.float32) for j in range(h.shape[1])],
            axis=1)
        return logits, new_leaves
    hl = h[:, 0] if last is None else jnp.take_along_axis(
        h, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = (hl @ params["lm_head"].astype(dt)).astype(jnp.float32)
    return logits, new_leaves


def decode_step(cfg: ArchConfig, params, cache, tokens, positions):
    """One decode step. tokens (B, 1) int32; positions (B,) int32.
    Returns (logits (B, vocab_padded), new_cache)."""

    def attn_body(layer_params, hn, ck, cv):
        a, nc = attn.decode_attention(
            layer_params["attn"], hn, {"k": ck, "v": cv}, positions,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
        )
        return a, (nc["k"], nc["v"])

    logits, (nk, nv) = _decode_layers(
        cfg, params, (cache["k"], cache["v"]), tokens, attn_body)
    return logits, {"k": nk, "v": nv}


def _paged_leaves(pool, scales):
    """The kv-leaf tuple a paged step carries through its layer scan:
    (k, v) plus, for narrow pools, the (L, R, 1, KV, 1) scale leaves."""
    if scales is None:
        return (pool["k"], pool["v"])
    return (pool["k"], pool["v"], scales["k"], scales["v"])


def _paged_result(logits, new_leaves, scales):
    if scales is None:
        nk, nv = new_leaves
        return logits, {"k": nk, "v": nv}
    nk, nv, nsk, nsv = new_leaves
    return logits, {"k": nk, "v": nv}, {"k": nsk, "v": nsv}


def paged_decode_step(cfg: ArchConfig, params, pool, tables, tokens,
                      positions, scales=None, kv_dtype: str = "bf16"):
    """Gather-free paged decode step (the serving O6 kernel path).

    Identical layer structure to :func:`decode_step`, but each layer's
    attention consumes the layer-stacked block-pool leaves
    (L, R, T, KV, dh), carried through the scan and written in place at
    its own layer, plus the
    per-slot block tables via ``attn.paged_decode_attention`` — the
    dense (B, max_seq, ...) view is never materialized; the current
    token's K/V is appended into the active block in place and the
    Pallas kernel streams only the blocks each slot's table references.

    Narrow pools (``scales`` given) re-quantize the slot's active block
    around the append and return the scales as a third result:
    (logits, pool, scales).
    """

    def attn_body(layer_params, hn, layer, *kvs):
        return attn.paged_decode_attention(
            layer_params["attn"], hn, kvs, layer, tables, positions,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
            kv_dtype=kv_dtype,
        )

    logits, new_leaves = _decode_layers(
        cfg, params, _paged_leaves(pool, scales), tokens, attn_body,
        in_place=True)
    return _paged_result(logits, new_leaves, scales)


def prefill_step(cfg: ArchConfig, params, cache, tokens, start, last):
    """One prompt-chunk step against the dense cache: tokens (B, C)
    int32 — C consecutive prompt tokens per slot starting at cache
    position ``start`` (B,); ``last`` (B,) is the row index of the
    chunk's final real token.  Returns (logits (B, vocab_padded) for the
    ``last`` rows, new_cache).  The padded tail of a final chunk rides
    along with clipped positions — its K/V writes land at future
    positions that are rewritten before first read, its logits rows are
    never selected.  Not valid for MoE configs (expert capacity is
    token-count-dependent); the ModelAPI wiring gates that."""
    C = tokens.shape[1]
    max_seq = cache["k"].shape[2]
    positions = jnp.clip(start[:, None] + jnp.arange(C)[None], 0,
                         max_seq - 1).astype(jnp.int32)

    def attn_body(layer_params, hn, ck, cv):
        a, nc = attn.chunk_prefill_attention(
            layer_params["attn"], hn, {"k": ck, "v": cv}, positions,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
        )
        return a, (nc["k"], nc["v"])

    logits, (nk, nv) = _decode_layers(
        cfg, params, (cache["k"], cache["v"]), tokens, attn_body, last=last)
    return logits, {"k": nk, "v": nv}


def paged_prefill_step(cfg: ArchConfig, params, pool, tables, tokens,
                       start, last, scales=None, kv_dtype: str = "bf16"):
    """Prompt-chunk step straight off the paged block pool: the chunk's
    K/V is scattered into pool blocks through the slot's table and the
    multi-query Pallas kernel attends the whole prefix — the dense view
    is never materialized.  Same signature discipline as
    :func:`prefill_step` plus the tables (and, for narrow pools, the
    scales: returns (logits, pool, scales))."""
    C = tokens.shape[1]
    T = pool["k"].shape[2]
    nb = tables.shape[1]
    positions = jnp.clip(start[:, None] + jnp.arange(C)[None], 0,
                         nb * T - 1).astype(jnp.int32)
    lengths = (start + C).astype(jnp.int32)      # unclipped: exact row masks

    def attn_body(layer_params, hn, layer, *kvs):
        return attn.paged_chunk_prefill_attention(
            layer_params["attn"], hn, kvs, layer, tables,
            positions, lengths,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
            kv_dtype=kv_dtype, start=start,
        )

    logits, new_leaves = _decode_layers(
        cfg, params, _paged_leaves(pool, scales), tokens, attn_body,
        last=last, in_place=True)
    return _paged_result(logits, new_leaves, scales)


def verify_step(cfg: ArchConfig, params, cache, tokens, start):
    """Speculative-verify step against the dense cache: tokens (B, C) —
    the pending token plus C-1 drafted tokens per slot, written at cache
    positions ``start`` .. ``start + C - 1``.  Returns (logits
    (B, C, vocab_padded) at EVERY row, new_cache): row j is the target's
    distribution after token j, so greedy rejection accepts the longest
    prefix where draft j+1 == argmax(row j).  Attention math is the
    chunked-prefill path (row arithmetic bit-identical to single-token
    decode); rejected rows' K/V writes land beyond the slot's frontier
    and are rewritten before first unmasked read — rollback is free.
    Not valid for MoE configs; the ModelAPI wiring gates that."""
    C = tokens.shape[1]
    max_seq = cache["k"].shape[2]
    positions = jnp.clip(start[:, None] + jnp.arange(C)[None], 0,
                         max_seq - 1).astype(jnp.int32)

    def attn_body(layer_params, hn, ck, cv):
        a, nc = attn.chunk_prefill_attention(
            layer_params["attn"], hn, {"k": ck, "v": cv}, positions,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
        )
        return a, (nc["k"], nc["v"])

    logits, (nk, nv) = _decode_layers(
        cfg, params, (cache["k"], cache["v"]), tokens, attn_body,
        all_rows=True)
    return logits, {"k": nk, "v": nv}


def paged_verify_step(cfg: ArchConfig, params, pool, tables, tokens, start,
                      scales=None, kv_dtype: str = "bf16"):
    """Speculative-verify step straight off the paged block pool: the
    window's K/V is scattered into pool blocks through the slot's table
    (writes past the reservation are absorbed by the NULL block) and the
    multi-query Pallas kernel attends the whole prefix.  Same all-rows
    logits contract as :func:`verify_step`; rejected drafts roll back by
    slot-length truncation — the table rows never change, so blocks
    never leak.  Narrow pools return (logits, pool, scales)."""
    C = tokens.shape[1]
    T = pool["k"].shape[2]
    nb = tables.shape[1]
    positions = jnp.clip(start[:, None] + jnp.arange(C)[None], 0,
                         nb * T - 1).astype(jnp.int32)
    lengths = (start + C).astype(jnp.int32)      # unclipped: exact row masks

    def attn_body(layer_params, hn, layer, *kvs):
        return attn.paged_chunk_prefill_attention(
            layer_params["attn"], hn, kvs, layer, tables,
            positions, lengths,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
            kv_dtype=kv_dtype, start=start,
        )

    logits, new_leaves = _decode_layers(
        cfg, params, _paged_leaves(pool, scales), tokens, attn_body,
        all_rows=True, in_place=True)
    return _paged_result(logits, new_leaves, scales)


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------

def cache_axes(cfg: ArchConfig) -> dict:
    ax = ("layers", "batch", "kv_seq", "kv", None)
    return {"k": ax, "v": ax}


def init(cfg: ArchConfig, rng) -> dict:
    return init_params(rng, model_defs(cfg), jnp.dtype(cfg.param_dtype))


def axes(cfg: ArchConfig):
    return param_axes(model_defs(cfg))
