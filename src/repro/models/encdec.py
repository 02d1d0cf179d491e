"""Whisper-style encoder-decoder backbone (conv frontend STUBBED).

Per the assignment, the modality frontend is a stub: ``input_specs()``
supplies precomputed frame embeddings (B, S, d_model).  The backbone is a
bidirectional encoder + causal decoder with cross-attention.  Decode caches
both the decoder self-attn KV and the (precomputed-once) cross-attn KV.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models.layers import (
    PDef, chunked_cross_entropy, init_params, mlp_apply, mlp_defs,
    param_axes, rms_norm, rms_norm_defs, stack_defs,
)
from repro.models.transformer import padded_vocab
from repro.parallel.sharding import constrain


def _enc_block_defs(cfg):
    d = cfg.d_model
    return {
        "attn_norm": rms_norm_defs(d),
        "attn": attn.attn_defs(d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim),
        "mlp_norm": rms_norm_defs(d),
        "mlp": mlp_defs(d, cfg.d_ff, cfg.mlp_kind),
    }


def _dec_block_defs(cfg):
    d = cfg.d_model
    defs = _enc_block_defs(cfg)
    defs["cross_norm"] = rms_norm_defs(d)
    defs["cross"] = attn.attn_defs(d, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.head_dim)
    return defs


def model_defs(cfg: ArchConfig) -> dict:
    vp = padded_vocab(cfg.vocab)
    return {
        "embedding": PDef((vp, cfg.d_model), ("vocab", "embed"), "small"),
        "lm_head": PDef((cfg.d_model, vp), ("embed", "vocab")),
        "enc_norm": rms_norm_defs(cfg.d_model),
        "final_norm": rms_norm_defs(cfg.d_model),
        "encoder": stack_defs(_enc_block_defs(cfg), cfg.n_enc_layers),
        "decoder": stack_defs(_dec_block_defs(cfg), cfg.n_layers),
    }


def encode(cfg: ArchConfig, params, frames):
    """frames: (B, S_enc, d) precomputed embeddings -> encoder states."""
    dt = jnp.dtype(cfg.compute_dtype)
    h = frames.astype(dt)
    h = constrain(h, "batch", None, None)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(h, layer_params):
        a = attn.attention(
            layer_params["attn"], rms_norm(h, layer_params["attn_norm"]),
            positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim, causal=False,
            rope_theta=cfg.rope_theta, q_chunk=cfg.q_chunk,
            unroll=cfg.unroll_layers,
        )
        h = h + a
        m = mlp_apply(layer_params["mlp"],
                      rms_norm(h, layer_params["mlp_norm"]), cfg.mlp_kind)
        return h + m, None

    from repro.models.remat import resolve_policy, wrap_layer_body
    body_fn = wrap_layer_body(body, resolve_policy(cfg))
    from repro.models.loops import scan_or_unroll
    h, _ = scan_or_unroll(body_fn, h, params["encoder"],
                          unroll=cfg.unroll_layers)
    return rms_norm(h, params["enc_norm"])


def decode_full(cfg: ArchConfig, params, tokens, enc_h):
    """Teacher-forced decoder pass. tokens: (B, S_dec)."""
    dt = jnp.dtype(cfg.compute_dtype)
    h = params["embedding"].astype(dt)[tokens]
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    Se = enc_h.shape[1]
    enc_pos = jnp.broadcast_to(jnp.arange(Se)[None], (B, Se))

    def body(h, layer_params):
        a = attn.attention(
            layer_params["attn"], rms_norm(h, layer_params["attn_norm"]),
            positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim, causal=True,
            rope_theta=cfg.rope_theta, q_chunk=cfg.q_chunk,
            unroll=cfg.unroll_layers,
        )
        h = h + a
        c = attn.attention(
            layer_params["cross"], rms_norm(h, layer_params["cross_norm"]),
            positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim, causal=False,
            rope_theta=cfg.rope_theta, q_chunk=cfg.q_chunk,
            kv_x=enc_h, kv_positions=enc_pos,
            unroll=cfg.unroll_layers,
        )
        h = h + c
        m = mlp_apply(layer_params["mlp"],
                      rms_norm(h, layer_params["mlp_norm"]), cfg.mlp_kind)
        return h + m, None

    from repro.models.remat import resolve_policy, wrap_layer_body
    body_fn = wrap_layer_body(body, resolve_policy(cfg))
    from repro.models.loops import scan_or_unroll
    h, _ = scan_or_unroll(body_fn, h, params["decoder"],
                          unroll=cfg.unroll_layers)
    return rms_norm(h, params["final_norm"])


def lm_loss(cfg: ArchConfig, params, batch):
    """batch: {"frames": (B,S,d), "tokens": (B,S), "labels": (B,S)}."""
    enc_h = encode(cfg, params, batch["frames"])
    h = decode_full(cfg, params, batch["tokens"], enc_h)
    return chunked_cross_entropy(
        h, params, batch["labels"],
        chunk=min(cfg.loss_chunk, batch["labels"].shape[1]),
        compute_dtype=jnp.dtype(cfg.compute_dtype),
        unroll=cfg.unroll_layers,
    )


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def cache_spec(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16, enc_len: int = None) -> dict:
    enc_len = enc_len or max_seq
    L = cfg.n_layers
    kv = attn.kv_cache_spec(batch, max_seq, cfg.n_kv_heads, cfg.head_dim,
                            dtype)
    cross = attn.kv_cache_spec(batch, enc_len, cfg.n_kv_heads, cfg.head_dim,
                               dtype)
    stack = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((L,) + s.shape, s.dtype), t)
    return {"self_kv": stack(kv), "cross_kv": stack(cross)}


def init_cache(cfg, batch, max_seq, dtype=jnp.bfloat16, enc_len=None):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec(cfg, batch, max_seq, dtype, enc_len))


def build_cross_cache(cfg: ArchConfig, params, enc_h):
    """Precompute per-layer cross-attention K/V from encoder states."""
    dt = enc_h.dtype

    def per_layer(layer_params):
        k = jnp.einsum("bsd,dhk->bshk", enc_h,
                       layer_params["cross"]["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", enc_h,
                       layer_params["cross"]["wv"].astype(dt))
        return {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}

    return jax.lax.map(per_layer, params["decoder"])


def decode_step(cfg: ArchConfig, params, cache, tokens, positions):
    dt = jnp.dtype(cfg.compute_dtype)
    h = params["embedding"].astype(dt)[tokens]

    def body(h, xs):
        layer_params, sk, sv, ck, cv = xs
        a, new_self = attn.decode_attention(
            layer_params["attn"], rms_norm(h, layer_params["attn_norm"]),
            {"k": sk, "v": sv}, positions,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta,
        )
        h = h + a
        c, _ = attn.decode_attention(
            layer_params["cross"], rms_norm(h, layer_params["cross_norm"]),
            {"k": ck, "v": cv}, positions,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, cross=True,
        )
        h = h + c
        m = mlp_apply(layer_params["mlp"],
                      rms_norm(h, layer_params["mlp_norm"]), cfg.mlp_kind)
        return h + m, (new_self["k"], new_self["v"])

    from repro.models.loops import scan_or_unroll
    h, (nk, nv) = scan_or_unroll(
        body, h,
        (params["decoder"], cache["self_kv"]["k"], cache["self_kv"]["v"],
         cache["cross_kv"]["k"], cache["cross_kv"]["v"]),
        unroll=cfg.unroll_layers)
    h = rms_norm(h, params["final_norm"])
    logits = (h[:, 0] @ params["lm_head"].astype(dt)).astype(jnp.float32)
    return logits, {"self_kv": {"k": nk, "v": nv},
                    "cross_kv": cache["cross_kv"]}


def cache_axes(cfg: ArchConfig) -> dict:
    kv = ("layers", "batch", "kv_seq", "kv", None)
    return {"self_kv": {"k": kv, "v": kv},
            "cross_kv": {"k": kv, "v": kv}}


def paged_decode_step(cfg: ArchConfig, params, pool, tables, rows, tokens,
                      positions, scales=None, kv_dtype: str = "bf16"):
    """MIXED-pool decode step (serving O6): decoder self-attention runs
    gather-free through per-slot block ``tables`` via the paged Pallas
    kernel, while the per-slot cross-attention KV (a fixed-size blob,
    not a growing sequence) lives in a state-row pool addressed by
    ``rows`` — gathered to the dense batch view for the plain cross
    attention and returned UNCHANGED (cross KV is written once at
    insert, read-only thereafter).  Narrow pools quantize only the
    self_kv block leaves; cross state stays bf16.  Returns
    (logits, pool[, scales])."""
    dt = jnp.dtype(cfg.compute_dtype)
    h = params["embedding"].astype(dt)[tokens]
    cross = jax.tree.map(lambda l: jnp.take(l, rows, axis=1),
                         pool["cross_kv"])
    kv_leaves = (pool["self_kv"]["k"], pool["self_kv"]["v"])
    if scales is not None:
        kv_leaves += (scales["self_kv"]["k"], scales["self_kv"]["v"])

    def body(carry, xs):
        h, kvs = carry
        layer_params, ck, cv, layer = xs
        a, new_kvs = attn.paged_decode_attention(
            layer_params["attn"], rms_norm(h, layer_params["attn_norm"]),
            kvs, layer, tables, positions,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, kv_dtype=kv_dtype,
        )
        h = h + a
        c, _ = attn.decode_attention(
            layer_params["cross"], rms_norm(h, layer_params["cross_norm"]),
            {"k": ck, "v": cv}, positions,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, cross=True,
        )
        h = h + c
        m = mlp_apply(layer_params["mlp"],
                      rms_norm(h, layer_params["mlp_norm"]), cfg.mlp_kind)
        return (h + m, tuple(new_kvs)), None

    # The stacked self_kv leaves ride in the carry, written in place at
    # each layer's index (see ``transformer._decode_layers``).
    from repro.models.loops import scan_or_unroll
    (h, new_kvs), _ = scan_or_unroll(
        body, (h, kv_leaves),
        (params["decoder"], cross["k"], cross["v"],
         jnp.arange(kv_leaves[0].shape[0], dtype=jnp.int32)),
        unroll=cfg.unroll_layers)
    h = rms_norm(h, params["final_norm"])
    logits = (h[:, 0] @ params["lm_head"].astype(dt)).astype(jnp.float32)
    if scales is None:
        nk, nv = new_kvs
        return logits, {"self_kv": {"k": nk, "v": nv},
                        "cross_kv": pool["cross_kv"]}
    nk, nv, nsk, nsv = new_kvs
    return (logits,
            {"self_kv": {"k": nk, "v": nv}, "cross_kv": pool["cross_kv"]},
            {"self_kv": {"k": nsk, "v": nsv},
             "cross_kv": scales["cross_kv"]})


def prefill_step(cfg: ArchConfig, params, cache, tokens, start, last):
    """Chunked prefill by scanning the decode body (see
    :mod:`repro.models.scan_prefill`): self-KV writes freeze per-slot
    past ``last``; cross KV passes through unchanged."""
    from repro.models.scan_prefill import batch_axes_of, scan_prefill

    def step(c, tok, pos):
        return decode_step(cfg, params, c, tok, pos)

    return scan_prefill(step, cache, tokens, start, last,
                        logits_width=padded_vocab(cfg.vocab),
                        batch_axes=batch_axes_of(cache_axes(cfg)),
                        max_seq=cache["self_kv"]["k"].shape[2])


def init(cfg: ArchConfig, rng):
    return init_params(rng, model_defs(cfg), jnp.dtype(cfg.param_dtype))


def axes(cfg: ArchConfig):
    return param_axes(model_defs(cfg))
