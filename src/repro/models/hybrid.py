"""Zamba2-style hybrid: Mamba-2 trunk + one *shared* attention block.

54 mamba layers in 9 groups of 6; after each group the shared block
(attention + MLP, weights reused across all 9 applications) runs on
``concat(hidden, embedding_output)`` projected down by a per-application
(unshared) linear — the Zamba2 weight-sharing scheme.  The shared block uses
full attention, so this arch is the hybrid long-context cell: its KV caches
exist only at the 9 application points (O(S) memory, sub-quadratic overall
compute share).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import mamba2
from repro.models.layers import (
    PDef, chunked_cross_entropy, init_params, mlp_apply, mlp_defs,
    param_axes, rms_norm, rms_norm_defs, stack_defs,
)
from repro.models.transformer import padded_vocab
from repro.parallel.sharding import constrain


def _n_apps(cfg: ArchConfig) -> int:
    return cfg.n_layers // cfg.attn_every


def _mamba_kw(cfg: ArchConfig) -> dict:
    return dict(expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
                state=cfg.ssm_state, conv_width=cfg.conv_width)


def model_defs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    vp = padded_vocab(cfg.vocab)
    na = _n_apps(cfg)
    shared = {
        "attn_norm": rms_norm_defs(d),
        "attn": attn.attn_defs(d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim),
        "mlp_norm": rms_norm_defs(d),
        "mlp": mlp_defs(d, cfg.d_ff, cfg.mlp_kind),
    }
    return {
        "embedding": PDef((vp, d), ("vocab", "embed"), "small"),
        "lm_head": PDef((d, vp), ("embed", "vocab")),
        "final_norm": rms_norm_defs(d),
        "mamba": stack_defs(mamba2.mamba2_defs(d, **_mamba_kw(cfg)),
                            cfg.n_layers),
        "shared": shared,
        "app_proj": PDef((na, 2 * d, d), ("layers", "embed", None), "small"),
    }


def _shared_block(cfg, shared, proj, h, emb0, positions):
    dt = h.dtype
    x = jnp.concatenate([h, emb0], axis=-1) @ proj.astype(dt)
    a = attn.attention(
        shared["attn"], rms_norm(x, shared["attn_norm"]), positions,
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        causal=True, rope_theta=cfg.rope_theta, q_chunk=cfg.q_chunk,
        scores_dtype=jnp.dtype(cfg.scores_dtype),
        unroll=cfg.unroll_layers,
    )
    x = x + a
    m = mlp_apply(shared["mlp"], rms_norm(x, shared["mlp_norm"]),
                  cfg.mlp_kind)
    return h + (x + m)


def _regroup(tree, na, per):
    return jax.tree.map(lambda x: x.reshape((na, per) + x.shape[1:]), tree)


def forward(cfg: ArchConfig, params, tokens):
    dt = jnp.dtype(cfg.compute_dtype)
    h = params["embedding"].astype(dt)[tokens]
    emb0 = h
    B, S, _ = h.shape
    h = constrain(h, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    na, per = _n_apps(cfg), cfg.attn_every
    mkw = _mamba_kw(cfg)

    grouped = _regroup(params["mamba"], na, per)

    from repro.models.loops import scan_or_unroll

    def inner(h, layer_params):
        out = mamba2.mamba2_apply(layer_params, h,
                                  unroll=cfg.unroll_layers, **mkw)
        return h + out, None

    from repro.models.remat import resolve_policy, wrap_layer_body
    inner_fn = wrap_layer_body(inner, resolve_policy(cfg))

    def group(h, xs):
        layer_group, proj = xs
        h, _ = scan_or_unroll(inner_fn, h, layer_group,
                              unroll=cfg.unroll_layers)
        h = _shared_block(cfg, params["shared"], proj, h, emb0, positions)
        return h, None

    h, _ = scan_or_unroll(group, h, (grouped, params["app_proj"]),
                          unroll=cfg.unroll_layers)
    return rms_norm(h, params["final_norm"])


def lm_loss(cfg: ArchConfig, params, batch):
    h = forward(cfg, params, batch["tokens"])
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
               dtype=jnp.bfloat16) -> dict:
    na = _n_apps(cfg)
    m = mamba2.mamba2_state_spec(batch, cfg.d_model, dtype=dtype,
                                 **_mamba_kw(cfg))
    stack = lambda s, n: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype)
    kv = attn.kv_cache_spec(batch, max_seq, cfg.n_kv_heads, cfg.head_dim,
                            dtype)
    return {
        "mamba": jax.tree.map(lambda s: stack(s, cfg.n_layers), m),
        "shared_kv": jax.tree.map(lambda s: stack(s, na), kv),
    }


def init_cache(cfg, batch, max_seq, dtype=jnp.bfloat16):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec(cfg, batch, max_seq, dtype))


def decode_step(cfg: ArchConfig, params, cache, tokens, positions):
    dt = jnp.dtype(cfg.compute_dtype)
    h = params["embedding"].astype(dt)[tokens]            # (B,1,d)
    emb0 = h[:, 0]
    na, per = _n_apps(cfg), cfg.attn_every
    mkw = _mamba_kw(cfg)

    grouped = _regroup(params["mamba"], na, per)
    mcache = _regroup(cache["mamba"], na, per)

    def inner(h, xs):
        layer_params, st = xs
        out, new_st = mamba2.mamba2_decode(layer_params, h, st, **mkw)
        return h + out, new_st

    def group(carry, xs):
        h = carry
        layer_group, st_group, proj, kv = xs
        h, new_states = scan_or_unroll(inner, h, (layer_group, st_group),
                                       unroll=cfg.unroll_layers)
        x = jnp.concatenate([h, emb0[:, None]], axis=-1) @ proj.astype(dt)
        a, new_kv = attn.decode_attention(
            params["shared"]["attn"],
            rms_norm(x, params["shared"]["attn_norm"]), kv, positions,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta,
        )
        x = x + a
        m = mlp_apply(params["shared"]["mlp"],
                      rms_norm(x, params["shared"]["mlp_norm"]),
                      cfg.mlp_kind)
        h = h + (x + m)
        return h, (new_states, new_kv)

    from repro.models.loops import scan_or_unroll
    kvc = {"k": cache["shared_kv"]["k"], "v": cache["shared_kv"]["v"]}
    h, (new_m, new_kv) = scan_or_unroll(
        group, h, (grouped, mcache, params["app_proj"], kvc),
        unroll=cfg.unroll_layers)
    h = rms_norm(h, params["final_norm"])
    logits = (h[:, 0] @ params["lm_head"].astype(dt)).astype(jnp.float32)
    flat_m = jax.tree.map(
        lambda x: x.reshape((na * per,) + x.shape[2:]), new_m)
    return logits, {"mamba": flat_m, "shared_kv": new_kv}


def cache_axes(cfg: ArchConfig) -> dict:
    kv = ("layers", "batch", "kv_seq", "kv", None)
    return {
        "mamba": {"conv": ("layers", "batch", None, "mlp"),
                  "ssm": ("layers", "batch", "heads", None, None)},
        "shared_kv": {"k": kv, "v": kv},
    }


def paged_decode_step(cfg: ArchConfig, params, pool, tables, rows, tokens,
                      positions, scales=None, kv_dtype: str = "bf16"):
    """MIXED-pool decode step (serving O6): the shared attention block
    reads/appends through per-slot block ``tables`` via the paged Pallas
    kernel (gather-free, like the transformer path), while the mamba
    trunk's carried state moves through ``rows`` — slot->state-row
    indirection into the conv/ssm row pools, gathered to the dense batch
    view around the exact contiguous layer bodies and scattered back.
    Narrow pools quantize only the shared_kv block leaves; the mamba
    scale placeholders pass through untouched (state is never
    quantized).  Returns (logits, pool[, scales])."""
    dt = jnp.dtype(cfg.compute_dtype)
    h = params["embedding"].astype(dt)[tokens]            # (B,1,d)
    emb0 = h[:, 0]
    na, per = _n_apps(cfg), cfg.attn_every
    mkw = _mamba_kw(cfg)

    grouped = _regroup(params["mamba"], na, per)
    mstate = jax.tree.map(lambda l: jnp.take(l, rows, axis=1),
                          pool["mamba"])
    mcache = _regroup(mstate, na, per)

    kv_leaves = (pool["shared_kv"]["k"], pool["shared_kv"]["v"])
    if scales is not None:
        kv_leaves += (scales["shared_kv"]["k"], scales["shared_kv"]["v"])

    from repro.models.loops import scan_or_unroll

    def inner(h, xs):
        layer_params, st = xs
        out, new_st = mamba2.mamba2_decode(layer_params, h, st, **mkw)
        return h + out, new_st

    def group(carry, xs):
        h, kvs = carry
        layer_group, st_group, proj, app = xs
        h, new_states = scan_or_unroll(inner, h, (layer_group, st_group),
                                       unroll=cfg.unroll_layers)
        x = jnp.concatenate([h, emb0[:, None]], axis=-1) @ proj.astype(dt)
        a, new_kvs = attn.paged_decode_attention(
            params["shared"]["attn"],
            rms_norm(x, params["shared"]["attn_norm"]), kvs, app, tables,
            positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            kv_dtype=kv_dtype,
        )
        x = x + a
        m = mlp_apply(params["shared"]["mlp"],
                      rms_norm(x, params["shared"]["mlp_norm"]),
                      cfg.mlp_kind)
        h = h + (x + m)
        return (h, tuple(new_kvs)), new_states

    # The stacked shared_kv leaves ride in the carry, written in place at
    # each application's index (see ``transformer._decode_layers``).
    (h, new_kvs), new_m = scan_or_unroll(
        group, (h, kv_leaves),
        (grouped, mcache, params["app_proj"],
         jnp.arange(na, dtype=jnp.int32)),
        unroll=cfg.unroll_layers)
    h = rms_norm(h, params["final_norm"])
    logits = (h[:, 0] @ params["lm_head"].astype(dt)).astype(jnp.float32)
    flat_m = jax.tree.map(
        lambda x: x.reshape((na * per,) + x.shape[2:]), new_m)
    new_mpool = jax.tree.map(
        lambda p, n: p.at[:, rows].set(n.astype(p.dtype)),
        pool["mamba"], flat_m)
    if scales is None:
        nk, nv = new_kvs
        return logits, {"mamba": new_mpool,
                        "shared_kv": {"k": nk, "v": nv}}
    nk, nv, nsk, nsv = new_kvs
    return (logits,
            {"mamba": new_mpool, "shared_kv": {"k": nk, "v": nv}},
            {"mamba": scales["mamba"],
             "shared_kv": {"k": nsk, "v": nsv}})


def prefill_step(cfg: ArchConfig, params, cache, tokens, start, last):
    """Chunked prefill by scanning the decode body (see
    :mod:`repro.models.scan_prefill`): the mamba trunk's carry freezes
    per-slot past ``last``; shared_kv writes at clipped positions are
    frozen out the same way."""
    from repro.models.scan_prefill import batch_axes_of, scan_prefill

    def step(c, tok, pos):
        return decode_step(cfg, params, c, tok, pos)

    return scan_prefill(step, cache, tokens, start, last,
                        logits_width=padded_vocab(cfg.vocab),
                        batch_axes=batch_axes_of(cache_axes(cfg)),
                        max_seq=cache["shared_kv"]["k"].shape[2])


def init(cfg: ArchConfig, rng):
    return init_params(rng, model_defs(cfg), jnp.dtype(cfg.param_dtype))


def axes(cfg: ArchConfig):
    return param_axes(model_defs(cfg))
