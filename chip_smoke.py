"""Drive the served path once on a TPU and check what comes out.

    python chip_smoke.py [--seed N]      # one chip
    python chip_smoke.py --chips 4       # the four-chip placement only

One chip runs three phases in this one process, through JAX alone:

  kernel     the compiled paged decode and prefill kernels at qwen3-8b's
             attention widths, bf16 and int8 pools, against the
             pure-jnp oracle in ``kernels/paged_attention/ref.py``;
  engine     qwen3-8b at its published widths (depth cut, see
             ``SMOKE_LAYERS``), random bf16 weights from ``--seed``, an
             O6 ``DecodeEngine`` with the block-table kernel and chunked
             prefill, driven through ``launch/server.serve_trace`` with
             a handful of requests of a few hundred prompt tokens;
  reference  the same requests through the O5 contiguous engine (the
             dense XLA path) with the same weights; greedy tokens must
             be identical (``serving.kvquant.assert_tokens_match``).  A
             request that diverges passes only if replaying it through
             both paths shows logits that agree within the bf16 kernel
             tolerance at the diverging step (reduction order, not a
             wrong attention result).

``--chips 4`` runs only the multi-chip path: O6 with ``pe=4`` (the pool
sharded on its block axis, kernel step) against O6 with ``pe=1`` on the
same requests.  Tokens must be identical, or every divergence must be a
near-tie on the replayed kernel-path logits, as above.

Wall times printed here are smoke timings, not benchmark numbers.  The
last line of standard output is one JSON object naming the device; it is
printed only when every phase passed.  Without a TPU the script exits
nonzero before any phase runs.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent

# qwen3-8b keeps every published width; only depth is cut.  36 layers of
# bf16 weights are ~16 GB, more than one v5e chip holds.  8 layers plus
# the untied embedding and head are ~5.6 GB, which leaves room for the
# paged pool, the O5 reference's contiguous cache and both engines'
# compiled steps on the chip's 16 GB.
SMOKE_LAYERS = 8
BATCH = 8                 # decode slots
MAX_SEQ = 512             # per-request positions (prompt + completion)
KV_BLOCK = 16             # tokens per pool block
POOL_BLOCKS = 1024        # a 16K-token pool (~0.5 GB over 8 layers)
PREFILL_CHUNK = 128
N_REQUESTS = 8
PROMPT_LEN = (128, 385)   # [lo, hi) prompt tokens per request
MAX_NEW = (16, 49)        # [lo, hi) generated tokens per request

# The repo's bf16 tolerance for the paged kernel against its f32-softmax
# oracle (tests/test_kernels.py); also the bound a diverging request's
# replayed logits must meet.
BF16_RTOL, BF16_ATOL = 0.06, 0.03

def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------

def _paged_inputs(rng, *, B, Q, H, KV, D, T, rows, nb):
    """Random pool, queries, tables and lengths in the allocator's
    invariant: each slot's valid prefix maps to distinct real rows, the
    rest of its table to the NULL row 0."""
    import jax
    import jax.numpy as jnp

    if Q == 1:
        lengths = rng.integers(1, nb * T + 1, B)
    else:
        lengths = rng.integers(0, nb * T - Q + 1, B) + Q
    free = rng.permutation(np.arange(1, rows))
    tables = np.zeros((B, nb), np.int32)
    used = 0
    for b in range(B):
        n = -(-int(lengths[b]) // T)
        tables[b, :n] = free[used:used + n]
        used += n
    keys = jax.random.split(jax.random.PRNGKey(int(rng.integers(1 << 30))), 3)
    q_shape = (B, H, D) if Q == 1 else (B, Q, H, D)
    q = jax.random.normal(keys[0], q_shape, jnp.bfloat16)
    kp = jax.random.normal(keys[1], (rows, T, KV, D), jnp.bfloat16)
    vp = jax.random.normal(keys[2], (rows, T, KV, D), jnp.bfloat16)
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


def assert_compiled_kernel(mode: str, fn, *args) -> None:
    """The wrapper resolves to a compiled Mosaic kernel, not the
    interpreter: ``interpret=None`` must mean compiled on this backend,
    and the lowered program must hold a TPU custom call."""
    import jax

    from repro.kernels.backend import resolve_interpret

    if resolve_interpret():
        raise RuntimeError(f"{mode}: kernel wrappers resolve to interpret "
                           f"mode on backend {jax.default_backend()!r}")
    if "tpu_custom_call" not in jax.jit(fn).lower(*args).compile().as_text():
        raise RuntimeError(f"{mode}: no Mosaic kernel in the program")


def kernel_phase(cfg, seed: int, *, rows: int, nb: int, block: int,
                 decode_batch: int, prefill_len: int) -> None:
    """Compiled paged kernels against ``ref.py`` at ``cfg``'s widths."""
    from repro.kernels.paged_attention import ops, ref
    from repro.serving import kvquant

    rng = np.random.default_rng(seed)
    dims = dict(H=cfg.n_heads, KV=cfg.n_kv_heads, D=cfg.head_dim, T=block,
                rows=rows, nb=nb)
    cases = (("decode", decode_batch, 1, ops.paged_attention,
              ref.paged_attention_ref),
             ("prefill", 1, prefill_len, ops.paged_prefill_attention,
              ref.paged_prefill_attention_ref))
    for mode, B, Q, fn, ref_fn in cases:
        q, kp, vp, tables, lengths = _paged_inputs(rng, B=B, Q=Q, **dims)
        assert_compiled_kernel(mode, fn, q, kp, vp, tables, lengths)
        for kv_dtype in ("bf16", "int8"):
            kq, vq, scales = kp, vp, {}
            if kv_dtype != "bf16":
                ks = kvquant.block_scale(kp, (1, 3), kv_dtype)
                vs = kvquant.block_scale(vp, (1, 3), kv_dtype)
                kq = kvquant.quantize(kp, ks, kv_dtype)
                vq = kvquant.quantize(vp, vs, kv_dtype)
                scales = dict(k_scale=ks[:, 0, :, 0], v_scale=vs[:, 0, :, 0])
            out = np.asarray(fn(q, kq, vq, tables, lengths, **scales),
                             np.float32)
            want = np.asarray(ref_fn(q, kq, vq, tables, lengths,
                                     *scales.values()), np.float32)
            err = float(np.max(np.abs(out - want)))
            log(f"kernel {mode} {kv_dtype}: B={B} Q={Q} H={dims['H']} "
                f"KV={dims['KV']} D={dims['D']} T={block} pool_rows={rows} "
                f"max|out-ref|={err:.6f} "
                f"(tolerance rtol={BF16_RTOL} atol={BF16_ATOL})")
            np.testing.assert_allclose(out, want, rtol=BF16_RTOL,
                                       atol=BF16_ATOL,
                                       err_msg=f"kernel {mode} {kv_dtype}")


# ---------------------------------------------------------------------------
# engine phases
# ---------------------------------------------------------------------------

def smoke_config():
    """qwen3-8b at published widths, depth cut to ``SMOKE_LAYERS``, bf16
    weights."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("qwen3-8b"), n_layers=SMOKE_LAYERS,
                               param_dtype="bfloat16")


def make_requests(cfg, seed: int, *, n: int, prompt_len, max_new) -> list:
    from repro.launch.server import make_trace
    return make_trace(n_requests=n, rate=50.0, seed=seed, vocab=cfg.vocab,
                      prompt_len=prompt_len, max_new=max_new)


def build_engine(model, params, *, level: int, pe: int, paged_attn: str,
                 prefill_chunk: int, batch: int, max_seq: int, block: int,
                 pool_blocks: int):
    from repro.core.optlevel import BestEffortConfig, OptLevel
    from repro.serving import DecodeEngine
    return DecodeEngine(
        model, params, batch_size=batch, max_seq=max_seq,
        config=BestEffortConfig(
            level=OptLevel(level), pe=pe, kv_block_size=block,
            kv_pool_blocks=pool_blocks, paged_attn=paged_attn,
            prefill_chunk=prefill_chunk))


def serve(name: str, engine, trace) -> list:
    """Serve ``trace`` through the async front end; check every request
    finished with its full token count; return the token lists in
    submission order."""
    import jax
    from repro.launch.server import serve_trace
    from repro.serving.tracing import compile_seconds

    c0, t0 = compile_seconds(), time.monotonic()
    out = serve_trace(engine, trace, max_ticks=20_000)
    wall = time.monotonic() - t0
    finished = out["finished"]
    for item, r in zip(trace, finished):
        if r.truncated or len(r.generated) != item.max_new_tokens:
            raise RuntimeError(
                f"{name}: request {r.rid} finished with "
                f"{len(r.generated)}/{item.max_new_tokens} tokens "
                f"(truncated={r.truncated})")
    tokens = [list(map(int, r.generated)) for r in finished]
    log(f"{name}: layout={engine.layout.name} "
        f"attn_impl={getattr(engine.layout, 'attn_impl', None)} "
        f"prefill_mode={engine.prefill_mode} "
        f"devices={engine.placement.n_devices} "
        f"degrade_reason={engine.degrade_reason} "
        f"requests={len(finished)} "
        f"prompt_tokens={sum(len(i.prompt) for i in trace)} "
        f"generated_tokens={sum(map(len, tokens))} ticks={out['ticks']} "
        f"compile_s={compile_seconds() - c0:.1f} smoke_wall_s={wall:.1f} "
        f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}")
    return tokens


def _replay_logits(model, params, prompt, generated, *, paged: bool,
                   chunk: int, max_seq: int, block: int):
    """Batch-1 replay of one request through one attention path: the
    prompt in ``chunk``-token prefill steps, then one decode step per
    token of ``generated``.  Returns the f32 logits that pick the next
    token."""
    import jax
    import jax.numpy as jnp

    nb = max_seq // block
    tables = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    if paged:
        cache = jax.tree.map(
            lambda s: jnp.zeros((s.shape[0], nb + 1, block) + s.shape[3:],
                                s.dtype),
            model.cache_spec(1, max_seq))
        prefill = jax.jit(lambda p, c, *a: model.paged_prefill_step(
            p, c, tables, *a))
        decode = jax.jit(lambda p, c, *a: model.paged_decode_step(
            p, c, tables, *a))
    else:
        cache = model.init_cache(1, max_seq)
        prefill = jax.jit(model.prefill_step)
        decode = jax.jit(model.decode_step)
    logits = None
    for start in range(0, len(prompt), chunk):
        n = min(chunk, len(prompt) - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = prompt[start:start + n]
        logits, cache = prefill(params, cache, jnp.asarray(toks),
                                jnp.asarray([start], jnp.int32),
                                jnp.asarray([n - 1], jnp.int32))
    for k, tok in enumerate(generated):
        logits, cache = decode(params, cache,
                               jnp.asarray([[tok]], jnp.int32),
                               jnp.asarray([len(prompt) + k], jnp.int32))
    return np.asarray(logits[0], np.float32)


def explain_divergence(model, params, trace, ref_tokens, got_tokens, *,
                       labels, with_dense: bool, chunk: int, max_seq: int,
                       block: int) -> None:
    """For each request whose tokens differ: print the first diverging
    position and the logits there, replayed at batch 1 on the common
    prefix through the paged kernel path (and, ``with_dense``, the dense
    path too, which must agree within the bf16 tolerance).  Raise unless
    the two picks are a near-tie on the replayed reference logits:
    rounding noise can flip only a near-tie."""
    for item, ref, got in zip(trace, ref_tokens, got_tokens):
        if ref == got:
            continue
        pos = next((i for i, (a, b) in enumerate(zip(ref, got)) if a != b),
                   min(len(ref), len(got)))
        replay = functools.partial(_replay_logits, model, params, item.prompt,
                                   ref[:pos], chunk=chunk, max_seq=max_seq,
                                   block=block)
        base = kern = replay(paged=True)
        a, b = ref[pos], got[pos]
        line = (f"divergence: prompt_len={len(item.prompt)} first diverging "
                f"generated position {pos}: {labels[0]} picked {a}, "
                f"{labels[1]} picked {b}; kernel-path gap "
                f"l[{a}]-l[{b}]={kern[a] - kern[b]:.6f}")
        if with_dense:
            base = replay(paged=False)
            line += (f", dense-path gap {base[a] - base[b]:.6f}; replayed "
                     f"max|dlogit|={float(np.max(np.abs(base - kern))):.6f}")
        log(f"{line}; max|logit|={float(np.max(np.abs(base))):.4f}")
        if with_dense:
            np.testing.assert_allclose(
                kern, base, rtol=BF16_RTOL, atol=BF16_ATOL,
                err_msg=f"logits at diverging position {pos} disagree "
                        f"beyond the bf16 tolerance")
        tie = BF16_ATOL + BF16_RTOL * max(abs(base[a]), abs(base[b]))
        gap = float(base[a] - base[b])
        if abs(gap) > tie:
            raise AssertionError(
                f"position {pos}: tokens {a} and {b} are {gap:.6f} apart "
                f"on the replayed logits, beyond the near-tie bound "
                f"{tie:.6f}")


def one_chip(seed: int) -> None:
    import jax

    from repro.models import get_model
    from repro.serving import kvquant
    from repro.serving.tracing import compile_seconds

    cfg = smoke_config()
    c0, t0 = compile_seconds(), time.monotonic()
    kernel_phase(cfg, seed, rows=POOL_BLOCKS + 1, nb=MAX_SEQ // KV_BLOCK,
                 block=KV_BLOCK, decode_batch=BATCH,
                 prefill_len=PREFILL_CHUNK)
    log(f"kernel phase passed: compile_s={compile_seconds() - c0:.1f} "
        f"smoke_wall_s={time.monotonic() - t0:.1f}")

    model = get_model(cfg)
    t0 = time.monotonic()
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"params: {cfg.name} layers={cfg.n_layers}/36 d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} n={n_params} dtype={cfg.param_dtype} "
        f"init_s={time.monotonic() - t0:.1f}")
    trace = make_requests(cfg, seed, n=N_REQUESTS, prompt_len=PROMPT_LEN,
                          max_new=MAX_NEW)
    sizes = dict(batch=BATCH, max_seq=MAX_SEQ, block=KV_BLOCK,
                 pool_blocks=POOL_BLOCKS, prefill_chunk=PREFILL_CHUNK)

    engine = build_engine(model, params, level=6, pe=1, paged_attn="kernel",
                          **sizes)
    got = serve("engine O6 kernel", engine, trace)
    if engine.layout.attn_impl != "kernel" or engine.degrade_reason:
        raise RuntimeError(f"O6 engine degraded: attn_impl="
                           f"{engine.layout.attn_impl} reason="
                           f"{engine.degrade_reason}")
    if engine.prefill_mode != "chunked":
        raise RuntimeError(f"O6 engine prefill_mode={engine.prefill_mode}")
    del engine
    log("engine phase passed")

    engine = build_engine(model, params, level=5, pe=1, paged_attn="gather",
                          **sizes)
    ref = serve("reference O5 dense", engine, trace)
    del engine
    contract = kvquant.tolerance_contract("bf16")
    try:
        kvquant.assert_tokens_match(ref, got, contract,
                                    label="O6 kernel vs O5 dense")
        log("reference phase passed: tokens identical")
    except AssertionError as e:
        log(f"reference: {e}")
        log(f"token agreement {kvquant.token_agreement(ref, got):.4f}")
        explain_divergence(model, params, trace, ref, got,
                           labels=("dense", "kernel"), with_dense=True,
                           chunk=PREFILL_CHUNK, max_seq=MAX_SEQ,
                           block=KV_BLOCK)
        log("reference phase passed: every divergence is a near-tie "
            "within the bf16 logit tolerance")


def four_chips(seed: int) -> None:
    import jax

    from repro.models import get_model
    from repro.serving import kvquant

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, JAX found "
                           f"{len(jax.devices())}")
    cfg = smoke_config()
    model = get_model(cfg)
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    trace = make_requests(cfg, seed, n=N_REQUESTS, prompt_len=PROMPT_LEN,
                          max_new=MAX_NEW)
    # A sharded placement has no chunked-prefill step, so both engines
    # take the token-by-token prompt path: placement is the only
    # difference between them.
    sizes = dict(batch=BATCH, max_seq=MAX_SEQ, block=KV_BLOCK,
                 pool_blocks=POOL_BLOCKS, prefill_chunk=0)
    engine = build_engine(model, params, level=6, pe=1, paged_attn="kernel",
                          **sizes)
    one = serve("O6 kernel pe=1", engine, trace)
    del engine
    engine = build_engine(model, params, level=6, pe=4, paged_attn="kernel",
                          **sizes)
    if engine.placement.n_devices != 4:
        raise RuntimeError(f"pe=4 engine placed on "
                           f"{engine.placement.n_devices} device(s)")
    four = serve("O6 kernel pe=4", engine, trace)
    if engine.layout.attn_impl != "kernel" or engine.degrade_reason:
        raise RuntimeError(f"pe=4 engine degraded: "
                           f"{engine.degrade_reason}")
    del engine
    try:
        kvquant.assert_tokens_match(one, four,
                                    kvquant.tolerance_contract("bf16"),
                                    label="O6 pe=4 vs pe=1")
        log("four-chip phase passed: 4 devices placed, tokens identical "
            "to pe=1")
    except AssertionError as e:
        # pe=4 runs the model body batch-sharded (2 slots per chip), so
        # the chip's compiler may sum in another order than at pe=1.
        log(f"four-chip: {e}")
        log(f"token agreement {kvquant.token_agreement(one, four):.4f}")
        explain_divergence(model, params, trace, one, four,
                           labels=("pe=1", "pe=4"), with_dense=False,
                           chunk=PREFILL_CHUNK, max_seq=MAX_SEQ,
                           block=KV_BLOCK)
        log("four-chip phase passed: 4 devices placed; every divergence "
            "from pe=1 is a near-tie within the bf16 logit tolerance")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX's first device is "
                         f"{dev.platform!r}")
    sys.path.insert(0, str(REPO / "src"))
    from repro.launch.compile_cache import configure_compile_cache
    from repro.serving.tracing import compile_seconds

    cache_dir = configure_compile_cache()
    log(f"device_kind={dev.device_kind} device_count={len(jax.devices())} "
        f"jax={jax.__version__} compile_cache={cache_dir}")
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    log(f"total compile_s={compile_seconds():.1f} "
        f"peak_bytes_in_use={peak_bytes(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
