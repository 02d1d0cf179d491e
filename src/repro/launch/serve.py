"""End-to-end serving driver: slot-based continuous batching at any rung
of the best-effort ladder.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --batch 4 --max-seq 64 --requests 8 --level 5 --policy spf

This script runs one engine in one process on the devices JAX finds:
``--smoke`` builds the reduced config, without it the published widths
(qwen3-8b's need a bf16 ``param_dtype`` and a cut depth to fit one
16 GB chip — see ``chip_smoke.py``).  It never builds a production
mesh; multi-device placement is the PE-duplication mesh below.
``--level`` selects the
OptLevel the engine is built at (see ``repro.serving``; 6 = paged KV
blocks, 7 = speculative decoding — pair it with ``--draft``); walk all
eight with ``python -m repro.autotune --serve``.

Layout x placement: ``--pe`` sets the PE-duplication degree — on >= 2
devices an O3+ engine shards (the contiguous cache on its batch axis;
at ``--level 6`` the paged pool on its BLOCK axis).  Force host devices
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``;
``--expect-devices`` turns the reported placement into an exit code for
CI smoke jobs.
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from repro.configs import ARCH_NAMES, get_config, get_smoke
from repro.core.optlevel import BestEffortConfig, OptLevel
from repro.models import get_model
from repro.serving import DecodeEngine, Request, SamplerConfig


def serve_demo(cfg, *, batch_size: int, max_seq: int, n_requests: int,
               seed: int = 0, prompt_len=(2, 12), max_new=(4, 16),
               level: OptLevel = OptLevel.O5, policy: str = "fcfs",
               sampler: SamplerConfig = None, pe: int = 8,
               kv_block_size: int = 16, kv_pool_blocks: int = 0,
               paged_attn: str = "gather", prefill_chunk: int = 0,
               draft_model: str = "", draft_k: int = 4,
               kv_dtype: str = "bf16") -> dict:
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    engine = DecodeEngine(model, params, batch_size=batch_size,
                          max_seq=max_seq,
                          config=BestEffortConfig(
                              level=level, pe=pe,
                              kv_block_size=kv_block_size,
                              kv_pool_blocks=kv_pool_blocks,
                              paged_attn=paged_attn,
                              prefill_chunk=prefill_chunk,
                              draft_model=draft_model,
                              draft_k=draft_k,
                              kv_dtype=kv_dtype),
                          policy=policy, sampler=sampler)

    rng = np.random.default_rng(seed)
    for _ in range(n_requests):
        plen = int(rng.integers(*prompt_len))
        new = int(rng.integers(*max_new))
        prompt = rng.integers(1, cfg.vocab, plen).tolist()
        engine.submit(Request(prompt=prompt, max_new_tokens=new))

    t0 = time.time()
    finished = engine.run()
    wall = time.time() - t0
    total_new = sum(len(r.generated) for r in finished)
    return {
        "finished": finished,
        "ticks": engine.n_steps,
        "wall_s": wall,
        "tokens": total_new,
        "tok_per_s": total_new / wall if wall > 0 else 0.0,
        "layout": engine.layout.name,
        "devices": engine.placement.n_devices,
        "paged_attn": getattr(engine.layout, "attn_impl", None),
        "state_impl": getattr(engine.layout, "state_impl", "none"),
        "degrade_reason": engine.degrade_reason,
        "kv_dtype": getattr(engine.layout, "kv_dtype", "bf16"),
        "prefill_mode": engine.prefill_mode,
        "spec_mode": engine.spec_mode,
        "spec": engine.spec_stats,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--level", type=int, default=5, choices=range(8),
                    help="OptLevel to build the engine at (0=naive, "
                         "6=paged KV blocks, 7=speculative decoding — "
                         "needs --draft)")
    ap.add_argument("--policy", default="fcfs",
                    choices=("fcfs", "spf", "deadline"),
                    help="admission policy: fcfs, spf (shortest-prompt-"
                         "first with aging), or deadline (EDF on "
                         "Request.deadline_s — the open-loop traffic "
                         "front end's SLO policy)")
    ap.add_argument("--sampler", default="greedy",
                    choices=("greedy", "temperature", "top_k"))
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--pe", type=int, default=8,
                    help="PE duplication degree (O3+): shard degree over "
                         "visible devices; degrades, never fails")
    ap.add_argument("--kv-block", type=int, default=16,
                    help="O6 paged-cache block size in tokens")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="O6 pool size in blocks (0 = auto)")
    ap.add_argument("--paged-attn", default="gather",
                    choices=("gather", "kernel"),
                    help="O6 attention implementation: gather "
                         "re-materializes the dense KV view per tick; "
                         "kernel runs the gather-free block-table "
                         "Pallas kernel on the raw pool (families "
                         "without a paged decode step fall back)")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=("bf16", "int8", "fp8"),
                    help="O6 pool STORED dtype: int8/fp8 store narrow "
                         "blocks with per-block absmax scales (~2x "
                         "capacity at equal pool memory; tokens track "
                         "the bf16 rung within the tolerance contract, "
                         "not bit-exactly)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: consume prompts in chunks of "
                         "this many tokens, one chunk per tick, "
                         "interleaved with decode (0 = legacy one-token-"
                         "per-tick prestaged path; families without a "
                         "prefill step degrade; greedy tokens identical "
                         "either way)")
    ap.add_argument("--draft", default="", dest="draft_model",
                    help="O7 drafter arch (e.g. smollm-360m): proposes "
                         "--draft-k tokens per slot per tick for the "
                         "target to verify in one batched forward; must "
                         "share the target's vocab (resolved at the same "
                         "smoke/full scale).  Empty disables speculation "
                         "(O7 then behaves exactly like O6)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculation window: drafted tokens per slot "
                         "per verify step (0 disables; greedy tokens "
                         "identical for every K)")
    ap.add_argument("--expect-devices", type=int, default=0,
                    help="exit 1 unless the engine's placement landed on "
                         "exactly this many devices (CI smoke)")
    args = ap.parse_args()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    sampler = SamplerConfig(kind=args.sampler, temperature=args.temperature,
                            top_k=args.top_k, seed=args.seed)
    out = serve_demo(cfg, batch_size=args.batch, max_seq=args.max_seq,
                     n_requests=args.requests, seed=args.seed,
                     level=OptLevel(args.level), policy=args.policy,
                     sampler=sampler, pe=args.pe,
                     kv_block_size=args.kv_block,
                     kv_pool_blocks=args.kv_pool_blocks,
                     paged_attn=args.paged_attn,
                     prefill_chunk=args.prefill_chunk,
                     draft_model=args.draft_model, draft_k=args.draft_k,
                     kv_dtype=args.kv_dtype)
    for r in out["finished"][:4]:
        print(f"[serve] req {r.rid}: prompt[{r.n_prompt}] -> "
              f"{r.generated}")
    attn = f"/{out['paged_attn']}" if out["paged_attn"] else ""
    if out.get("state_impl", "none") != "none":
        attn += f"/state={out['state_impl']}"
    if out.get("kv_dtype", "bf16") != "bf16":
        attn += f"/kv={out['kv_dtype']}"
    if args.prefill_chunk:
        attn += f"/prefill={out['prefill_mode']}({args.prefill_chunk})"
    if out["spec_mode"] == "draft":
        st = out["spec"]
        attn += (f"/spec=K{st['draft_k']}({args.draft_model},"
                 f"accept={st['accept_rate']:.2f},"
                 f"eff={st['eff_tok_per_step']:.2f})")
    elif args.level >= 7:
        attn += "/spec=off"
    print(f"[serve] O{args.level}/{args.policy} "
          f"[{out['layout']}{attn} x {out['devices']} device(s)]: "
          f"{len(out['finished'])} requests, {out['tokens']} new "
          f"tokens in {out['ticks']} ticks / {out['wall_s']:.2f}s "
          f"({out['tok_per_s']:.1f} tok/s batched)")
    if out.get("degrade_reason"):
        print(f"[serve] degraded: {out['degrade_reason']}")
    if args.expect_devices and out["devices"] != args.expect_devices:
        raise SystemExit(
            f"placement landed on {out['devices']} device(s), expected "
            f"{args.expect_devices} (XLA_FLAGS / --pe / batch "
            f"divisibility?)")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
