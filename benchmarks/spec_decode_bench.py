"""Microbenchmark: speculative decoding — the O7 draft/verify loop swept
across a (drafter, draft_k, batch, workload mix) grid.

Each cell builds a full O7 ``DecodeEngine`` (paged cache, greedy
sampler) and drains the fixed continuous-batching workload
(``autotune.measurement.serving_workload``), timing wall-clock per run.
Three drafter variants bracket the mechanism:

  K=0   — speculation off: the O6-equivalent hot path (the incumbent
          every K must beat);
  zoo   — the real pairing (``smollm-360m`` proposes for the target).
          On the smoke zoo both models have RANDOM weights, so
          acceptance is ~0 and this row is speculation's overhead
          floor: K drafter forwards + one (K+1)-wide verify that
          mostly emits a single token;
  self  — the target drafts for itself: acceptance is exactly 1.0 by
          construction, so this row is the mechanism's ceiling — every
          verify window emits K+1 tokens (window effects aside) and the
          tick count collapses by ~1/(K+1).

Real deployments live between the two rows, at the drafter's actual
acceptance; the serving autotuner (``--serve``, ``draft_k="auto"``)
measures exactly that and keeps speculation only when it wins.  Greedy
rejection keeps every cell bit-identical to K=0 — asserted per cell.

Methodology follows the serving-ladder noise memo: jit compiles outside
the timed region (one warmup drain per engine), measurement rounds
interleave every variant in the cell (container drift cancels), and
each variant's floor is the trimmed min (mean of its 3 fastest rounds).
Never run this under concurrent load.

Rows are appended as JSONL to
``experiments/autotune/spec_decode_bench.jsonl`` (one row per cell x
variant, acceptance telemetry alongside the measured floor) so the perf
trajectory tooling can track the speculation frontier over time.

  PYTHONPATH=src python -m benchmarks.spec_decode_bench
"""

import json
import os
import time

TRAJ = os.path.join(os.path.dirname(__file__), "..", "experiments",
                    "autotune", "spec_decode_bench.jsonl")

ARCH = "qwen3-8b"
DRAFT = "smollm-360m"
DRAFT_KS = (2, 4, 8)

# (mix, batch) cells.  The mixes move the prefill/decode balance the
# spec loop must live with: decode_heavy is where speculation can win
# (long generations amortize the verify window); prefill_heavy stresses
# the prompt-rides-the-verify-window path instead.
MIXES = {
    "decode_heavy": dict(max_seq=48, max_new=12, n_requests=10),
    "prefill_heavy": dict(max_seq=48, max_new=3, n_requests=10),
}
BATCHES = (2, 4)


def build_cell(mix: str, batch: int, seed: int = 0):
    """One (mix, batch) cell: the shared workload plus an engine per
    variant — ``("off", 0)`` then ``(drafter, K)`` for both drafter
    variants at every K."""
    import jax

    from repro.autotune.measurement import (serving_smoke_config,
                                            serving_workload)
    from repro.core.optlevel import BestEffortConfig, OptLevel
    from repro.models import get_model
    from repro.models.model_zoo import compatible_drafter
    from repro.serving import DecodeEngine

    cfg = serving_smoke_config(ARCH)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    dcfg = compatible_drafter(cfg, DRAFT)
    draft_api = get_model(dcfg)
    draft_params = draft_api.init(jax.random.PRNGKey(seed + 1))
    workload = serving_workload(cfg.vocab, seed=seed,
                                n_requests=MIXES[mix]["n_requests"],
                                max_seq=MIXES[mix]["max_seq"],
                                max_new=MIXES[mix]["max_new"])

    def engine(k: int, api=None, ps=None):
        return DecodeEngine(
            model, params, batch_size=batch,
            max_seq=MIXES[mix]["max_seq"],
            config=BestEffortConfig(level=OptLevel.O7, kv_block_size=8,
                                    draft_model=DRAFT, draft_k=k),
            draft_model=api, draft_params=ps)

    variants = {("off", 0): engine(0)}
    for k in DRAFT_KS:
        variants[("zoo", k)] = engine(k, draft_api, draft_params)
        variants[("self", k)] = engine(k, model, params)
    return workload, variants


def bench(rounds: int = 5, seed: int = 0) -> list:
    from repro.autotune.measurement import run_serving_workload

    rows = []
    for mix in MIXES:
        for batch in BATCHES:
            workload, variants = build_cell(mix, batch, seed)
            generated = None
            samples = {v: [] for v in variants}
            ticks = {}
            for v, eng in variants.items():     # warmup: jit compiles
                _, _, gen, _ = run_serving_workload(eng, workload)
                if generated is None:
                    generated = gen
                assert gen == generated, (
                    f"{mix}/B{batch}/{v}: speculation changed greedy "
                    f"tokens")
            for _ in range(rounds):
                for v, eng in variants.items():           # interleaved
                    t0 = eng.n_steps
                    wall, _, gen, _ = run_serving_workload(eng, workload)
                    assert gen == generated, "nondeterminism"
                    samples[v].append(wall)
                    ticks[v] = eng.n_steps - t0
            tokens = sum(len(g) for g in generated)
            for (drafter, k), eng in variants.items():
                floor = sum(sorted(samples[(drafter, k)])[:3]) / 3
                st = eng.spec_stats
                rows.append({
                    "arch": ARCH, "mix": mix, "batch": batch,
                    "max_seq": MIXES[mix]["max_seq"],
                    "max_new": MIXES[mix]["max_new"],
                    "requests": MIXES[mix]["n_requests"],
                    "drafter": drafter,
                    "draft_model": (None if drafter == "off" else
                                    ARCH if drafter == "self" else DRAFT),
                    "draft_k": k, "spec_mode": st["spec_mode"],
                    "wall_s": floor, "tok_per_s": tokens / floor,
                    "ticks": ticks[(drafter, k)], "tokens": tokens,
                    "accept_rate": st["accept_rate"],
                    "eff_tok_per_step": st["eff_tok_per_step"],
                    "identical": True,      # asserted at warmup
                })
    return rows


def main():
    rows = bench()
    os.makedirs(os.path.dirname(TRAJ), exist_ok=True)
    with open(TRAJ, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print("mix            batch drafter K | wall_ms tok/s  ticks | "
          "accept eff_tok | vs K=0")
    base = {}
    for r in rows:
        if r["drafter"] == "off":
            base[(r["mix"], r["batch"])] = r["wall_s"]
    for r in rows:
        b = base[(r["mix"], r["batch"])]
        print(f"{r['mix']:14s} {r['batch']:5d} {r['drafter']:7s} "
              f"{r['draft_k']:d} | {r['wall_s'] * 1e3:7.1f} "
              f"{r['tok_per_s']:6.0f} {r['ticks']:5d} | "
              f"{r['accept_rate']:6.2f} {r['eff_tok_per_step']:7.2f} | "
              f"{b / r['wall_s']:5.2f}x")
    print(f"wrote {os.path.relpath(TRAJ)}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
