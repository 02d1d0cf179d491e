"""The serving ladder — the paper's Table 1 analog for the decode engine.

Measures ``repro.serving.DecodeEngine`` at every OptLevel O0..O7 on one
fixed continuous-batching workload (smoke config) and renders the
per-level throughput/latency table to ``benchmarks/SERVING_LADDER.md``,
plus a JSONL trajectory compatible with the autotune tooling (every row
records its ``layout`` and ``devices`` placement cell).  The O6 rung
(paged KV blocks) runs at equal worst-case capacity here so the table
stays a pure speed comparison; its capacity win — more admitted
concurrency at equal memory on long-tail mixes — is measured separately
by :func:`capacity_demo` and rendered under the same table.  On >= 2
visible devices (``XLA_FLAGS=--xla_force_host_platform_device_count=N``)
every O3+ row shards — the O6 row then IS the layout x placement
composition cell (paged pool sharded on its BLOCK axis, same placement
as the O5 row so O5->O6 stays the pure block-indirection delta) — and
the ladder gains the ``O6pe1`` placement-ablation row (same paged pool,
replicated), measured by the same interleaved trimmed-min harness as
every other row.

  PYTHONPATH=src python -m benchmarks.serving_ladder

Methodology: wall-clock on a shared CPU container is noisy and the upper
rungs of the serving ladder are near-ties by design (PE duplication is
inert on one device; double buffering hides tens of microseconds of host
work per tick), so a naive one-engine-per-level sweep confounds the
ladder with jit-instance and process-warmup luck.  This harness builds
``INSTANCES`` independent engines per level (serpentine creation order),
warms every one up (jit compiles outside the timed region), interleaves
measurement rounds across all engines, and estimates each level's floor
as the trimmed min (mean of its 3 fastest runs).  Adjacent levels whose
difference is indistinguishable from round-to-round jitter under a
paired-delta test (median inside 1.5 MADs / 1%) are reported as TIES at
the pooled floor; a regression beyond noise is rendered as-is.  If an
inversion persists, extra rounds with fresh engine instances are run
(up to a cap) before giving up.

Each row also carries TTFT/ITL columns — single-request latency probes
on the idle warm engines (``serving_latency_probe``), trimmed-min over
the same interleaved rounds, through each engine's real prefill path —
and the ``O5c`` row ablates chunked prefill (``prefill_chunk=16``)
against the O5 row it modifies.

The O7 row (speculative decoding) additionally reports ``accept %`` and
``eff tok/step`` — the fraction of drafted tokens the target's argmax
accepted and the tokens emitted per slot per verify window.  With the
smoke zoo's random-weight drafter acceptance is near zero, so the row
reads as speculation's OVERHEAD floor (drafter forwards + a K+1-wide
verify that mostly emits one token); the acceptance column is what
turns it into a win when the drafter approximates the target.  Tokens
stay bit-identical regardless — greedy rejection guarantees it.

The harness also asserts the ladder's semantic contract: under greedy
sampling every level generates bit-identical tokens for every request.
"""

import json
import os
import time

# Keys 0..7 are the OptLevels; keys >= 90 are ablation rows (they were
# 7/8/9 before the ladder grew the O7 rung, which collided with level 7).
STAGES = {
    0: "naive: per-request B=1 decode calls + per-request cache rebuild",
    1: "+ data caching: persistent device cache, in-place slot zeroing",
    2: "+ pipelining: continuous batching, one fused step, sample-in-graph",
    3: "+ PE duplication: batch-axis sharding across devices",
    4: "+ double buffering: bookkeeping runs under the in-flight step",
    5: "+ scratchpad reorg: packed one-call zeroing of admitted slots",
    6: "+ paged scratchpad: KV block pool + per-request block tables",
    7: "+ speculative decoding: drafter proposes K=4, one verify forward",
    # Key 91 is not a level: on >= 2 devices (where the O6 row itself
    # runs the block-axis-sharded composition cell) it re-runs O6 pinned
    # to pe=1 — the placement ablation within the paged layout.
    91: "O6 placement ablation: same paged pool, replicated (pe=1)",
    # Key 92 is not a level either: the O6 attention-implementation
    # ablation — the same paged pool driven by the gather-free
    # block-table Pallas kernel (paged_attn=kernel) instead of the
    # per-tick dense gather.  Its bytes-moved column is the point:
    # O(blocks touched), not O(B * max_seq).
    92: "O6 attn ablation: gather-free block-table kernel "
        "(paged_attn=kernel)",
    # Key 93: the prefill ablation — the O5 engine with CHUNKED prefill
    # (prefill_chunk=16): prompts ride multi-token chunk dispatches
    # interleaved with decode instead of one decode tick per prompt
    # token.  Its column of interest is TTFT, not tok/s.
    93: "O5 prefill ablation: chunked prefill (prefill_chunk=16)",
    # Key 94: the pool-dtype ablation — the O6 engine storing int8
    # blocks with per-block absmax scales (kv_dtype=int8).  Its columns
    # of interest are `pool MB` and `KV bytes/tick` (roughly halved);
    # its token contract is the TOLERANCE contract, not bit-identity —
    # the `identical` column reports contract satisfaction.
    94: "O6 kv-dtype ablation: int8 block pool + per-block scales "
        "(kv_dtype=int8)",
}

# The drafter the O7 row pairs with the target (``model_zoo.
# DRAFTER_PAIRS`` validated at engine build) and its window size.
LADDER_DRAFT = {"draft_model": "smollm-360m", "draft_k": 4}

MD_PATH = os.path.join(os.path.dirname(__file__), "SERVING_LADDER.md")
TRAJ_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "autotune")


def ladder_variants(devices: int):
    """The measured (key, label, config) cells.  Keys 0..7 are the
    OptLevels at their default configs (the O7 row adds the
    ``LADDER_DRAFT`` drafter pairing — speculation needs one) — on >= 2
    devices every O3+ row shards, so O5->O6 compares MATCHED placements
    and the O6 row itself is the layout x placement composition cell
    (block-axis-sharded paged pool).  Key 92 (always present, adjacent
    to the O6 row it ablates) is the attention-implementation ablation:
    the same paged pool driven by the gather-free block-table kernel, so
    O6->O6k reads as the pure gather-elimination delta.  Key 93 is the
    prefill ablation: the O5 engine with chunked prefill
    (prefill_chunk=16), paired against the O5 row so O5->O5c reads as
    the pure chunked-prefill delta — its interesting column is TTFT, not
    tok/s.  Key 91, added only on multi-device runs, is the placement
    ablation: the same paged engine pinned to pe=1, isolating what
    sharding buys (or costs) within the paged layout."""
    from repro.core.optlevel import ALL_LEVELS, BestEffortConfig, OptLevel

    out = [(int(lvl), f"O{int(lvl)}",
            BestEffortConfig(level=lvl, **(LADDER_DRAFT
                                           if lvl == OptLevel.O7 else {})))
           for lvl in ALL_LEVELS]
    out.append((92, "O6k", BestEffortConfig(level=OptLevel.O6,
                                            paged_attn="kernel")))
    out.append((93, "O5c", BestEffortConfig(level=OptLevel.O5,
                                            prefill_chunk=16)))
    out.append((94, "O6q", BestEffortConfig(level=OptLevel.O6,
                                            kv_dtype="int8")))
    if devices > 1:
        out.append((91, "O6pe1", BestEffortConfig(level=OptLevel.O6, pe=1)))
    return out


def _traced_kernel_bytes(eng, workload) -> int:
    """One untimed replay that accumulates the kernel step's per-tick
    KV-bytes estimate (sum over slots of the blocks their tables
    reference, via ``PagedCacheManager.slot_lengths``) — the gather-free
    path's traffic depends on the live lengths, so it is measured off
    the actual schedule, not a formula.  Lengths are sampled BEFORE each
    step: the slots that will attend this tick, including ones that
    retire on it (their final, longest walk counts); on the cold-start
    tick, where admission happens inside the step, they are read back
    post-step instead.  Run AFTER the timed rounds (never under
    concurrent load)."""
    from repro.serving import Request

    mgr = eng.cache_mgr
    for p, n in workload:
        eng.submit(Request(prompt=list(p), max_new_tokens=n))
    total = ticks = 0
    for _ in range(10_000):
        lengths = mgr.slot_lengths(
            [s.pos if s.active else 0 for s in eng.slots])
        steps_before = eng.n_steps
        stepped = eng.step()
        if eng.n_steps > steps_before:
            if not any(lengths):         # cold start: admitted in-step;
                lengths = mgr.slot_lengths(     # pos already advanced
                    [s.pos - 1 if s.active else 0 for s in eng.slots])
            total += mgr.plan.kernel_bytes_per_tick(lengths)
            ticks += 1
        if not stepped and not eng.queue:
            break
    return total // max(1, ticks)


def measure_ladder(arch: str = "qwen3-8b", *, batch_size: int = 4,
                   max_seq: int = 48, n_requests: int = 16,
                   max_new: int = 8, instances: int = 2, rounds: int = 8,
                   max_extra_rounds: int = 24, policy: str = "fcfs",
                   vocab: int = 0, seed: int = 0) -> list:
    """Returns one row dict per measured variant: wall_s, tok_per_s,
    ticks, tokens, identical (vs O0), layout/devices, plus the workload
    identity."""
    import jax

    from repro.autotune.measurement import (run_serving_workload,
                                            serving_latency_probe,
                                            serving_smoke_config,
                                            serving_workload)
    from repro.models import get_model
    from repro.serving import DecodeEngine

    cfg = serving_smoke_config(arch, vocab)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    workload = serving_workload(cfg.vocab, max_seq=max_seq,
                                n_requests=n_requests, max_new=max_new,
                                seed=seed)
    variants = ladder_variants(jax.device_count())
    by_key = {k: (label, vcfg) for k, label, vcfg in variants}
    keys = [k for k, _, _ in variants]

    def run(eng):
        wall, _, gen, _ = run_serving_workload(eng, workload)
        return wall, gen

    generated = {}        # key -> token lists (must agree per key too)
    engines = []          # [(key, engine)]
    kv_capacity = {}      # key -> persistent cache capacity (tokens)
    devices_used = {}     # key -> placement device count
    layouts = {}          # key -> cache layout name
    attn_impls = {}       # key -> paged attention impl (None: contiguous)
    state_impls = {}      # key -> recurrent-state impl ("rows" | "none")
    degrades = {}         # key -> recorded degrade reason (or None)
    prefill_modes = {}    # key -> "chunked" | "token"
    kv_dtypes = {}        # key -> pool stored dtype ("bf16" contiguous)
    pool_mb = {}          # key -> paged pool MB (None: contiguous)
    probe_len = max(1, min(24, max_seq - max_new))

    def add_instance(k):
        _, vcfg = by_key[k]
        eng = DecodeEngine(
            model, params, batch_size=batch_size, max_seq=max_seq,
            config=vcfg, policy=policy)
        _, gen = run(eng)                          # warmup: jit compiles
        assert generated.setdefault(k, gen) == gen, (
            f"variant {k}: instances disagree")
        # Untimed warmup probe so the timed latency probes never carry a
        # first-touch compile (the chunked-prefill step traces here).
        serving_latency_probe(eng, cfg.vocab, prompt_len=probe_len,
                              max_new=max_new, seed=seed + 17)
        kv_capacity[k] = eng.cache_mgr.capacity_tokens
        devices_used[k] = eng.placement.n_devices
        layouts[k] = eng.layout.name
        attn_impls[k] = getattr(eng.layout, "attn_impl", None)
        state_impls[k] = getattr(eng.layout, "state_impl", "none")
        degrades[k] = eng.degrade_reason
        prefill_modes[k] = eng.prefill_mode
        kv_dtypes[k] = getattr(eng.layout, "kv_dtype", "bf16")
        geo = getattr(eng.cache_mgr, "geometry", None)
        pool_mb[k] = geo.get("pool_mb") if geo else None
        engines.append((k, eng))
        return eng

    # Serpentine creation order: engine construction order measurably
    # biases performance (allocator state drifts over process lifetime),
    # so instance 0 is built O0->O6, instance 1 O6->O0, and so on — no
    # variant systematically inherits the worst allocator state.
    for i in range(instances):
        order = keys if i % 2 == 0 else list(reversed(keys))
        for k in order:
            add_instance(k)

    samples = {k: [] for k in keys}
    round_best = {k: [] for k in keys}   # per-round minima
    ttft_samples = {k: [] for k in keys}
    itl_samples = {k: [] for k in keys}
    ticks = {}

    def one_round():
        this_round = {}
        for k, eng in engines:
            t_before = eng.n_steps
            wall, gen = run(eng)
            assert gen == generated[k], f"variant {k}: nondeterminism"
            samples[k].append(wall)
            this_round[k] = min(this_round.get(k, wall), wall)
            ticks[k] = eng.n_steps - t_before
            # Latency probe on the now-idle warm engine: TTFT/ITL through
            # the REAL prefill path (chunked where the config says so),
            # single unloaded request — NOT wall-clock under load.  Rides
            # the same interleaved rounds so process drift cancels.
            ttft, itl, _ = serving_latency_probe(
                eng, cfg.vocab, prompt_len=probe_len, max_new=max_new,
                seed=seed + 17)
            ttft_samples[k].append(ttft)
            itl_samples[k].append(itl)
        for k, w in this_round.items():
            round_best[k].append(w)

    for _ in range(rounds):
        one_round()

    noise_ties = []

    def floors():
        # Trimmed min — mean of the 3 fastest samples — not the raw min:
        # on a shared container one transient quiet period can hand a
        # single level an unrepresentatively lucky sample that a raw min
        # never takes back; the trimmed floor needs the luck to repeat.
        # And on one device PE duplication is inert: the O3 engine
        # resolves to the *identical* configuration as O2 (no mesh, same
        # shared compiled step, same host loop), so the two levels sample
        # the same distribution and share one measurement pool —
        # different floors for identical machine behavior would just be
        # split-sample noise.
        pool = dict(samples)
        if jax.device_count() == 1:
            merged = sorted(samples[2] + samples[3])
            pool[2] = pool[3] = merged
        est = {k: sum(sorted(v)[:3]) / min(3, len(v))
               for k, v in pool.items()}

        # Adjacent variants whose measured difference is statistically
        # indistinguishable from round-to-round jitter are TIES: compare
        # the PAIRED per-round minima (same process epoch, so drift
        # cancels) and, when the median delta is inside the noise band
        # (1.5 MADs, floored at 1%), give both variants the pooled floor.
        # A real regression (beyond noise) is left standing and renders
        # as non-monotone — the harness never papers over mechanism.
        # The ablation rows are NOT paired positionally: O6k (attn impl)
        # and O6pe1 (placement) ablate the O6 row itself, so each is
        # paired against key 6, never against the other ablation; O5c
        # (chunked prefill) ablates the O5 row.
        tie_baseline = {91: 6, 92: 6, 93: 5, 94: 6}
        noise_ties.clear()
        for i in range(1, len(keys)):
            k = keys[i]
            prev = tie_baseline.get(k, keys[i - 1])
            if est[k] <= est[prev]:
                continue
            n = min(len(round_best[k]), len(round_best[prev]))
            deltas = sorted(round_best[k][j] - round_best[prev][j]
                            for j in range(n))
            med = deltas[n // 2]
            mad = sorted(abs(d - med) for d in deltas)[n // 2]
            if med <= max(1.5 * mad, 0.01 * est[prev]):
                merged = sorted(pool[k] + pool[prev])
                tie = sum(merged[:3]) / min(3, len(merged))
                est[k] = est[prev] = tie
                noise_ties.append((prev, k))
        return est

    best = floors()
    extra = 0
    # Inversion escalation covers the MECHANISM rungs O0..O5 only: an
    # inversion there after the initial rounds is instance luck and more
    # instances converge it away.  O5->O6 (and the O6+pe composition row)
    # is excluded — the paged rung pays a real gather/scatter toll at
    # equal capacity, so "slower than O5" is the expected reading, not
    # luck, and chasing it would burn every extra round (and ~2 fresh jit
    # compiles per round) for nothing; the rendered table explains the
    # regression instead.
    mono_top = min(5, len(keys) - 1)
    while extra < max_extra_rounds and any(
            best[k] > best[k - 1] for k in range(1, mono_top + 1)):
        for k in range(1, mono_top + 1):
            if best[k] > best[k - 1]:
                add_instance(k)
                add_instance(k - 1)
        one_round()
        best = floors()
        extra += 1

    # Per-tick KV-cache bytes estimate (the gather-vs-kernel delta the
    # O6k row exists to show).  Contiguous rungs: dense attention streams
    # the whole (B, max_seq) cache each tick.  Paged gather: the dense
    # view is materialized AND read (plan.gather_bytes_per_tick).  Paged
    # kernel: O(blocks touched), measured off a replay of the actual
    # schedule.  Computed after the timed rounds so the replay can't
    # perturb them.
    first_eng = {}
    for k, eng in engines:
        first_eng.setdefault(k, eng)
    # Speculation telemetry (O7 row): counters accumulate over the same
    # deterministic workload every round, so the rate is the workload's.
    spec_stats = {k: first_eng[k].spec_stats for k in keys}
    tb = first_eng[6].cache_mgr.geometry["token_bytes"]
    kv_bytes = {}
    for k in keys:
        eng = first_eng[k]
        if eng.layout.name == "contiguous":
            kv_bytes[k] = batch_size * max_seq * tb
        elif getattr(eng.layout, "attn_impl", "gather") == "kernel":
            kv_bytes[k] = _traced_kernel_bytes(eng, workload)
        else:
            kv_bytes[k] = eng.cache_mgr.plan.gather_bytes_per_tick()

    # Latency floors use the same trimmed-min estimator as the
    # throughput column: each probe is one unloaded request through the
    # engine's real prefill path, sampled once per engine per round.
    ttft_est = {k: sum(sorted(v)[:3]) / min(3, len(v))
                for k, v in ttft_samples.items()}
    itl_est = {k: sum(sorted(v)[:3]) / min(3, len(v))
               for k, v in itl_samples.items()}

    from repro.serving.kvquant import token_agreement, tolerance_contract

    tokens = sum(len(g) for g in generated[0])
    tie_partner = {k: p for p, k in noise_ties}
    row_level = {91: 6, 92: 6, 93: 5, 94: 6}
    rows = []
    for i, k in enumerate(keys):
        stage = STAGES[k]
        if k == 92 and attn_impls[k] != "kernel":
            # A family without a paged decode step degrades the kernel
            # row to gather — say so instead of mislabeling the cell.
            stage += (" — DEGRADED to gather (this family has no paged "
                      "decode step)")
        if k == 93 and prefill_modes[k] != "chunked":
            stage += (" — DEGRADED to token prefill (this family has no "
                      "prefill step)")
        if k == 7 and spec_stats[k]["spec_mode"] != "draft":
            stage += (" — DEGRADED to plain decode (this cell cannot "
                      "speculate)")
        # The ladder's token contract is per-row: bf16 rows must be
        # bit-identical to O0; a narrow-pool row is held to its dtype's
        # tolerance contract instead (the `identical` column then reports
        # contract SATISFACTION, and `agreement` the measured fraction).
        if kv_dtypes[k] == "bf16":
            identical = generated[k] == generated[0]
            agreement = None
        else:
            tc = tolerance_contract(kv_dtypes[k])
            agreement = token_agreement(generated[0], generated[k])
            identical = agreement >= tc["min_agreement"]
        rows.append({
            "level": row_level.get(k, k),
            "label": by_key[k][0],
            "stage": stage,
            "wall_s": best[k],
            "tok_per_s": tokens / best[k],
            "tick_ms": best[k] / ticks[k] * 1e3,
            "ticks": ticks[k],
            "tokens": tokens,
            "speedup_vs_o0": best[0] / best[k],
            "identical": identical,
            "kv_dtype": kv_dtypes[k],
            "agreement": agreement,
            "pool_mb": pool_mb[k],
            # the baseline this row pooled floors with (each ablation row
            # ties against the O6 row it ablates, not its table neighbor)
            "noise_tie_with": (by_key[tie_partner[k]][0]
                               if k in tie_partner else None),
            "extra_rounds": extra,
            "kv_capacity": kv_capacity[k],
            "layout": layouts[k],
            "devices": devices_used[k],
            "paged_attn": attn_impls[k],
            "state_impl": state_impls[k],
            "degrade_reason": degrades[k],
            "kv_bytes_per_tick": int(kv_bytes[k]),
            "prefill_mode": prefill_modes[k],
            "ttft_ms": ttft_est[k] * 1e3,
            "itl_ms": itl_est[k] * 1e3,
            "spec_mode": spec_stats[k]["spec_mode"],
            "draft_k": spec_stats[k]["draft_k"],
            "accept_rate": spec_stats[k]["accept_rate"],
            "eff_tok_per_step": spec_stats[k]["eff_tok_per_step"],
        })
    return rows


def capacity_demo(arch: str = "qwen3-8b", *, memory_slots: int = 4,
                  max_seq: int = 48, slots_paged: int = 8,
                  block_size: int = 8, n_requests: int = 24,
                  max_new: int = 6, seed: int = 0) -> dict:
    """The paged rung's actual win, measured: at EQUAL KV memory
    (``memory_slots x max_seq`` token positions), the contiguous cache
    admits at most ``memory_slots`` concurrent requests no matter how
    short they are, while the paged pool admits as many as their actual
    reservations pack — more concurrency (and fewer ticks) on long-tail
    prompt mixes.  Greedy tokens must stay identical between the two
    engines (slot placement and batch composition never change *what* is
    computed).

    The QUANTIZED row compounds the win: at the same pool BYTES the
    int8 pool holds ~2x the blocks (1-byte cells + per-block scales vs
    2-byte bf16 cells), so it admits ~2x the paged engine's concurrency
    on the same mix.  Its tokens are held to the int8 tolerance
    contract against the contiguous baseline, not bit-identity.

    Timing follows the ladder harness's rules, not a hand-rolled
    stopwatch: jit compiles (the O6 engine always builds its own step —
    pool geometry is part of the program) and the deterministic run shape
    (peak concurrency, ticks) are captured on an untimed warmup pass, and
    the tok/s column is the best of interleaved re-runs on the
    already-warm engines, so neither side's number carries compile time
    or a one-sided quiet period."""
    import jax

    from repro.autotune.measurement import (run_serving_workload,
                                            serving_smoke_config,
                                            serving_workload)
    from repro.core.optlevel import BestEffortConfig, OptLevel
    from repro.models import get_model
    from repro.serving import DecodeEngine, Request

    rounds = 3
    cfg = serving_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    workload = serving_workload(cfg.vocab, max_seq=max_seq,
                                n_requests=n_requests, max_new=max_new,
                                seed=seed)
    pool_blocks = memory_slots * max_seq // block_size   # same token memory

    def warmup_tracked(eng):
        """Untimed first pass: compiles, and records the run's
        deterministic shape (peak concurrency, ticks, generations)."""
        rids = [eng.submit(Request(prompt=list(p), max_new_tokens=n))
                for p, n in workload]
        peak = 0
        for _ in range(10_000):
            stepped = eng.step()
            peak = max(peak, sum(s.active for s in eng.slots))
            if not stepped and not eng.queue:
                break
        by_rid = {r.rid: r.generated for r in eng.finished}
        gen = [by_rid[rid] for rid in rids]
        return {"peak_concurrency": peak, "ticks": eng.n_steps,
                "gen": gen, "tokens": sum(len(g) for g in gen)}

    eng_c = DecodeEngine(
        model, params, batch_size=memory_slots, max_seq=max_seq,
        config=BestEffortConfig(level=OptLevel.O5))
    eng_p = DecodeEngine(
        model, params, batch_size=slots_paged, max_seq=max_seq,
        config=BestEffortConfig(level=OptLevel.O6,
                                kv_block_size=block_size,
                                kv_pool_blocks=pool_blocks))
    contig, paged = warmup_tracked(eng_c), warmup_tracked(eng_p)
    assert paged["gen"] == contig["gen"], "capacity demo changed tokens"

    # Quantized pool at the SAME pool BYTES as the bf16 pool: the bytes
    # the 1-byte cells save (minus the per-block scale overhead) are
    # spent on more blocks, and the slot count doubles so the extra
    # blocks can actually become admitted concurrency.
    from repro.serving.kvquant import token_agreement, tolerance_contract
    from repro.serving.paged import BlockPagingPlan

    wide_plan = eng_p.cache_mgr.plan
    nplan = BlockPagingPlan(model, slots_paged, max_seq, block_size,
                            pool_blocks, kv_dtype="int8")
    wide_bb = block_size * wide_plan.token_bytes \
        + wide_plan.scale_bytes_per_block
    narrow_bb = block_size * nplan.token_bytes + nplan.scale_bytes_per_block
    q_blocks = pool_blocks * wide_bb // narrow_bb
    eng_q = DecodeEngine(
        model, params, batch_size=slots_paged * 2, max_seq=max_seq,
        config=BestEffortConfig(level=OptLevel.O6,
                                kv_block_size=block_size,
                                kv_pool_blocks=q_blocks,
                                kv_dtype="int8"))
    quant = warmup_tracked(eng_q)
    tc = tolerance_contract("int8")
    agreement = token_agreement(contig["gen"], quant["gen"])
    assert agreement >= tc["min_agreement"], (
        f"capacity demo int8 agreement {agreement:.3f} below the "
        f"{tc['min_agreement']} tolerance contract")

    contig["wall_s"] = paged["wall_s"] = quant["wall_s"] = float("inf")
    for _ in range(rounds):                       # interleaved best-of-K
        for rec, eng in ((contig, eng_c), (paged, eng_p), (quant, eng_q)):
            wall, _, gen, _ = run_serving_workload(eng, workload)
            assert gen == rec["gen"], "capacity demo nondeterminism"
            rec["wall_s"] = min(rec["wall_s"], wall)
    quant["pool_blocks"] = q_blocks
    quant["agreement"] = agreement
    return {
        "arch": arch,
        "kv_memory_tokens": memory_slots * max_seq,
        "block_size": block_size,
        "pool_blocks": pool_blocks,
        "n_requests": n_requests,
        "contiguous": {k: v for k, v in contig.items() if k != "gen"},
        "paged": {k: v for k, v in paged.items() if k != "gen"},
        "quantized": {k: v for k, v in quant.items() if k != "gen"},
        "identical": True,
    }


def capacity_demo_state(arch: str = "zamba2-2.7b", *, memory_slots: int = 4,
                        max_seq: int = 256, slots_paged: int = 12,
                        block_size: int = 8, n_requests: int = 12,
                        max_new: int = 6, seed: int = 0) -> dict:
    """The paged rung's capacity story for a RECURRENT family, at equal
    TOTAL cache bytes (attention KV blocks + state rows, leaf-summed off
    the real device trees — no formula).

    Hybrid (and enc-dec self-attention) families win the same way
    transformers do: recurrent state is O(1) per slot, so at a long
    ``max_seq`` almost the whole contiguous budget is worst-case
    attention KV, and the paged engine re-spends it as block-packed
    short reservations plus one cheap state row per extra slot — more
    admitted concurrency on short-prompt mixes.  Pure-state families
    (rwkv6, mamba2) have NO per-position cache at all: capacity is one
    row per slot whichever layout holds it, so at equal bytes the paged
    pool admits exactly ``contig_rows - 1`` slots (the constant NULL
    row is the entire overhead, amortized away at scale) — the table
    reports that parity honestly; the O6 rung's value for them is the
    uniform full-rung mechanism (kernel step, NULL-row chunk parking,
    defrag), not bytes.

    Greedy tokens must stay identical across layouts and batch sizes —
    slot placement never changes what is computed."""
    import jax

    from repro.autotune.measurement import (serving_smoke_config,
                                            serving_workload)
    from repro.core.optlevel import BestEffortConfig, OptLevel
    from repro.models import get_model
    from repro.serving import DecodeEngine, PagedCacheManager, Request

    cfg = serving_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    # short prompts (4x shorter than the engine's max_seq would draw):
    # the long-tail mix where block packing beats worst-case slabs
    workload = serving_workload(cfg.vocab, max_seq=max_seq // 4,
                                n_requests=n_requests, max_new=max_new,
                                seed=seed)

    def drain(eng):
        rids = [eng.submit(Request(prompt=list(p), max_new_tokens=n))
                for p, n in workload]
        peak = 0
        for _ in range(10_000):
            stepped = eng.step()
            peak = max(peak, sum(s.active for s in eng.slots))
            if not stepped and not eng.queue:
                break
        by_rid = {r.rid: r.generated for r in eng.finished}
        return {"peak_concurrency": peak, "ticks": eng.n_steps,
                "gen": [by_rid[rid] for rid in rids]}

    eng_c = DecodeEngine(model, params, batch_size=memory_slots,
                         max_seq=max_seq,
                         config=BestEffortConfig(level=OptLevel.O5))
    contig_bytes = sum(l.size * l.dtype.itemsize
                       for l in jax.tree.leaves(eng_c.cache_mgr.cache))

    # probe manager: per-block and per-state-row byte costs of THIS
    # family's cache tree (geometry, not guesswork)
    g = PagedCacheManager(model, 2, max_seq, block_size=block_size).geometry
    block_bytes = block_size * g["token_bytes"] + g["scale_bytes_per_block"]
    row_bytes = g["state_row_bytes"]
    if g["token_bytes"] == 0:
        # pure state: no block leaves to page; equal bytes buys
        # contig_rows - 1 slots (the NULL row is the whole overhead)
        slots = max(1, contig_bytes // max(1, row_bytes) - 1)
        pcfg = BestEffortConfig(level=OptLevel.O6, kv_block_size=block_size)
        note = "state only: parity minus the constant NULL row"
    else:
        # spend the contiguous budget on (slots_paged + NULL) state rows,
        # then pack the remainder with KV blocks (one row is the NULL
        # block, not allocatable)
        state_total = (slots_paged + 1) * row_bytes
        blocks = (contig_bytes - state_total) // block_bytes - 1
        slots = slots_paged
        pcfg = BestEffortConfig(level=OptLevel.O6, kv_block_size=block_size,
                                kv_pool_blocks=int(blocks))
        note = "mixed pools: block tables + one state row per slot"
    eng_p = DecodeEngine(model, params, batch_size=int(slots),
                         max_seq=max_seq, config=pcfg)
    paged_bytes = eng_p.cache_mgr.geometry["pool_bytes"]
    assert paged_bytes <= contig_bytes, (arch, paged_bytes, contig_bytes)

    contig, paged = drain(eng_c), drain(eng_p)
    assert paged["gen"] == contig["gen"], (
        f"{arch} state capacity demo changed tokens")
    return {
        "arch": arch, "family": cfg.family,
        "contig_bytes": int(contig_bytes), "paged_bytes": int(paged_bytes),
        "contig_slots": memory_slots, "paged_slots": int(slots),
        "state_impl": eng_p.layout.state_impl, "note": note,
        "contiguous": {k: v for k, v in contig.items() if k != "gen"},
        "paged": {k: v for k, v in paged.items() if k != "gen"},
        "identical": True,
    }


# The family x rung support matrix SERVING_LADDER.md and README render:
# static truth about which mechanism each family runs at each rung,
# asserted by the differential-fuzz suite (tests/test_serving.py).
FAMILY_RUNG_MATRIX = [
    ("dense / moe / vlm", "qwen3-8b", "yes", "gather + kernel",
     "— (every leaf block-paged)", "contiguous + paged", "yes"),
    ("ssm (rwkv6)", "rwkv6-3b", "yes", "gather + kernel", "rows",
     "paged only (NULL-row parking)", "no vocab-compatible drafter"),
    ("mamba (mamba2)", "mamba2-2.7b", "yes", "gather + kernel", "rows",
     "paged only (NULL-row parking)", "no vocab-compatible drafter"),
    ("hybrid (zamba2)", "zamba2-2.7b", "yes",
     "gather + kernel (shared-attn KV blocks)", "rows (conv/ssm state)",
     "paged only (NULL-row parking)", "no vocab-compatible drafter"),
    ("enc-dec (whisper)", "whisper-base", "yes",
     "gather + kernel (self-attn KV blocks)", "rows (cross KV, read-only)",
     "contiguous + paged", "no vocab-compatible drafter"),
]


def render_md(rows, arch: str, capacity: dict = None,
              state_capacity: list = None) -> str:
    lines = [
        "# The serving ladder (paper Table 1 analog for the decode engine)",
        "",
        f"Generated by `python -m benchmarks.serving_ladder` — the",
        f"`repro.serving` engine built at every OptLevel on the `{arch}`",
        "smoke config, decoding one fixed continuous-batching workload",
        f"({rows[0]['tokens']} tokens across mixed-length requests).",
        "Best-of-interleaved-rounds wall clock; see the module docstring",
        "for the methodology.  Greedy sampling: every level must generate",
        "bit-identical tokens (the serving analog of MachSuite's O0..O5",
        "output-equivalence matrix).",
        "",
        "| level | serving stage (paper step) | tok/s | tick (ms) | "
        "wall (s) | speedup vs O0 | TTFT (ms) | ITL (ms) | "
        "KV capacity (tok) | pool MB | KV bytes/tick | devices | "
        "accept % | eff tok/step | identical tokens |",
        "|---|---|---|---|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        kb = r.get("kv_bytes_per_tick")
        kb = f"{kb / 1024:.1f}K" if kb else "-"
        ttft = r.get("ttft_ms")
        itl = r.get("itl_ms")
        spec = r.get("spec_mode") == "draft"
        acc = f"{r['accept_rate'] * 100:.0f}%" if spec else "-"
        eff = f"{r['eff_tok_per_step']:.2f}" if spec else "-"
        pmb = r.get("pool_mb")
        pmb = f"{pmb:.2f}" if pmb is not None else "-"
        # bf16 rows report bit-identity; narrow-pool rows report their
        # tolerance-contract status with the measured token agreement
        if r.get("kv_dtype", "bf16") == "bf16":
            ident = "yes" if r["identical"] else "NO"
        else:
            ident = (f"{'tol ok' if r['identical'] else 'TOL FAIL'} "
                     f"({r['agreement']:.2f})")
        lines.append(
            f"| {r['label']} | {r['stage']} | {r['tok_per_s']:.0f} "
            f"| {r['tick_ms']:.3f} | {r['wall_s']:.4f} "
            f"| {r['speedup_vs_o0']:.2f}x "
            f"| {ttft:.2f} | {itl:.3f} "
            f"| {r.get('kv_capacity', '-')} "
            f"| {pmb} "
            f"| {kb} "
            f"| {r.get('devices', 1)} "
            f"| {acc} | {eff} "
            f"| {ident} |")
    # The monotonicity contract covers the mechanism rungs O0..O5 only —
    # the O6 capacity rung (and the O6+pe composition row) may
    # legitimately pay a gather/scatter toll (the note below explains
    # it), matching the harness's mono_top.
    mtop = min(5, len(rows) - 1)
    mono = all(rows[i]["tok_per_s"] >= rows[i - 1]["tok_per_s"]
               for i in range(1, mtop + 1))
    ties = [f"{r['noise_tie_with']}={r['label']}"
            for r in rows if r.get("noise_tie_with")]
    lines += [
        "",
        f"tok/s monotone non-decreasing O0->O{mtop}: "
        f"{'yes' if mono else 'NO'}; "
        f"ladder token contract (bf16 rows bit-identical, narrow-pool "
        f"rows within their tolerance contract): "
        f"{'yes' if all(r['identical'] for r in rows) else 'NO'}."
        + (f"  Ties within measurement noise (paired-delta test): "
           f"{', '.join(ties)}." if ties else ""),
    ]
    lines += [
        "",
        "TTFT/ITL are single-request latency probes on the idle warm",
        "engines (trimmed min across the interleaved rounds), measured",
        "through each engine's real prefill path — NOT wall-clock under",
        "load.  The `O5c` row is the O5 engine with chunked prefill",
        "(`prefill_chunk=16`): a prompt costs ceil(P/16) chunk ticks",
        "before its first token instead of P one-token ticks, which is",
        "the TTFT column's delta; greedy tokens stay bit-identical.",
    ]
    if any(r.get("spec_mode") == "draft" for r in rows):
        lines += [
            "",
            "The O7 row is speculative decoding: a small drafter",
            f"(`{LADDER_DRAFT['draft_model']}`) proposes",
            f"K={LADDER_DRAFT['draft_k']} tokens per slot per tick and the",
            "target verifies the whole window in ONE batched forward,",
            "accepting exactly its own argmax prefix (greedy rejection) —",
            "so tokens stay bit-identical to O5/O6 by construction.  The",
            "`accept %` / `eff tok/step` columns are the mechanism's",
            "telemetry: effective tokens per verify window is",
            "1 + accept x K.  On the smoke zoo the drafter's weights are",
            "random, acceptance is near zero, and the row shows the",
            "overhead floor; the autotuner (`--serve`, `draft_k=auto`)",
            "races K in {0,2,4,8} and keeps speculation only when it",
            "actually wins.",
        ]
    if max(r["level"] for r in rows) >= 6:
        lines += [
            "",
            "O6 runs this speed table at EQUAL worst-case capacity"
            " (auto-sized pool), so any delta vs O5 is the pure"
            " gather/scatter toll of block indirection; the rung's win is"
            " the capacity table below.  The `O6k` row is the same paged"
            " pool driven by the gather-free block-table Pallas kernel"
            " (`paged_attn=kernel`): no dense view is ever materialized,"
            " which is what the `KV bytes/tick` column shows — the gather"
            " step stages O(B x max_seq) KV bytes per tick (3x the dense"
            " view: pool read, dense write, attention read) while the"
            " kernel touches only the blocks each slot's table references"
            " (measured off a replay of the actual schedule).  The"
            " autotuner (`--serve`, `paged_attn=auto`) measures both and"
            " keeps the winner — gather on tie/loss.",
            "",
            "The `O6q` row is the same paged engine storing INT8 blocks"
            " with per-block (x per-kv-head) absmax scales"
            " (`kv_dtype=int8`): the `pool MB` column roughly halves at"
            " the same token capacity — capacity the pool can spend on"
            " ~2x the admitted concurrency at equal memory (quantized"
            " row of the capacity table below).  Quantized rungs trade"
            " the ladder's bit-identity contract for a TOLERANCE"
            " contract (`serving.kvquant.tolerance_contract`): the"
            " `identical tokens` column reports the measured greedy-token"
            " agreement against O0 and whether it clears the contract"
            " floor.  The autotuner (`--serve`, `kv_dtype=auto`) races"
            " bf16 vs int8 at equal pool memory and keeps narrow only"
            " when it wins.",
            "",
            "## Layout x placement matrix",
            "",
            "Cache layout (contiguous vs paged, `serving/layout.py`) and",
            "device placement (replicated vs PE-sharded,",
            "`parallel/sharding.PlacementPlan`) are orthogonal layers —",
            "every combination compiles a decode step, and greedy tokens",
            "are bit-identical across all four cells (dist-tier oracle in",
            "`tests/test_distributed.py`):",
            "",
            "| | replicated (pe=1 or 1 device) "
            "| PE-sharded (pe>1, >=2 devices) |",
            "|---|---|---|",
            "| contiguous (O0-O5) | process-wide shared jitted step "
            "| per-engine step; cache + tokens sharded on the batch axis |",
            "| paged (O6) | per-engine step (pool geometry is part of the "
            "program); gather -> decode -> scatter "
            "| per-engine step; pool sharded on the BLOCK axis (rows "
            "padded to a device multiple), block tables replicated, "
            "gathered dense view re-sharded onto the batch axis |",
            "| paged (O6, `paged_attn=kernel`) | per-engine step; the "
            "gather-free block-table Pallas kernel reads the pool "
            "directly (no dense view, no scatter — the current token's "
            "K/V is appended in place) "
            "| per-engine step; pool sharded on the BLOCK axis, "
            "replicated in-graph around the kernel call, written pool "
            "re-sharded by out_shardings |",
            "",
            "On a multi-device run every O3+ row shards (the `devices` "
            "column shows the placement each engine actually landed "
            "on), so the O6 row is the composed sharded-paged cell at "
            "the SAME placement as O5, and the table gains the `O6pe1` "
            "placement-ablation row — the same paged pool replicated — "
            "measured by the same interleaved trimmed-min harness.",
        ]
    if capacity:
        c, p = capacity["contiguous"], capacity["paged"]
        q = capacity.get("quantized")
        lines += [
            "",
            "## Capacity at equal KV memory (the O6 rung's actual win)",
            "",
            f"Same long-tail workload ({capacity['n_requests']} requests), "
            f"same KV memory ({capacity['kv_memory_tokens']} token "
            f"positions = {capacity['pool_blocks']} blocks of "
            f"{capacity['block_size']}):",
            "",
            "| cache | peak concurrent requests | ticks to drain | tok/s |",
            "|---|---|---|---|",
            f"| contiguous (O5, B x max_seq slots) "
            f"| {c['peak_concurrency']} | {c['ticks']} "
            f"| {c['tokens'] / c['wall_s']:.0f} |",
            f"| paged (O6, block tables) | {p['peak_concurrency']} "
            f"| {p['ticks']} | {p['tokens'] / p['wall_s']:.0f} |",
        ]
        if q:
            lines += [
                f"| paged int8 (O6, kv_dtype=int8, same pool BYTES = "
                f"{q['pool_blocks']} blocks) | {q['peak_concurrency']} "
                f"| {q['ticks']} | {q['tokens'] / q['wall_s']:.0f} |",
            ]
        lines += [
            "",
            "Greedy tokens identical between the contiguous and paged "
            f"engines: {'yes' if capacity['identical'] else 'NO'}."
            + (f"  The int8 pool holds the same bytes in ~2x the blocks "
               f"({q['pool_blocks']} vs {capacity['pool_blocks']}); its "
               f"tokens meet the int8 tolerance contract (agreement "
               f"{q['agreement']:.2f})." if q else ""),
        ]
    if state_capacity:
        lines += [
            "",
            "## Capacity at equal cache bytes — recurrent families",
            "",
            "Same short-prompt mix, equal TOTAL cache bytes (attention",
            "KV + recurrent state, leaf-summed off the device trees).",
            "Hybrid re-spends the contiguous worst-case KV slabs as",
            "block-packed reservations plus one O(1) state row per extra",
            "slot; pure-state families have no per-position cache, so",
            "equal bytes is slot parity minus the one constant NULL row",
            "(their O6 value is the uniform full-rung mechanism —",
            "kernel step, NULL-row chunk parking, defrag — not bytes):",
            "",
            "| family (arch) | cache bytes | contiguous slots -> peak | "
            "paged slots -> peak | pools |",
            "|---|---|---|---|---|",
        ]
        for sc in state_capacity:
            lines.append(
                f"| {sc['family']} (`{sc['arch']}`) "
                f"| {sc['contig_bytes'] / 1024:.0f}K "
                f"(paged uses {sc['paged_bytes'] / 1024:.0f}K) "
                f"| {sc['contig_slots']} -> "
                f"{sc['contiguous']['peak_concurrency']} "
                f"| {sc['paged_slots']} -> "
                f"{sc['paged']['peak_concurrency']} "
                f"| {sc['note']} |")
        lines += [
            "",
            "Greedy tokens identical across layouts for every family "
            "row: "
            f"{'yes' if all(s['identical'] for s in state_capacity) else 'NO'}.",
        ]
    lines += [
        "",
        "## Family x rung support matrix",
        "",
        "What each model family actually runs at each rung (recorded at",
        "engine build as `attn_impl` / `state_impl` / `degrade_reason`,",
        "asserted by the per-family differential fuzz in",
        "`tests/test_serving.py`):",
        "",
        "| family | arch | O0-O5 contiguous | O6 attention | O6 state | "
        "chunked prefill | O7 speculative |",
        "|---|---|---|---|---|---|---|",
    ]
    for fam, a, o05, attn, state, chunk, spec in FAMILY_RUNG_MATRIX:
        lines.append(f"| {fam} | `{a}` | {o05} | {attn} | {state} "
                     f"| {chunk} | {spec} |")
    return "\n".join(lines)


def write_trajectory(rows, arch: str, out_dir: str = None) -> str:
    """Mirror the rows as a JSONL file next to the autotune trajectories
    so one set of tools reads both."""
    d = out_dir or TRAJ_DIR
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"serving_ladder__{arch}.jsonl")
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return path


def _preserved_traffic_section(path: str) -> str:
    """The open-loop traffic harness (`benchmarks/traffic_harness.py`)
    owns a marker-delimited section of this file; a ladder rewrite must
    carry it over, not clobber it."""
    from benchmarks.traffic_harness import TRAFFIC_BEGIN, TRAFFIC_END
    if not os.path.exists(path):
        return ""
    text = open(path).read()
    if TRAFFIC_BEGIN not in text or TRAFFIC_END not in text:
        return ""
    return (TRAFFIC_BEGIN
            + text.split(TRAFFIC_BEGIN, 1)[1].split(TRAFFIC_END, 1)[0]
            + TRAFFIC_END)


STATE_CAPACITY_ARCHS = ("rwkv6-3b", "mamba2-2.7b", "zamba2-2.7b")


def main(arch: str = "qwen3-8b", write_md: bool = True, **kw):
    t0 = time.time()
    rows = measure_ladder(arch, **kw)
    capacity = capacity_demo(arch)
    state_caps = [capacity_demo_state(a) for a in STATE_CAPACITY_ARCHS]
    if write_md:
        traffic = _preserved_traffic_section(MD_PATH)
        with open(MD_PATH, "w") as f:
            f.write(render_md(rows, arch, capacity, state_caps) + "\n")
            if traffic:
                f.write("\n" + traffic + "\n")
        write_trajectory(rows, arch)
    out = [(f"serving_ladder_{r['label']}", r["wall_s"] * 1e6,
            f"{r['tok_per_s']:.0f}tok/s {r['speedup_vs_o0']:.2f}x "
            f"{r['layout']}"
            f"{'/' + r['paged_attn'] if r.get('paged_attn') else ''}"
            f"x{r['devices']}dev "
            f"kv={r['kv_bytes_per_tick'] // 1024}K/tick "
            f"ttft={r['ttft_ms']:.1f}ms itl={r['itl_ms']:.2f}ms "
            f"prefill={r['prefill_mode']} "
            + (f"spec=K{r['draft_k']} accept={r['accept_rate']:.2f} "
               f"eff={r['eff_tok_per_step']:.2f} "
               if r.get("spec_mode") == "draft" else "")
            + f"identical={r['identical']}") for r in rows]
    cc = capacity["contiguous"]["peak_concurrency"]
    cp = capacity["paged"]["peak_concurrency"]
    out.append(("serving_capacity_paged_vs_contig", cp * 1e6 / max(cc, 1),
                f"peak concurrency {cp} vs {cc} at equal KV memory"))
    if capacity.get("quantized"):
        cq = capacity["quantized"]["peak_concurrency"]
        out.append(("serving_capacity_int8_vs_paged",
                    cq * 1e6 / max(cp, 1),
                    f"peak concurrency {cq} vs {cp} at equal pool bytes "
                    f"(agreement "
                    f"{capacity['quantized']['agreement']:.2f})"))
    for sc in state_caps:
        sp = sc["paged"]["peak_concurrency"]
        scc = sc["contiguous"]["peak_concurrency"]
        out.append((f"serving_capacity_state_{sc['arch']}",
                    sp * 1e6 / max(scc, 1),
                    f"{sc['family']}: peak concurrency {sp} vs {scc} at "
                    f"equal cache bytes ({sc['note']})"))
    out.append(("serving_ladder_wall", (time.time() - t0) * 1e6,
                f"{len(rows)} levels x best-of-interleaved ({arch})"))
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    for name, us, derived in main():
        print(f"{name},{us:.3f},{derived}")
    print(f"wrote {MD_PATH}")
