"""Open-loop traffic harness: goodput-under-SLO curves for the serving
engine behind the async front end (`repro.launch.server`).

    PYTHONPATH=src python -m benchmarks.traffic_harness --arch qwen3-8b \
        --rates 2,5,10 --requests 24 --pattern poisson

For each arrival rate the harness replays a deterministic Poisson (or
bursty) trace at the `AsyncServer` — arrivals never wait for
completions — and records p50/p99 TTFT, p50/p99 per-token latency
(TPOT), and GOODPUT: finished requests that met both SLOs, per second.
Rows land in `experiments/traffic/traffic__<arch>.jsonl` and render as
a marker-delimited section of `benchmarks/SERVING_LADDER.md`, alongside
(never replacing) the closed-loop trimmed-min ladder.

Measurement honesty, per the ROADMAP noise memo: wall-clock under
concurrent load is noisy on this container, so these curves are for
SHAPE — how latency and goodput bend as the offered rate crosses the
engine's capacity — not for absolute speed claims; the interleaved
trimmed-min ladder remains the authoritative speed table.  The knee is
robust to noise: below capacity TTFT is flat, above it the queue grows
without bound and p99 TTFT explodes.

`--smoke` runs a tiny 3-rate sweep and then ASSERTS the written JSONL
carries every required field (the CI fast-tier contract).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax

from repro.configs import ARCH_NAMES, get_smoke
from repro.core.optlevel import BestEffortConfig, OptLevel
from repro.launch.server import latency_metrics, make_trace, serve_trace
from repro.models import get_model
from repro.serving import DecodeEngine

MD_PATH = os.path.join(os.path.dirname(__file__), "SERVING_LADDER.md")
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "traffic")
TRAFFIC_BEGIN = "<!-- traffic:begin -->"
TRAFFIC_END = "<!-- traffic:end -->"

# Every JSONL row must carry these (the CI smoke asserts it): the
# goodput-under-SLO curve is unusable if any percentile column goes
# missing silently.
REQUIRED_FIELDS = (
    "arch", "rate_rps", "pattern", "policy", "level",
    "ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s",
    "goodput_rps", "goodput_frac", "throughput_rps", "tok_per_s",
)


def build_engine(arch: str, *, level: int = 5, batch: int = 3,
                 max_seq: int = 48, policy: str = "fcfs",
                 kv_block: int = 8, prefill_chunk: int = 0,
                 seed: int = 0) -> DecodeEngine:
    cfg = get_smoke(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return DecodeEngine(
        model, params, batch_size=batch, max_seq=max_seq, policy=policy,
        config=BestEffortConfig(level=OptLevel(level),
                                kv_block_size=kv_block,
                                prefill_chunk=prefill_chunk))


def sweep(arch: str, rates, *, pattern: str = "poisson",
          n_requests: int = 24, level: int = 5, batch: int = 3,
          max_seq: int = 48, policy: str = "fcfs", seed: int = 0,
          ttft_slo_s: float = 0.5, tpot_slo_s: float = 0.1,
          prefill_chunk: int = 0) -> list:
    """One engine, one rate point at a time (drained between points, so
    nothing leaks across); speculation telemetry comes from the WINDOWED
    snapshot — per rate point, not lifetime — which is what the
    `spec_stats_window` API exists for."""
    engine = build_engine(arch, level=level, batch=batch, max_seq=max_seq,
                          policy=policy, prefill_chunk=prefill_chunk,
                          seed=seed)
    # Warm the jitted step outside the measured replays: the first tick
    # pays compile, which would otherwise land entirely on rate point 1
    # as fake TTFT.
    warm = make_trace(n_requests=2, rate=100.0, seed=seed + 999,
                      vocab=engine.model.cfg.vocab, prompt_len=(2, 5),
                      max_new=(2, 4))
    serve_trace(engine, warm, time_scale=0.0)
    engine.spec_stats_window(reset=True)

    rows = []
    for rate in rates:
        trace = make_trace(n_requests=n_requests, rate=rate, seed=seed,
                           pattern=pattern,
                           vocab=engine.model.cfg.vocab,
                           prompt_len=(2, 10),
                           max_new=(3, min(12, max_seq // 3)))
        res = serve_trace(engine, trace)
        m = latency_metrics(res["finished"], makespan_s=res["makespan_s"],
                            ttft_slo_s=ttft_slo_s, tpot_slo_s=tpot_slo_s)
        spec = engine.spec_stats_window(reset=True)
        row = {
            "arch": arch, "rate_rps": float(rate), "pattern": pattern,
            "policy": policy, "level": int(level), "batch": batch,
            "max_seq": max_seq, "ticks": res["ticks"], "seed": seed,
            **m,
            "spec_mode": spec["spec_mode"],
            "spec_accept_rate": spec["accept_rate"],
            "spec_eff_tok_per_step": spec["eff_tok_per_step"],
        }
        rows.append(row)
        print(f"[traffic] {arch} O{level}/{policy} {pattern} "
              f"rate={rate:g}/s: goodput={m['goodput_rps']:.2f}/s "
              f"({m['goodput_frac'] * 100:.0f}%) "
              f"ttft p50/p99={m['ttft_p50_s'] * 1e3:.0f}/"
              f"{m['ttft_p99_s'] * 1e3:.0f}ms "
              f"tpot p50/p99={m['tpot_p50_s'] * 1e3:.1f}/"
              f"{m['tpot_p99_s'] * 1e3:.1f}ms")
    return rows


def write_jsonl(rows, arch: str, out_dir: str = None) -> str:
    d = out_dir or OUT_DIR
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"traffic__{arch}.jsonl")
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return path


def render_section(rows, arch: str) -> str:
    """The SERVING_LADDER.md traffic section, between the markers the
    closed-loop ladder's writer preserves."""
    lines = [
        TRAFFIC_BEGIN,
        "",
        "## Open-loop traffic: goodput under SLO",
        "",
        f"Arrival-rate sweep through the asyncio front end "
        f"(`repro.launch.server`), {rows[0]['pattern']} arrivals, "
        f"policy `{rows[0]['policy']}`, O{rows[0]['level']} engine "
        f"(`{arch}` smoke weights).  SLOs: TTFT <= "
        f"{rows[0]['slo_ttft_s'] * 1e3:.0f}ms, per-token <= "
        f"{rows[0]['slo_tpot_s'] * 1e3:.0f}ms.  Goodput counts only "
        "requests meeting BOTH — raw throughput rewards a server that "
        "strands its tail.  Per the noise memo these curves are for "
        "SHAPE (where the knee is), not absolute speed; the trimmed-min "
        "closed-loop ladder above stays the speed table.",
        "",
        "| rate req/s | TTFT p50/p99 ms | TPOT p50/p99 ms "
        "| goodput req/s | good % | tok/s |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['rate_rps']:g} "
            f"| {r['ttft_p50_s'] * 1e3:.0f} / {r['ttft_p99_s'] * 1e3:.0f} "
            f"| {r['tpot_p50_s'] * 1e3:.1f} / {r['tpot_p99_s'] * 1e3:.1f} "
            f"| {r['goodput_rps']:.2f} "
            f"| {r['goodput_frac'] * 100:.0f}% "
            f"| {r['tok_per_s']:.0f} |")
    lines += [
        "",
        f"Rows mirrored to `experiments/traffic/traffic__{arch}.jsonl` "
        "(one JSON object per rate point; regenerate with "
        "`python -m benchmarks.traffic_harness`).",
        "",
        TRAFFIC_END,
    ]
    return "\n".join(lines)


def upsert_section(section: str, md_path: str = None) -> str:
    """Insert or replace the marker-delimited traffic section, leaving
    the rest of SERVING_LADDER.md (the closed-loop ladder) untouched.
    Creates a stub file when the ladder has not been rendered yet."""
    path = md_path or MD_PATH
    if os.path.exists(path):
        text = open(path).read()
    else:
        text = "# Serving ladder\n\n(closed-loop ladder not rendered yet)\n"
    if TRAFFIC_BEGIN in text and TRAFFIC_END in text:
        head = text.split(TRAFFIC_BEGIN)[0].rstrip("\n")
        tail = text.split(TRAFFIC_END, 1)[1].lstrip("\n")
        text = head + "\n\n" + section + ("\n\n" + tail if tail else "\n")
    else:
        text = text.rstrip("\n") + "\n\n" + section + "\n"
    with open(path, "w") as f:
        f.write(text)
    return path


def check_jsonl(path: str) -> None:
    """The CI contract: every row carries every required field."""
    rows = [json.loads(line) for line in open(path)]
    assert rows, f"{path} is empty"
    for r in rows:
        missing = [k for k in REQUIRED_FIELDS if k not in r]
        assert not missing, f"JSONL row missing fields {missing}: {r}"
    rates = {r["rate_rps"] for r in rows}
    assert len(rates) >= 3, \
        f"goodput curve needs >= 3 arrival rates (got {sorted(rates)})"
    print(f"[traffic] JSONL check OK: {len(rows)} rows, "
          f"{len(rates)} rates, all {len(REQUIRED_FIELDS)} fields present")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=ARCH_NAMES)
    ap.add_argument("--rates", default="2,5,10",
                    help="comma-separated arrival rates (req/s)")
    ap.add_argument("--pattern", default="poisson",
                    choices=("poisson", "bursty"))
    ap.add_argument("--requests", type=int, default=24,
                    help="requests per rate point")
    ap.add_argument("--level", type=int, default=5, choices=range(8))
    ap.add_argument("--batch", type=int, default=3)
    ap.add_argument("--max-seq", type=int, default=48)
    ap.add_argument("--policy", default="fcfs",
                    choices=("fcfs", "spf", "deadline"))
    ap.add_argument("--prefill-chunk", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ttft-slo-ms", type=float, default=500.0)
    ap.add_argument("--tpot-slo-ms", type=float, default=100.0)
    ap.add_argument("--no-md", action="store_true",
                    help="skip the SERVING_LADDER.md section update")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep + assert the JSONL contract (CI)")
    args = ap.parse_args(argv)

    rates = [float(x) for x in args.rates.split(",") if x]
    n_requests = args.requests
    if args.smoke:
        rates = rates[:3] if len(rates) >= 3 else [5.0, 20.0, 80.0]
        n_requests = min(n_requests, 8)
    if len(rates) < 3:
        raise SystemExit("need >= 3 rates for a goodput curve")

    t0 = time.time()
    rows = sweep(args.arch, rates, pattern=args.pattern,
                 n_requests=n_requests, level=args.level,
                 batch=args.batch, max_seq=args.max_seq,
                 policy=args.policy, seed=args.seed,
                 ttft_slo_s=args.ttft_slo_ms / 1e3,
                 tpot_slo_s=args.tpot_slo_ms / 1e3,
                 prefill_chunk=args.prefill_chunk)
    path = write_jsonl(rows, args.arch)
    print(f"[traffic] wrote {path} ({time.time() - t0:.1f}s)")
    if not args.no_md:
        md = upsert_section(render_section(rows, args.arch))
        print(f"[traffic] updated {md}")
    if args.smoke:
        check_jsonl(path)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    sys.exit(0 if main() else 1)
