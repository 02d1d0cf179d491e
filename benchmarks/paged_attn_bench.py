"""Microbenchmark: paged decode attention — gather step vs gather-free
kernel — across a (max_seq, block_size, batch) grid.

Each cell builds a block pool with realistic occupancy (every slot holds
a random prefix of its reservation), then times two jitted formulations
of one decode-attention tick:

  gather — materialize the dense (B, nb*T, KV, D) view from the pool
           (``jnp.take``, what ``serving/paged.BlockPagingPlan.gather``
           does every tick) and run dense masked attention on it;
  kernel — ``repro.kernels.paged_attention`` walking the block tables
           directly (O(blocks touched) KV traffic).

Methodology follows the serving-ladder noise memo: jit compiles outside
the timed region, measurement rounds interleave the two variants (so
container drift cancels), and each variant's floor is the trimmed min
(mean of its 3 fastest rounds).  Never run this under concurrent load.

Rows are appended as JSONL to ``experiments/autotune/paged_attn_bench.jsonl``
(one row per cell x variant, with the analytic bytes estimate alongside
the measured floor) so the perf trajectory tooling can track the
kernel-vs-gather frontier over time.

CPU caveat: on this container the kernel runs in Pallas interpret mode —
every grid step is emulated with traced jax ops — so its WALL-CLOCK
carries a large constant emulation toll and gather wins the stopwatch;
the ``kv_bytes_est`` column is the hardware-relevant axis (the kernel
moves O(blocks touched), the gather step O(B * max_seq)).  This is
exactly why the serving autotuner *measures* the two and keeps gather on
a tie/loss instead of assuming the kernel wins: on a real TPU
(``interpret=False``) the bytes column is the stopwatch.

  PYTHONPATH=src python -m benchmarks.paged_attn_bench
"""

import json
import os
import time

TRAJ = os.path.join(os.path.dirname(__file__), "..", "experiments",
                    "autotune", "paged_attn_bench.jsonl")

# (max_seq, block_size, batch) cells; heads/dims fixed at a small GQA
# shape so the sweep isolates the KV-traffic axes the kernel changes.
GRID = [
    (64, 8, 4), (64, 16, 4),
    (256, 16, 4), (256, 16, 8),
    (512, 16, 8), (512, 32, 8),
]
H, KV, D = 4, 2, 32


def build_cell(max_seq: int, block: int, batch: int, seed: int = 0,
               kv_dtype: str = "bf16"):
    """Pool + tables + lengths with random prefix occupancy, plus the
    per-variant jitted callables.  ``kv_dtype`` int8/fp8 stores the pool
    quantized with per-block (x per-kv-head) absmax scales: the gather
    variant dequantizes the gathered view (what
    ``serving/paged.BlockPagingPlan.gather`` does), the kernel variant
    passes the (rows, KV) scale operands and dequantizes each streamed
    block in place."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention.ops import paged_attention
    from repro.serving import kvquant

    quantized = kvquant.is_quantized(kv_dtype)
    rng = np.random.default_rng(seed)
    nb = -(-max_seq // block)
    rows = batch * nb + 1
    lengths = rng.integers(1, max_seq + 1, batch)
    tables = np.zeros((batch, nb), np.int32)
    free = list(range(1, rows))
    rng.shuffle(free)
    for b in range(batch):
        for j in range(-(-int(lengths[b]) // block)):
            tables[b, j] = free.pop()
    key = jax.random.PRNGKey(seed)
    kp, vp, q = (jax.random.normal(k, s, jnp.bfloat16) for k, s in zip(
        jax.random.split(key, 3),
        [(rows, block, KV, D), (rows, block, KV, D), (batch, H, D)]))
    tables = jnp.asarray(tables)
    lengths = jnp.asarray(lengths, jnp.int32)
    if quantized:
        ks = kvquant.block_scale(kp, (1, 3), kv_dtype)   # (rows,1,KV,1)
        vs = kvquant.block_scale(vp, (1, 3), kv_dtype)
        kp = kvquant.quantize(kp, ks, kv_dtype)
        vp = kvquant.quantize(vp, vs, kv_dtype)
        ks, vs = ks[:, 0, :, 0], vs[:, 0, :, 0]          # (rows, KV)
    else:
        ks = vs = None

    @jax.jit
    def gather_step(q, kp, vp, ks, vs, tables, lengths):
        flat = tables.reshape(-1)
        dk = jnp.take(kp, flat, axis=0)
        dv = jnp.take(vp, flat, axis=0)
        if quantized:
            sk = jnp.take(ks, flat, axis=0)[:, None, :, None]
            sv = jnp.take(vs, flat, axis=0)[:, None, :, None]
            dk = (dk.astype(jnp.float32) * sk).astype(q.dtype)
            dv = (dv.astype(jnp.float32) * sv).astype(q.dtype)
        dk = dk.reshape(batch, nb * block, KV, D)
        dv = dv.reshape(batch, nb * block, KV, D)
        qg = q.reshape(batch, KV, H // KV, D)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, dk) * (D ** -0.5)
        s = s.astype(jnp.float32)
        idx = jnp.arange(nb * block)
        s = jnp.where(idx[None, None, None, :]
                      < lengths[:, None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bkgs,bskd->bkgd", p, dv)
        return o.reshape(batch, H, D)

    @jax.jit
    def kernel_step(q, kp, vp, ks, vs, tables, lengths):
        return paged_attention(q, kp, vp, tables, lengths,
                               k_scale=ks, v_scale=vs)

    args = (q, kp, vp, ks, vs, tables, lengths)
    itemsize = 1 if quantized else 2
    tb_store = 2 * KV * D * itemsize                      # k + v, stored
    tb_compute = 2 * KV * D * 2                           # dense bf16 view
    sb = 2 * KV * 4 if quantized else 0                   # k + v scales/row
    blocks = int(sum(-(-int(x) // block) for x in lengths))
    # gather: pool read (stored bytes + scales) + dense-view write and
    # attention read (compute bytes); kernel: stream only referenced
    # blocks (stored bytes + scales) + the appended token
    gather_est = (batch * nb * (block * tb_store + sb)
                  + 2 * batch * nb * block * tb_compute)
    kernel_est = blocks * (block * tb_store + sb) + batch * tb_store
    return {
        "gather": (gather_step, args, gather_est),
        "kernel": (kernel_step, args, kernel_est),
    }


def bench(rounds: int = 7, iters: int = 20,
          kv_dtypes=("bf16",)) -> list:
    import jax

    rows = []
    for max_seq, block, batch in GRID:
        for kvd in kv_dtypes:
            variants = build_cell(max_seq, block, batch, kv_dtype=kvd)
            # warmup: compile + first-run costs outside the timed region
            for fn, args, _ in variants.values():
                jax.block_until_ready(fn(*args))
            samples = {v: [] for v in variants}
            for _ in range(rounds):
                for v, (fn, args, _) in variants.items():   # interleaved
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        out = fn(*args)
                    jax.block_until_ready(out)
                    samples[v].append((time.perf_counter() - t0) / iters)
            for v, (fn, args, est) in variants.items():
                floor = sum(sorted(samples[v])[:3]) / 3     # trimmed min
                rows.append({
                    "max_seq": max_seq, "block_size": block,
                    "batch": batch,
                    "heads": H, "kv_heads": KV, "head_dim": D,
                    "variant": v, "kv_dtype": kvd,
                    "wall_us": floor * 1e6,
                    "kv_bytes_est": int(est),
                })
    return rows


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--kv-dtype", default="bf16,int8",
                    help="comma list of pool stored dtypes to sweep "
                         "(bf16|int8|fp8); each cell x variant is "
                         "measured per dtype and the JSONL rows carry "
                         "kv_dtype + the dtype's bytes/tick estimate")
    ap.add_argument("--rounds", type=int, default=7)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args(argv)
    dtypes = tuple(d.strip() for d in args.kv_dtype.split(",") if d.strip())

    rows = bench(rounds=args.rounds, iters=args.iters, kv_dtypes=dtypes)
    os.makedirs(os.path.dirname(TRAJ), exist_ok=True)
    with open(TRAJ, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    by_cell = {}
    for r in rows:
        by_cell.setdefault(
            (r["max_seq"], r["block_size"], r["batch"], r["kv_dtype"]),
            {})[r["variant"]] = r
    print("max_seq block batch kv_dtype | gather_us kernel_us speedup | "
          "gather_KB kernel_KB")
    for (ms, bl, ba, kvd), cell in sorted(by_cell.items()):
        g, k = cell["gather"], cell["kernel"]
        print(f"{ms:7d} {bl:5d} {ba:5d} {kvd:>8s} | {g['wall_us']:9.1f} "
              f"{k['wall_us']:9.1f} {g['wall_us'] / k['wall_us']:7.2f}x | "
              f"{g['kv_bytes_est'] / 1024:9.1f} "
              f"{k['kv_bytes_est'] / 1024:9.1f}")
    print(f"wrote {os.path.relpath(TRAJ)}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
