"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel is a subpackage with the repo-standard triple:

  kernel.py — ``pl.pallas_call`` + explicit ``BlockSpec`` VMEM tiling
  ops.py    — the jit'd public wrapper (shape plumbing, level knobs)
  ref.py    — the pure-jnp oracle the tests assert against

Kernels target the TPU (BlockSpec shapes chosen for VMEM/MXU).  Every
``ops`` wrapper takes ``interpret=None`` and resolves it by backend
(``backend.resolve_interpret``): compiled with Mosaic when JAX's default
backend is a TPU, run by the Pallas interpreter on any other backend —
which is how the CPU test suite executes the kernel bodies.
``tests/test_chip_compile.py`` compiles the paged kernels for a
described v5e chip at qwen3-8b widths, so a tiling the chip's compiler
refuses fails the suite without a chip.

Kernels:

  paged_attention — block-table-aware decode / chunked-prefill attention
                    straight off the paged KV pool (the served O6 step)

The four below reproduce the paper's ladder on kernels; no model or
serving path calls them, only their tests do:

  tiled_matmul    — the paper's Fig. 4 ladder transplanted to a TPU matmul:
                    block staging (O1), grid software pipelining (O2),
                    parallel tile grid (O3), double-buffer-aware block
                    sizing (O4), bf16 lane packing w/ f32 accum (O5)
  flash_attention — blocked causal attention (online softmax), the
                    data-caching + pipelining steps applied to attention
  rwkv6_wkv       — RWKV-6 chunked WKV recurrence (state in VMEM scratch,
                    chunk grid = the load-compute-store rotation)
  mamba2_ssd      — Mamba-2 SSD chunked scan, same structure
"""

from repro.kernels.tiled_matmul import ops as tiled_matmul  # noqa: F401
from repro.kernels.flash_attention import ops as flash_attention  # noqa: F401
from repro.kernels.rwkv6_wkv import ops as rwkv6_wkv  # noqa: F401
from repro.kernels.mamba2_ssd import ops as mamba2_ssd  # noqa: F401
