"""Public wrapper: the best-effort ladder for the TPU matmul kernel.

``matmul(a, b, level)`` dispatches per OptLevel (see kernel.py header).
Block sizes follow the paper's guidance: MXU-aligned (multiples of 128 on
real shapes; the helpers degrade gracefully for small test shapes), with a
VMEM budget feedback rule at O4 (two in-flight buffers per stream must fit
— the "shrink the cache size" feedback of paper §6).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.hw import TPU_V5E
from repro.core.optlevel import OptLevel
from repro.kernels.backend import resolve_interpret
from repro.kernels.tiled_matmul.kernel import matmul_pallas, matmul_whole

# VMEM working budget per core we allow kernels to claim (half of 128 MB,
# leaving room for the pipeline's metadata/semaphores).
VMEM_BUDGET = TPU_V5E.vmem_bytes // 2


def _fit(dim: int, want: int) -> int:
    """Largest divisor of ``dim`` that is <= want (prefers want itself)."""
    want = min(dim, want)
    for c in range(want, 0, -1):
        if dim % c == 0:
            return c
    return 1


def pick_blocks(M: int, N: int, K: int, *, level: OptLevel,
                elem_bytes: int = 4) -> tuple:
    """(bm, bn, bk) per the ladder's resource rules."""
    bm = _fit(M, 256)
    bn = _fit(N, 256)
    bk = _fit(K, 512)
    n_buf = 2 if level >= OptLevel.O4 else 1   # double buffering in flight
    while n_buf * elem_bytes * (bm * bk + bk * bn + bm * bn) > VMEM_BUDGET:
        # shrink the largest contributor first (paper: shrink cache size,
        # spare BRAM for other strategies)
        if bk >= max(bm, bn) and bk > 1:
            bk = _fit(K, bk // 2)
        elif bm >= bn and bm > 1:
            bm = _fit(M, bm // 2)
        elif bn > 1:
            bn = _fit(N, bn // 2)
        else:
            break
    return bm, bn, bk


def matmul(a, b, level: OptLevel = OptLevel.O5, *, interpret=None,
           blocks: tuple = None):
    """Best-effort blocked matmul.  Returns float32 (M, N)."""
    level = OptLevel(level)
    interpret = resolve_interpret(interpret)
    M, K = a.shape
    _, N = b.shape

    if level == OptLevel.O0:
        return matmul_whole(a, b, interpret=interpret)

    if level >= OptLevel.O5:          # scratchpad reorg: bf16 lane packing
        a = a.astype(jnp.bfloat16)
        b = b.astype(jnp.bfloat16)
        elem = 2
    else:
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        elem = 4

    bm, bn, bk = blocks or pick_blocks(M, N, K, level=level, elem_bytes=elem)
    if level == OptLevel.O1:
        return matmul_pallas(a, b, bm=bm, bn=bn, bk=K, split_k=False,
                             parallel_mn=False, interpret=interpret)
    return matmul_pallas(
        a, b, bm=bm, bn=bn, bk=bk, split_k=True,
        parallel_mn=(level >= OptLevel.O3), interpret=interpret)
