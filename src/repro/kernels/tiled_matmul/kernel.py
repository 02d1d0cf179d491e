"""Blocked matmul Pallas kernel — the paper's Fig. 4 ladder on the MXU.

The five refinement steps map onto kernel structure like this (DESIGN.md §2):

  O0  no tiling: one grid step, whole operands as the "block" (the naive
      compute-against-HBM architecture; only legal for small shapes)
  O1  explicit data caching: (bm, bk) x (bk, bn) BlockSpec tiles staged in
      VMEM, one output tile per grid step, K walked whole
  O2  customized pipelining: K split into bk-blocks on the innermost grid
      dim with an f32 VMEM accumulator — the Mosaic grid pipeliner overlaps
      DMA-in / MXU / DMA-out across steps (the II=1 analog)
  O3  PE duplication: (M, N) tile grid marked "parallel" dimension
      semantics (tiles land on independent compute units / cores)
  O4  double buffering: Mosaic multiple-buffers grid streams automatically;
      the programmer-visible knob is block sizing so TWO in-flight copies of
      every stream fit VMEM — ops.py halves blocks at O4 (paper §6: shrink
      the cache, keep the overlap)
  O5  scratchpad reorganization: bf16 operand staging (2 values per 32-bit
      lane word) with f32 accumulation scratch

All variants share this one kernel body; ops.py picks grid/specs per level.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel_noacc(a_ref, b_ref, o_ref):
    """O0/O1: single K-pass per output tile, no carried accumulator."""
    o_ref[...] = jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=o_ref.dtype
    )


def _matmul_kernel_acc(a_ref, b_ref, o_ref, acc_ref):
    """O2+: K on the innermost grid dim, f32 accumulator in VMEM scratch."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "split_k", "parallel_mn",
                     "interpret"),
)
def matmul_pallas(a, b, *, bm: int, bn: int, bk: int, split_k: bool,
                  parallel_mn: bool, interpret: bool):
    """Blocked a @ b.  a: (M, K), b: (K, N) -> (M, N) float32.

    ``split_k=False`` -> O1 structure (K whole per tile);
    ``split_k=True``  -> O2+ structure (K blocked + VMEM accumulator).
    ``parallel_mn``   -> O3+: mark the (M, N) tile grid parallel.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (a.shape, b.shape,
                                                         (bm, bn, bk))
    out_shape = jax.ShapeDtypeStruct((M, N), jnp.float32)

    if not split_k:
        grid = (M // bm, N // bn)
        sem = ("parallel", "parallel") if parallel_mn else None
        kw = {}
        if sem and not interpret:
            kw["compiler_params"] = pltpu.CompilerParams(
                dimension_semantics=sem)
        return pl.pallas_call(
            _matmul_kernel_noacc,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
                pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=out_shape,
            interpret=interpret,
            **kw,
        )(a, b)

    grid = (M // bm, N // bn, K // bk)
    sem = (("parallel", "parallel", "arbitrary") if parallel_mn
           else ("arbitrary", "arbitrary", "arbitrary"))
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=sem)
    return pl.pallas_call(
        _matmul_kernel_acc,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        **kw,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("interpret",))
def matmul_whole(a, b, *, interpret: bool):
    """O0: one grid step, whole operands — no explicit caching."""
    M, K = a.shape
    _, N = b.shape
    return pl.pallas_call(
        _matmul_kernel_noacc,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((M, K), lambda i: (0, 0)),
            pl.BlockSpec((K, N), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((M, N), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(a, b)
