"""RWKV-6 chunked WKV recurrence as a Pallas TPU kernel.

The recurrence  S_t = diag(w_t) S_{t-1} + k_t^T v_t,  y_t = r_t (S_{t-1} +
u k_t^T v_t)  is evaluated chunk-by-chunk: each grid step stages one
(Q, N) chunk of r/k/v/log-decay in VMEM (explicit data caching), computes
the intra-chunk part as dense (Q,Q)/(Q,N) MXU matmuls, and carries the
(N, N) state in VMEM scratch across the sequential chunk dim (the
load-compute-store rotation over a *recurrence*).  (B*H) is the parallel
grid dim.

Matches ``repro.models.rwkv6.wkv_chunked`` exactly (same clamp convention:
lw is log-decay already clamped to [-LW_CLAMP, 0] by the caller).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                y_ref, sf_ref, state_ref, *, chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    r_c = r_ref[0].astype(jnp.float32)            # (Q, N)
    k_c = k_ref[0].astype(jnp.float32)
    v_c = v_ref[0].astype(jnp.float32)
    lw_c = lw_ref[0].astype(jnp.float32)          # log-decay, <= 0
    u = u_ref[0].astype(jnp.float32)              # (1, N) bonus

    cum = jnp.cumsum(lw_c, axis=0)                # (Q, N)
    ri = r_c * jnp.exp(cum - lw_c)                # r_i * exp(cum_{i-1})
    kj = k_c * jnp.exp(-cum)

    # A[i, j] = <ri_i, kj_j> for j < i (strictly causal)
    A = jax.lax.dot_general(ri, kj, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    A = jnp.where(jj < ii, A, 0.0)

    diag = jnp.sum(r_c * u * k_c, axis=1, keepdims=True)         # (Q, 1)
    state = state_ref[...]                                       # (N, N)
    y = (jax.lax.dot_general(A, v_c, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + diag * v_c
         + jax.lax.dot_general(ri, state, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32))
    y_ref[0] = y.astype(y_ref.dtype)

    # state update to end of chunk
    decay_k = jnp.exp(cum[-1:] - cum)                            # (Q, N)
    st_c = jax.lax.dot_general(k_c * decay_k, v_c,
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (N, N)
    total_decay = jnp.exp(cum[-1])                               # (N,)
    state_ref[...] = state * total_decay[:, None] + st_c

    @pl.when(c == pl.num_programs(1) - 1)
    def _done():
        sf_ref[0] = state_ref[...].astype(sf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas(r, k, v, lw, u, s0, *, chunk: int = 128,
               interpret: bool):
    """r,k,v,lw: (BH, S, N); u: (BH, N); s0: (BH, N, N) f32.

    Returns (y (BH, S, N) same dtype as r, final_state (BH, N, N) f32).
    """
    BH, S, N = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    grid = (BH, S // chunk)

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))

    y, sf = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N), lambda b, c: (b, 0)),
            pl.BlockSpec((1, N, N), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, N), r.dtype),
            jax.ShapeDtypeStruct((BH, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
        **kw,
    )(r, k, v, lw, u, s0)
    return y, sf
