"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

One grid step = one (batch, chunk) cell: stages x (Q, H, P), dt (Q, H),
B/C (Q, N) in VMEM, computes the intra-chunk dense block on the MXU, and
carries the (H, P, N) SSM state across the sequential chunk dim in VMEM
scratch.  Matches ``repro.models.mamba2.ssd_chunked``'s math f32-for-f32.

VMEM sizing (the explicit-data-caching design choice): with the zamba2
config (H=80, P=64, N=64) the state is 80*64*64*4 B = 1.25 MB, one chunk
of x at Q=256 is 256*80*64*4 B = 5 MB — comfortably inside the 64 MB
working budget with double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref,
                y_ref, sf_ref, state_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    x_c = x_ref[0].astype(jnp.float32)            # (Q, H, P)
    dt_c = dt_ref[0].astype(jnp.float32)          # (Q, H)
    A = a_ref[0].astype(jnp.float32)              # (1, H) negative
    B_c = b_ref[0].astype(jnp.float32)            # (Q, N)
    C_c = c_ref[0].astype(jnp.float32)            # (Q, N)

    la = dt_c * A                                 # (Q, H), <= 0
    cum = jnp.cumsum(la, axis=0)                  # (Q, H)

    seg = cum[:, None, :] - cum[None, :, :]       # (Q, Q, H)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where((ii >= jj)[..., None], jnp.exp(seg), 0.0)  # (Q, Q, H)
    CB = jax.lax.dot_general(C_c, B_c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    xdt = x_c * dt_c[..., None]                   # (Q, H, P)
    y_diag = jnp.einsum("ij,ijh,jhp->ihp", CB, L, xdt)

    state = state_ref[...]                        # (H, P, N)
    out_decay = jnp.exp(cum)                      # (Q, H)
    y_off = jnp.einsum("in,hpn,ih->ihp", C_c, state, out_decay)
    y_ref[0] = (y_diag + y_off).astype(y_ref.dtype)

    decay_states = jnp.exp(cum[-1:, :] - cum)     # (Q, H)
    st_c = jnp.einsum("jn,jh,jhp->hpn", B_c, decay_states, xdt)
    chunk_decay = jnp.exp(cum[-1, :])             # (H,)
    state_ref[...] = state * chunk_decay[:, None, None] + st_c

    @pl.when(ci == pl.num_programs(1) - 1)
    def _done():
        sf_ref[0] = state_ref[...].astype(sf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A, Bs, Cs, s0, *, chunk: int = 128,
               interpret: bool):
    """x: (B, S, H, P); dt: (B, S, H); A: (H,); Bs, Cs: (B, S, N);
    s0: (B, H, P, N) f32.

    Returns (y (B,S,H,P) same dtype as x, final_state (B,H,P,N) f32).
    """
    Bsz, S, H, P = x.shape
    N = Bs.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    grid = (Bsz, S // chunk)
    A2 = jnp.broadcast_to(A[None, :], (Bsz, H))

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))

    y, sf = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, chunk, H), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, H), lambda b, c: (b, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, H, P, N), lambda b, c: (b, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, H, P, N), lambda b, c: (b, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, S, H, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        interpret=interpret,
        **kw,
    )(x, dt, A2, Bs, Cs, s0)
    return y, sf
