"""Public wrapper for the SSD kernel (drop-in for models.mamba2.ssd_chunked)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.mamba2_ssd.kernel import ssd_pallas


def ssd(xh, dt, A, Bs, Cs, *, init_state=None, chunk: int = 128,
        interpret=None):
    """xh: (B, S, H, P); dt: (B, S, H) post-softplus; A: (H,) negative;
    Bs, Cs: (B, S, N).  Returns (y, final_state (B,H,P,N) f32)."""
    B, S, H, P = xh.shape
    N = Bs.shape[-1]
    s0 = (init_state if init_state is not None
          else jnp.zeros((B, H, P, N), jnp.float32)).astype(jnp.float32)
    return ssd_pallas(xh, dt, jnp.asarray(A, jnp.float32), Bs, Cs, s0,
                      chunk=chunk, interpret=resolve_interpret(interpret))
