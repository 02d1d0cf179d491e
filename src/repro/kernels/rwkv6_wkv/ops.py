"""Public wrapper: (B, S, H, N) layout -> kernel's flat (B*H, S, N)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.rwkv6_wkv.kernel import wkv_pallas


def wkv(r, k, v, lw, u, *, init_state=None, chunk: int = 128,
        interpret=None):
    """Drop-in for ``models.rwkv6.wkv_chunked``.

    r,k,v,lw: (B, S, H, N); u: (H, N).
    Returns (y (B,S,H,N), final_state (B,H,N,N) f32).
    """
    B, S, H, N = r.shape
    flat = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    u_f = jnp.broadcast_to(u, (B, H, N)).reshape(B * H, N)
    s0 = (init_state if init_state is not None
          else jnp.zeros((B, H, N, N), jnp.float32))
    s0_f = s0.reshape(B * H, N, N).astype(jnp.float32)

    y, sf = wkv_pallas(flat(r), flat(k), flat(v), flat(lw), u_f, s0_f,
                       chunk=chunk, interpret=resolve_interpret(interpret))
    y = y.reshape(B, H, S, N).transpose(0, 2, 1, 3)
    return y, sf.reshape(B, H, N, N)
