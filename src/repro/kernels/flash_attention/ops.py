"""Public wrapper: GQA-aware flash attention entry point."""

from __future__ import annotations

from repro.kernels.backend import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_pallas


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret=None):
    """q: (B, S, H, D); k, v: (B, S_kv, Hkv, D) with H % Hkv == 0 and
    S_kv >= S.

    Returns (B, S, H, D).  GQA is resolved on the kernel grid (each q
    stream's block-index map points at its kv group's stream) — K/V are
    flattened to (B*Hkv, S_kv, D) as-is, never repeated to H first, so
    GQA models stop copying KV ``H/Hkv``x before every call.  With
    S_kv > S the causal mask shifts by ``S_kv - S`` (chunked prefill:
    the last S kv positions ARE the queries).
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    assert H % Hkv == 0, (H, Hkv)

    def to_flat(t):
        _, s, h, _ = t.shape
        return t.transpose(0, 2, 1, 3).reshape(B * h, s, D)

    out = flash_attention_pallas(
        to_flat(q), to_flat(k), to_flat(v), causal=causal,
        block_q=block_q, block_k=block_k,
        interpret=resolve_interpret(interpret),
        n_heads=H, n_kv_heads=Hkv)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
