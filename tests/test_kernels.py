"""Pallas kernel sweeps: shapes x dtypes vs pure-jnp oracles
(on the CPU the wrappers pick interpret mode, which executes the kernel
bodies there)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.optlevel import OptLevel
from repro.kernels.tiled_matmul.ops import matmul, pick_blocks
from repro.kernels.tiled_matmul.ref import matmul_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention.ops import (paged_attention,
                                               paged_prefill_attention)
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_prefill_attention_ref)
from repro.kernels.rwkv6_wkv.ops import wkv
from repro.kernels.rwkv6_wkv.ref import wkv_ref
from repro.kernels.mamba2_ssd.ops import ssd
from repro.kernels.mamba2_ssd.ref import ssd_ref

KEYS = jax.random.split(jax.random.PRNGKey(42), 8)


# ---------------------------------------------------------------------------
# tiled matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(32, 32, 32), (64, 96, 128),
                                   (128, 64, 32), (48, 80, 112)])
@pytest.mark.parametrize("lvl", range(6))
def test_matmul_levels(shape, lvl):
    M, K, N = shape
    a = jax.random.normal(KEYS[0], (M, K), jnp.float32)
    b = jax.random.normal(KEYS[1], (K, N), jnp.float32)
    ref = matmul_ref(a, b)
    out = matmul(a, b, OptLevel(lvl))
    tol = 3e-2 if lvl >= 5 else 1e-5   # bf16 packing at O5
    rel = float(jnp.max(jnp.abs(out - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < tol, (shape, lvl, rel)


def test_matmul_explicit_blocks():
    a = jax.random.normal(KEYS[2], (64, 64), jnp.float32)
    b = jax.random.normal(KEYS[3], (64, 64), jnp.float32)
    ref = matmul_ref(a, b)
    for blocks in [(16, 16, 16), (32, 64, 16), (64, 64, 64)]:
        out = matmul(a, b, OptLevel.O3, blocks=blocks)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_pick_blocks_vmem_budget():
    from repro.kernels.tiled_matmul.ops import VMEM_BUDGET
    for level in (OptLevel.O2, OptLevel.O4):
        bm, bn, bk = pick_blocks(4096, 4096, 4096, level=level)
        n_buf = 2 if level >= OptLevel.O4 else 1
        assert n_buf * 4 * (bm * bk + bk * bn + bm * bn) <= VMEM_BUDGET
    # O4 blocks never exceed O2 blocks (double buffering halves the budget)
    o2 = pick_blocks(4096, 4096, 4096, level=OptLevel.O2)
    o4 = pick_blocks(4096, 4096, 4096, level=OptLevel.O4)
    assert all(x4 <= x2 for x4, x2 in zip(o4, o2))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _attn_ref_gqa(q, k, v, causal):
    B, S, H, D = q.shape
    rep = H // k.shape[2]
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    tf = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    out = attention_ref(tf(q), tf(kr), tf(vr), causal=causal)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dims", [(1, 64, 2, 2, 16), (2, 128, 4, 2, 32),
                                  (1, 128, 3, 1, 64)])
def test_flash_attention(dims, causal):
    B, S, H, Hkv, D = dims
    q = jax.random.normal(KEYS[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(KEYS[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(KEYS[2], (B, S, Hkv, D), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = _attn_ref_gqa(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("blocks", [(16, 64), (64, 16), (128, 128)])
def test_flash_attention_block_invariance(blocks):
    bq, bk = blocks
    B, S, H, D = 1, 128, 2, 16
    q = jax.random.normal(KEYS[3], (B, S, H, D), jnp.float32)
    k = jax.random.normal(KEYS[4], (B, S, H, D), jnp.float32)
    v = jax.random.normal(KEYS[5], (B, S, H, D), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    ref = _attn_ref_gqa(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    B, S, H, D = 1, 64, 2, 32
    q = jax.random.normal(KEYS[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(KEYS[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(KEYS[2], (B, S, H, D), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = _attn_ref_gqa(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.06, atol=0.03)


def test_flash_attention_gqa_no_repeat_bitwise_matches_repeated():
    """The GQA fix: the per-KV-head grid (k-block index maps pointing at
    the kv group's stream) must be BITWISE identical to feeding the
    kernel explicitly repeated K/V — same per-stream compute, minus the
    H/Hkv materialized copies the old wrapper paid before every call."""
    for B, S, H, Hkv, D in [(2, 64, 4, 2, 16), (1, 128, 6, 2, 32),
                            (2, 64, 4, 1, 16)]:
        q = jax.random.normal(KEYS[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(KEYS[1], (B, S, Hkv, D), jnp.float32)
        v = jax.random.normal(KEYS[2], (B, S, Hkv, D), jnp.float32)
        rep = H // Hkv
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        ref = flash_attention(q, jnp.repeat(k, rep, 2),
                              jnp.repeat(v, rep, 2),
                              block_q=32, block_k=32)
        assert np.array_equal(np.asarray(out), np.asarray(ref)), (B, H, Hkv)


# ---------------------------------------------------------------------------
# paged decode attention (block-table-aware, gather-free)
# ---------------------------------------------------------------------------

def _paged_case(B, H, KV, D, T, nb, *, extra_rows=2, dtype=jnp.float32,
                seed=1, full_lengths=False):
    """Random pool/tables/lengths with real blocks covering each slot's
    valid prefix and NULL (row 0) entries past it — the allocator's
    invariant.  ``extra_rows`` leaves unreferenced pool rows (the padded
    rows a sharded placement adds) holding garbage that must not leak."""
    r = np.random.default_rng(seed)
    lengths = (np.full(B, nb * T) if full_lengths
               else r.integers(1, nb * T + 1, B))
    R = 1 + B * nb + extra_rows
    kp = r.normal(size=(R, T, KV, D)).astype(np.float32)
    vp = r.normal(size=(R, T, KV, D)).astype(np.float32)
    tables = np.zeros((B, nb), np.int32)
    free = list(range(1, R))
    r.shuffle(free)
    for b in range(B):
        for j in range(-(-int(lengths[b]) // T)):
            tables[b, j] = free.pop()
    q = r.normal(size=(B, H, D)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(kp, dtype),
            jnp.asarray(vp, dtype), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("dims", [
    (3, 4, 2, 16, 4, 8),     # GQA, partial final blocks
    (2, 2, 2, 32, 8, 4),     # MHA
    (1, 3, 1, 16, 4, 3),     # single kv head, odd group
    (4, 8, 2, 16, 16, 2),    # wide groups, big blocks
])
def test_paged_attention_vs_ref(dims):
    q, kp, vp, tables, lengths = _paged_case(*dims)
    out = paged_attention(q, kp, vp, tables, lengths)
    ref = paged_attention_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_paged_attention_full_lengths_and_block_invariance():
    """Full sequences (no partial block) agree with the ref, and the
    same logical content paged at different block sizes agrees with
    itself (block size is layout, not math)."""
    q, kp, vp, tables, lengths = _paged_case(2, 4, 2, 16, 4, 8,
                                             full_lengths=True)
    out = paged_attention(q, kp, vp, tables, lengths)
    ref = paged_attention_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)

    # repage T=4 content into T=8 blocks: dense views identical
    B, nb, T = 2, 8, 4
    dense_k = np.asarray(kp)[np.asarray(tables)].reshape(B, nb * T, 2, 16)
    dense_v = np.asarray(vp)[np.asarray(tables)].reshape(B, nb * T, 2, 16)
    kp2 = np.concatenate([np.zeros((1, 8, 2, 16), np.float32),
                          dense_k.reshape(B * 4, 8, 2, 16)])
    vp2 = np.concatenate([np.zeros((1, 8, 2, 16), np.float32),
                          dense_v.reshape(B * 4, 8, 2, 16)])
    tables2 = np.arange(1, B * 4 + 1, dtype=np.int32).reshape(B, 4)
    out2 = paged_attention(q, jnp.asarray(kp2), jnp.asarray(vp2),
                           jnp.asarray(tables2), lengths)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                               rtol=2e-4, atol=2e-5)


def test_paged_attention_bf16():
    q, kp, vp, tables, lengths = _paged_case(3, 4, 2, 16, 4, 6,
                                             dtype=jnp.bfloat16)
    out = paged_attention(q, kp, vp, tables, lengths)
    ref = paged_attention_ref(q, kp, vp, tables, lengths)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0.06, atol=0.03)


@pytest.mark.parametrize("kvd", ["int8", "fp8"])
def test_paged_attention_quantized_matches_dequantized_pool(kvd):
    """A narrow pool + (rows, KV) scale operands: the kernel's in-stream
    dequant applies the SAME expression the gather path uses on its
    dense view, so the output must be bitwise identical to calling the
    kernel on the explicitly pre-dequantized pool with no scales."""
    from repro.serving import kvquant

    q, kp, vp, tables, lengths = _paged_case(3, 4, 2, 16, 4, 6,
                                             dtype=jnp.bfloat16)
    ks = kvquant.block_scale(kp, (1, 3), kvd)
    vs = kvquant.block_scale(vp, (1, 3), kvd)
    kq = kvquant.quantize(kp, ks, kvd)
    vq = kvquant.quantize(vp, vs, kvd)
    out = paged_attention(q, kq, vq, tables, lengths,
                          k_scale=ks[:, 0, :, 0], v_scale=vs[:, 0, :, 0])
    wide = paged_attention(q, kvquant.dequantize(kq, ks),
                           kvquant.dequantize(vq, vs), tables, lengths)
    assert out.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(wide, np.float32))
    # and it stays close to the full-precision pool's answer
    ref = paged_attention_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0.1, atol=0.05)
    # scale operands are validated: wrong shape fails loudly
    with pytest.raises(ValueError, match="scale"):
        paged_attention(q, kq, vq, tables, lengths,
                        k_scale=ks[:, 0, :, 0].T, v_scale=vs[:, 0, :, 0])


def test_paged_attention_null_block_garbage_never_leaks():
    """Mutating the NULL block (row 0) and every unreferenced pool row
    must not change any output — the length mask plus the in-range block
    skip are what make paging safe."""
    q, kp, vp, tables, lengths = _paged_case(3, 4, 2, 16, 4, 6, seed=9)
    out = np.asarray(paged_attention(q, kp, vp, tables, lengths))
    referenced = set()
    for b in range(3):
        for j in range(-(-int(lengths[b]) // 4)):
            referenced.add(int(tables[b, j]))
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for row in range(kp2.shape[0]):
        if row not in referenced:
            kp2[row] = 1e3
            vp2[row] = -1e3
    out2 = np.asarray(paged_attention(q, jnp.asarray(kp2),
                                      jnp.asarray(vp2), tables, lengths))
    assert np.array_equal(out, out2)


def test_paged_attention_rejects_bad_shapes():
    q, kp, vp, tables, lengths = _paged_case(2, 3, 2, 16, 4, 4)
    with pytest.raises(ValueError, match="multiple"):
        paged_attention(q, kp, vp, tables, lengths)   # 3 heads, 2 kv
    q, kp, vp, tables, lengths = _paged_case(2, 4, 2, 16, 4, 4)
    with pytest.raises(ValueError, match="mismatch"):
        paged_attention(q, kp, vp[..., :8], tables, lengths)


# ---------------------------------------------------------------------------
# paged prefill attention (qlen > 1: the chunked-prefill query mode)
# ---------------------------------------------------------------------------

def _paged_prefill_case(B, H, KV, D, T, nb, Q, *, extra_rows=2,
                        dtype=jnp.float32, seed=3):
    """Random pool/tables with Q consecutive query tokens per slot whose
    K/V are already appended: lengths = start + Q with random starts, so
    final blocks are partially filled and earlier chunks' history is in
    the pool.  Real blocks cover each slot's valid prefix; NULL (row 0)
    past it; ``extra_rows`` unreferenced garbage rows."""
    r = np.random.default_rng(seed)
    starts = r.integers(0, nb * T - Q + 1, B)
    lengths = starts + Q
    R = 1 + B * nb + extra_rows
    kp = r.normal(size=(R, T, KV, D)).astype(np.float32)
    vp = r.normal(size=(R, T, KV, D)).astype(np.float32)
    tables = np.zeros((B, nb), np.int32)
    free = list(range(1, R))
    r.shuffle(free)
    for b in range(B):
        for j in range(-(-int(lengths[b]) // T)):
            tables[b, j] = free.pop()
    q = r.normal(size=(B, Q, H, D)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(kp, dtype),
            jnp.asarray(vp, dtype), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("dims", [
    (3, 4, 2, 16, 4, 8, 5),    # GQA, Q coprime with T: rows cross blocks
    (2, 2, 2, 32, 8, 4, 8),    # MHA, Q == T
    (1, 3, 1, 16, 4, 3, 2),    # single kv head, odd group
    (2, 8, 2, 16, 16, 2, 11),  # big blocks, Q > T/2, partial final block
])
def test_paged_prefill_attention_vs_ref(dims):
    q, kp, vp, tables, lengths = _paged_prefill_case(*dims)
    out = paged_prefill_attention(q, kp, vp, tables, lengths)
    ref = paged_prefill_attention_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


from hypothesis import given, settings, strategies as st  # noqa: E402


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_paged_prefill_random_shapes(seed):
    """Random (qlen, kv_len, block size, GQA group) draws against the
    dense oracle — the shapes the chunked-prefill engine actually emits
    (arbitrary starts, partial final blocks, ragged per-slot lengths)."""
    r = np.random.default_rng(seed)
    B = int(r.integers(1, 4))
    KV = int(r.integers(1, 3))
    G = int(r.integers(1, 4))
    D = int(r.choice([8, 16]))
    T = int(r.integers(2, 9))
    nb = int(r.integers(2, 6))
    Q = int(r.integers(1, min(8, nb * T) + 1))
    q, kp, vp, tables, lengths = _paged_prefill_case(
        B, KV * G, KV, D, T, nb, Q, seed=seed + 1)
    out = paged_prefill_attention(q, kp, vp, tables, lengths)
    ref = paged_prefill_attention_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5,
        err_msg=f"B={B} KV={KV} G={G} D={D} T={T} nb={nb} Q={Q}")


def test_paged_prefill_qlen1_bitwise_matches_decode():
    """Q == 1 must degenerate BIT-EXACTLY to the decode kernel: the
    engine's bit-identity contract rides on the prefill path's final
    token computing the same floats the per-token path would."""
    for dims in [(3, 4, 2, 16, 4, 8), (2, 2, 2, 32, 8, 4),
                 (1, 3, 1, 16, 4, 3)]:
        q, kp, vp, tables, lengths = _paged_case(*dims, seed=5)
        dec = paged_attention(q, kp, vp, tables, lengths)
        pre = paged_prefill_attention(q[:, None], kp, vp, tables, lengths)
        assert np.array_equal(np.asarray(pre[:, 0]), np.asarray(dec)), dims


def test_paged_prefill_null_and_future_garbage_never_leaks():
    """Mutating every pool row outside each slot's valid prefix — NULL,
    unreferenced rows, AND positions past ``lengths`` inside referenced
    final blocks — must not change any output row: the per-row causal
    limit is what makes writing a whole chunk before reading it safe."""
    q, kp, vp, tables, lengths = _paged_prefill_case(3, 4, 2, 16, 4, 6, 5,
                                                     seed=11)
    out = np.asarray(paged_prefill_attention(q, kp, vp, tables, lengths))
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    T = kp2.shape[1]
    referenced = {}
    for b in range(3):
        for j in range(-(-int(lengths[b]) // T)):
            row = int(tables[b, j])
            valid = min(int(lengths[b]) - j * T, T)
            referenced[row] = max(referenced.get(row, 0), valid)
    for row in range(kp2.shape[0]):
        vfrom = referenced.get(row, 0)
        kp2[row, vfrom:] = 1e3
        vp2[row, vfrom:] = -1e3
    out2 = np.asarray(paged_prefill_attention(q, jnp.asarray(kp2),
                                              jnp.asarray(vp2), tables,
                                              lengths))
    assert np.array_equal(out, out2)


def test_paged_prefill_bf16():
    q, kp, vp, tables, lengths = _paged_prefill_case(2, 4, 2, 16, 4, 6, 5,
                                                     dtype=jnp.bfloat16)
    out = paged_prefill_attention(q, kp, vp, tables, lengths)
    ref = paged_prefill_attention_ref(q, kp, vp, tables, lengths)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0.06, atol=0.03)


def test_flash_attention_rectangular_prefill_offset():
    """S_kv > S (chunked prefill against a dense cache): the causal mask
    shifts by ``S_kv - S`` — query row qi attends kv positions
    <= offset + qi — and S_kv == S stays the plain square case."""
    B, H, Hkv, D = 2, 4, 2, 16
    for S_kv, S in [(64, 16), (48, 48), (96, 32)]:
        q = jax.random.normal(KEYS[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(KEYS[1], (B, S_kv, Hkv, D), jnp.float32)
        v = jax.random.normal(KEYS[2], (B, S_kv, Hkv, D), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        # dense oracle with the shifted causal mask
        rep = H // Hkv
        kr = jnp.repeat(k, rep, axis=2).transpose(0, 2, 1, 3)
        vr = jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3)
        qt = q.transpose(0, 2, 1, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kr) / (D ** 0.5)
        mask = (jnp.arange(S_kv)[None, :]
                <= (S_kv - S) + jnp.arange(S)[:, None])
        s = jnp.where(mask[None, None], s, -1e30)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vr)
        ref = ref.transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=f"S_kv={S_kv} S={S}")


# ---------------------------------------------------------------------------
# rwkv6 wkv
# ---------------------------------------------------------------------------

def _wkv_case(B, S, H, N, chunk, with_state, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(B * S + H + N), 6)
    r = (jax.random.normal(ks[0], (B, S, H, N)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, S, H, N)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (B, S, H, N)) * 0.5).astype(dtype)
    lw = (-jnp.abs(jax.random.normal(ks[3], (B, S, H, N))) * 0.3).astype(dtype)
    u = (jax.random.normal(ks[4], (H, N)) * 0.1).astype(dtype)
    s0 = (jax.random.normal(ks[5], (B, H, N, N)) * 0.2
          if with_state else jnp.zeros((B, H, N, N))).astype(jnp.float32)

    y, sf = wkv(r, k, v, lw, u, init_state=s0, chunk=chunk)
    flat = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    u_f = jnp.broadcast_to(u, (B, H, N)).reshape(B * H, N)
    yr, sr = wkv_ref(flat(r), flat(k), flat(v), flat(lw), u_f,
                     s0.reshape(B * H, N, N))
    yr = yr.reshape(B, H, S, N).transpose(0, 2, 1, 3)
    sr = sr.reshape(B, H, N, N)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("case", [
    (1, 32, 1, 8, 8, False), (2, 64, 3, 16, 16, True),
    (1, 64, 2, 16, 64, False),    # chunk == S
    (2, 48, 2, 8, 16, True),      # S % 32 != 0 path
])
def test_wkv_sweep(case):
    _wkv_case(*case)


def test_wkv_bf16():
    _wkv_case(1, 32, 2, 8, 8, False, dtype=jnp.bfloat16)


def test_wkv_matches_model_chunked():
    """Kernel == the model's chunked implementation (not just the oracle)."""
    from repro.models.rwkv6 import wkv_chunked
    B, S, H, N = 2, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    r = jax.random.normal(ks[0], (B, S, H, N)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, N)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, N)) * 0.5
    lw = -jnp.abs(jax.random.normal(ks[3], (B, S, H, N))) * 0.3
    u = jax.random.normal(ks[4], (H, N)) * 0.1
    y1, s1 = wkv(r, k, v, lw, u, chunk=16)
    y2, s2 = wkv_chunked(r, k, v, lw, u, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# mamba2 ssd
# ---------------------------------------------------------------------------

def _ssd_case(B, S, H, P, N, chunk, with_state):
    ks = jax.random.split(jax.random.PRNGKey(B + S + H + P + N), 6)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bs = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cs = jax.random.normal(ks[4], (B, S, N)) * 0.5
    s0 = (jax.random.normal(ks[5], (B, H, P, N)) * 0.2
          if with_state else jnp.zeros((B, H, P, N))).astype(jnp.float32)
    y, sf = ssd(x, dt, A, Bs, Cs, init_state=s0, chunk=chunk)
    yr, sr = ssd_ref(x, dt, A, Bs, Cs, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr),
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("case", [
    (1, 32, 2, 8, 8, 8, False), (2, 64, 4, 16, 8, 16, True),
    (1, 64, 1, 8, 16, 64, False),   # chunk == S
    (2, 40, 2, 8, 8, 8, True),      # odd chunk count
])
def test_ssd_sweep(case):
    _ssd_case(*case)


def test_ssd_matches_model_chunked():
    from repro.models.mamba2 import ssd_chunked
    B, S, H, P, N = 2, 64, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bs = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cs = jax.random.normal(ks[4], (B, S, N)) * 0.5
    y1, s1 = ssd(x, dt, A, Bs, Cs, chunk=16)
    y2, s2 = ssd_chunked(x, dt, A, Bs, Cs, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-3, atol=1e-4)
