"""MachSuite substrate: level-equivalence vs oracles + property tests.

The core claim of the faithful reproduction: every optimization level
O0..O5 of every kernel computes the SAME function (the paper's refinement
steps are performance transforms, not semantic ones)."""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.machsuite import KERNELS, aes, bfs, gemm, kmp, nw, sort, spmv, viterbi
from repro.core.optlevel import OptLevel

# scaled-down inputs (seconds, not hours, per kernel on CPU)
SCALES = {
    "aes": 2048 / 64e6,
    "bfs": 16 / 4096,
    "gemm": 32 / 1024,
    "kmp": 4096 / 128e6,
    "nw": 1 / 4096,
    "sort": 64 / 262144 / 16,
    "spmv": 1 / 64,
    "viterbi": 1 / 62500,
}


def _check(name, mod, lvl, rng):
    inp = mod.make_inputs(rng, SCALES[name])
    ref = np.asarray(mod.oracle(**inp))
    out = np.asarray(mod.run(OptLevel(lvl), **inp))
    if out.dtype.kind == "f":
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=1e-5,
                                   err_msg=f"{name} O{lvl}")
    else:
        np.testing.assert_array_equal(out, ref, err_msg=f"{name} O{lvl}")


@pytest.mark.parametrize("lvl", range(6))
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_level_equivalence(name, lvl, rng):
    _check(name, KERNELS[name], lvl, rng)


def test_second_seed(rng):
    rng2 = np.random.default_rng(1234)
    for name in ("aes", "nw", "kmp"):
        _check(name, KERNELS[name], 5, rng2)


# ---------------------------------------------------------------------------
# AES properties
# ---------------------------------------------------------------------------

def test_aes_fips197_c3():
    key = np.arange(32, dtype=np.uint8)
    pt = np.frombuffer(bytes.fromhex("00112233445566778899aabbccddeeff"),
                       np.uint8)
    ct = aes.encrypt_blocks_np(pt[None, :], aes.expand_key(key))[0]
    assert ct.tobytes().hex() == "8ea2b7ca516745bfeafc49904b496089"


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_aes_ecb_block_independence(seed):
    """ECB: identical plaintext blocks -> identical ciphertext blocks."""
    r = np.random.default_rng(seed)
    key = r.integers(0, 256, 32, dtype=np.uint8)
    blk = r.integers(0, 256, 16, dtype=np.uint8)
    data = np.tile(blk, 4)
    ct = aes.oracle(data, key).reshape(4, 16)
    assert (ct == ct[0]).all()
    # and it is not the identity map
    assert not np.array_equal(ct[0], blk)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_aes_key_sensitivity(seed):
    r = np.random.default_rng(seed)
    k1 = r.integers(0, 256, 32, dtype=np.uint8)
    k2 = k1.copy()
    k2[0] ^= 1
    data = r.integers(0, 256, 64, dtype=np.uint8)
    assert not np.array_equal(aes.oracle(data, k1), aes.oracle(data, k2))


# ---------------------------------------------------------------------------
# KMP properties
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 6))
def test_kmp_counts_overlapping(seed, m):
    r = np.random.default_rng(seed)
    text = r.integers(0, 2, 256, dtype=np.uint8)   # binary => many matches
    pattern = r.integers(0, 2, m, dtype=np.uint8)
    expect = sum(
        1 for i in range(len(text) - m + 1)
        if (text[i:i + m] == pattern).all())
    assert int(kmp.oracle(text, pattern)) == expect
    assert int(kmp.run(OptLevel.O3, text, pattern)) == expect


def test_kmp_dfa_matches_failure_automaton(rng):
    text = rng.integers(0, 3, 512, dtype=np.uint8)
    pattern = rng.integers(0, 3, 5, dtype=np.uint8)
    o0 = int(kmp.run(OptLevel.O0, text, pattern))
    o2 = int(kmp.run(OptLevel.O2, text, pattern))
    assert o0 == o2 == int(kmp.oracle(text, pattern))


# ---------------------------------------------------------------------------
# NW properties
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(4, 16))
def test_nw_properties(seed, L):
    r = np.random.default_rng(seed)
    a = r.integers(0, 4, (1, L), dtype=np.uint8)
    b = r.integers(0, 4, (1, L), dtype=np.uint8)
    s_ab = int(nw.oracle(a, b)[0])
    s_ba = int(nw.oracle(b, a)[0])
    assert s_ab == s_ba                       # symmetric scoring scheme
    assert s_ab <= L * nw.MATCH               # bounded by all-match
    assert int(nw.oracle(a, a)[0]) == L * nw.MATCH   # self-alignment


# ---------------------------------------------------------------------------
# SORT properties
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_sort_is_sorted_permutation(seed):
    r = np.random.default_rng(seed)
    chunk = 32
    data = r.integers(-1000, 1000, 4 * chunk, dtype=np.int32)
    out = np.asarray(sort.run(OptLevel.O3, data, chunk)).reshape(-1, chunk)
    src = data.reshape(-1, chunk)
    for c in range(4):
        assert (np.diff(out[c]) >= 0).all()
        assert np.array_equal(np.sort(src[c]), out[c])


# ---------------------------------------------------------------------------
# BFS properties
# ---------------------------------------------------------------------------

def test_bfs_triangle_inequality(rng):
    inp = bfs.make_inputs(rng, 32 / 4096)
    dist = np.asarray(bfs.run(OptLevel.O2, **inp))
    off, nbr = inp["offsets"], inp["neighbors"]
    n = len(off) - 1
    assert dist[inp["source"]] == 0
    for u in range(n):
        if dist[u] < 0:
            continue
        for v in nbr[off[u]:off[u + 1]]:
            assert dist[v] >= 0 and dist[v] <= dist[u] + 1


# ---------------------------------------------------------------------------
# SPMV / GEMM / VITERBI extra checks
# ---------------------------------------------------------------------------

def test_spmv_linearity(rng):
    inp = spmv.make_inputs(rng, 1 / 64)
    y1 = np.asarray(spmv.run(OptLevel.O3, **inp))
    y2 = np.asarray(spmv.run(OptLevel.O3, inp["vals"] * 2.0, inp["cols"],
                             inp["x"]))
    np.testing.assert_allclose(y2, 2.0 * y1, rtol=1e-5)


def test_gemm_identity(rng):
    n = gemm.TILE * 2
    a = rng.standard_normal((n, n)).astype(np.float32)
    eye = np.eye(n, dtype=np.float32)
    out = np.asarray(gemm.run(OptLevel.O3, a, eye))
    np.testing.assert_allclose(out, a, rtol=1e-5, atol=1e-6)


def test_viterbi_beats_random_paths(rng):
    inp = viterbi.make_inputs(rng, 1 / 62500)
    best = np.asarray(viterbi.run(OptLevel.O2, **inp))
    obs, init, trans, emit = (inp["obs"], inp["init"], inp["trans"],
                              inp["emit"])
    S = init.shape[0]
    c = 0
    for _ in range(50):   # random path cost >= viterbi cost
        path = rng.integers(0, S, obs.shape[1])
        cost = init[path[0]] + emit[path[0], obs[c, 0]]
        for t in range(1, obs.shape[1]):
            cost += trans[path[t - 1], path[t]] + emit[path[t], obs[c, t]]
        assert cost >= best[c] - 1e-3
