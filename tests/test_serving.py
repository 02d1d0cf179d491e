"""Serving engine: continuous batching, slot hygiene, retirement — and the
O0..O7 ladder contract: every level generates bit-identical tokens under
greedy sampling (the serving analog of MachSuite's output-equivalence
matrix), with the paged O6 cache and the speculative O7 draft/verify
loop differentially fuzzed against the contiguous path on random
request mixes."""

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke
from repro.core.optlevel import ALL_LEVELS, BestEffortConfig, OptLevel
from repro.models import get_model
from repro.serving import (CacheManager, DecodeEngine, Request,
                           SamplerConfig, Scheduler)
from repro.serving.kvquant import assert_tokens_match, tolerance_contract

# The two poles of the ladder token contract (kvquant.assert_tokens_match
# enforces whichever one a cell's stored dtype buys).
EXACT = tolerance_contract("bf16")
INT8_TOL = tolerance_contract("int8")

RNG = jax.random.PRNGKey(0)

_MODELS = {}


def _model(arch="qwen3-8b"):
    if arch not in _MODELS:
        cfg = get_smoke(arch)
        model = get_model(cfg)
        _MODELS[arch] = (cfg, model, model.init(RNG))
    return _MODELS[arch]


def _engine(arch="qwen3-8b", B=3, max_seq=32, **kw):
    cfg, model, params = _model(arch)
    return DecodeEngine(model, params, batch_size=B, max_seq=max_seq,
                        **kw), cfg


_DRAFTERS = {}


def _drafter(arch="smollm-360m"):
    """The zoo drafter for speculation tests.  Its smoke weights are
    random, so acceptance is near zero — which is exactly what stresses
    the reject/rollback path."""
    if arch not in _DRAFTERS:
        api = get_model(get_smoke(arch))
        _DRAFTERS[arch] = (api, api.init(jax.random.PRNGKey(1)))
    return _DRAFTERS[arch]


def test_all_requests_finish_exact_lengths():
    eng, _ = _engine()
    lens = [4, 2, 7, 1, 3]
    for i, n in enumerate(lens):
        eng.submit(Request(prompt=[i + 1, i + 2], max_new_tokens=n))
    fin = eng.run()
    assert sorted(len(r.generated) for r in fin) == sorted(lens)


def test_more_requests_than_slots():
    eng, _ = _engine(B=2)
    for i in range(7):
        eng.submit(Request(prompt=[1 + i], max_new_tokens=3))
    fin = eng.run()
    assert len(fin) == 7


def test_determinism_across_slot_reuse():
    """Same prompt gives the same completion whether it runs in a fresh
    engine or a reused slot (cache zeroing)."""
    for arch in ("qwen3-8b", "rwkv6-3b", "zamba2-2.7b"):
        eng, _ = _engine(arch, B=2, max_seq=24)
        eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=4))
        first = eng.run()[-1].generated
        # occupy + retire slots with other traffic, then repeat
        eng.submit(Request(prompt=[9, 9], max_new_tokens=5))
        eng.submit(Request(prompt=[3, 1, 4, 1], max_new_tokens=2))
        eng.run()
        eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=4))
        again = eng.run()[-1].generated
        assert first == again, arch


def test_batched_equals_solo():
    """A request decodes to the same tokens alone or batched with others
    (slots are independent)."""
    eng, _ = _engine(B=1, max_seq=24)
    eng.submit(Request(prompt=[2, 4, 6], max_new_tokens=5))
    solo = eng.run()[0].generated

    eng2, _ = _engine(B=3, max_seq=24)
    eng2.submit(Request(prompt=[2, 4, 6], max_new_tokens=5))
    eng2.submit(Request(prompt=[1, 1, 1, 1], max_new_tokens=3))
    eng2.submit(Request(prompt=[7], max_new_tokens=6))
    fin = eng2.run()
    batched = next(r for r in fin if r.prompt == [2, 4, 6]).generated
    assert solo == batched


# ---------------------------------------------------------------------------
# The ladder: every OptLevel computes the same function (greedy sampling)
# ---------------------------------------------------------------------------

_WORKLOAD = [([5, 6, 7], 4), ([9], 6), ([3, 1, 4, 1], 3), ([2, 2], 5),
             ([8, 8, 8, 8, 8], 2), ([4, 2], 4)]
_LADDER_REF = {}


def _run_ladder_workload(level, arch="qwen3-8b"):
    eng, _ = _engine(arch, B=3, max_seq=32,
                     config=BestEffortConfig(level=level))
    rids = [eng.submit(Request(prompt=list(p), max_new_tokens=n))
            for p, n in _WORKLOAD]
    fin = {r.rid: r.generated for r in eng.run()}
    return [fin[rid] for rid in rids]


@pytest.mark.parametrize("level", ALL_LEVELS, ids=lambda l: f"O{int(l)}")
def test_identical_tokens_at_every_level(level):
    """Greedy generations are bit-identical at every rung: the ladder only
    changes *how* the engine runs, never *what* it computes."""
    gen = _run_ladder_workload(level)
    if "qwen3-8b" not in _LADDER_REF:
        _LADDER_REF["qwen3-8b"] = _run_ladder_workload(OptLevel.O5)
    ref = _LADDER_REF["qwen3-8b"]
    assert gen == ref, f"O{int(level)} diverged from O5"
    assert [len(g) for g in gen] == [n for _, n in _WORKLOAD]


def test_mid_flight_admission_at_o5():
    """Requests submitted while others decode join without disturbing the
    in-flight generations (continuous batching at the top rung)."""
    eng, _ = _engine(B=2, max_seq=32,
                     config=BestEffortConfig(level=OptLevel.O5))
    r0 = eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=6))
    for _ in range(3):
        eng.step()
    r1 = eng.submit(Request(prompt=[9, 9], max_new_tokens=4))
    fin = {r.rid: r.generated for r in eng.run()}
    assert set(fin) == {r0, r1}
    assert len(fin[r0]) == 6 and len(fin[r1]) == 4

    # in-flight tokens match an undisturbed run of the same request
    solo, _ = _engine(B=2, max_seq=32,
                      config=BestEffortConfig(level=OptLevel.O5))
    solo.submit(Request(prompt=[5, 6, 7], max_new_tokens=6))
    assert solo.run()[0].generated == fin[r0]


def test_eos_stops_early_at_o5():
    eng, cfg = _engine(config=BestEffortConfig(level=OptLevel.O5))
    # run once to find what token gets generated, then use it as EOS
    eng.submit(Request(prompt=[3, 5], max_new_tokens=6))
    toks = eng.run()[0].generated
    eos = toks[1]
    eng.submit(Request(prompt=[3, 5], max_new_tokens=6, eos_id=eos))
    out = eng.run()[-1]
    assert out.generated[-1] == eos
    assert len(out.generated) <= 2


# ---------------------------------------------------------------------------
# Differential fuzz: paged (O6) vs contiguous, random request mixes
# ---------------------------------------------------------------------------

def _random_mix(seed, vocab, *, n=8, max_seq=32, prompt_hi=10, new_hi=6):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        plen = int(rng.integers(1, prompt_hi))
        new = int(rng.integers(1, new_hi))
        out.append((rng.integers(1, vocab, plen).tolist(), new))
    return out


def _run_mix(mix, level, *, arch="qwen3-8b", policy="fcfs", B=3,
             max_seq=32, eos=None, late_from=None, draft=None, **cfg_kw):
    """Decode ``mix`` at ``level``; ``late_from`` submits the tail of the
    mix mid-flight (after two ticks); ``eos`` maps request index ->
    eos_id; ``draft`` wires a drafter into the engine ("zoo" = the
    smollm-360m pairing, "self" = the target drafts for itself).
    Returns generated tokens in submission order."""
    eng_kw = {}
    if draft == "self":
        _, tmodel, tparams = _model(arch)
        eng_kw = dict(draft_model=tmodel, draft_params=tparams)
    elif draft == "zoo":
        api, dparams = _drafter()
        eng_kw = dict(draft_model=api, draft_params=dparams)
    eng, _ = _engine(arch, B=B, max_seq=max_seq, policy=policy,
                     config=BestEffortConfig(level=level, **cfg_kw),
                     **eng_kw)
    head = mix if late_from is None else mix[:late_from]
    rids = [eng.submit(Request(prompt=list(p), max_new_tokens=n,
                               eos_id=(eos or {}).get(k)))
            for k, (p, n) in enumerate(head)]
    if late_from is not None:
        for _ in range(2):
            eng.step()
        rids += [eng.submit(Request(prompt=list(p), max_new_tokens=n,
                                    eos_id=(eos or {}).get(late_from + k)))
                 for k, (p, n) in enumerate(mix[late_from:])]
    fin = {r.rid: r.generated for r in eng.run()}
    return [fin[rid] for rid in rids]


@pytest.mark.parametrize("seed,policy", [(1, "fcfs"), (2, "spf"),
                                         (3, "fcfs")])
def test_differential_fuzz_paged_vs_contiguous(seed, policy):
    """Random request mixes (prompt lengths, budgets, eos positions,
    mid-flight arrivals, fcfs/spf) decode to bit-identical greedy tokens
    on the contiguous O5 path and BOTH paged O6 steps — the gather step
    and the gather-free block-table kernel — including a pool small
    enough that the block gate queues admissions."""
    cfg, _, _ = _model()
    mix = _random_mix(seed, cfg.vocab)
    ref = _run_mix(mix, OptLevel.O5, policy=policy)
    # plant real eos positions from the reference generations on half the
    # requests so early-exit paths actually fire in both engines
    eos = {k: g[len(g) // 2] for k, g in enumerate(ref) if k % 2 == 0
           and len(g) > 1}
    ref = _run_mix(mix, OptLevel.O5, policy=policy, eos=eos, late_from=5)
    paged = _run_mix(mix, OptLevel.O6, policy=policy, eos=eos, late_from=5,
                     kv_block_size=4, kv_pool_blocks=14)
    assert_tokens_match(ref, paged, EXACT,
                        f"paged (seed={seed}, {policy})")
    kernel = _run_mix(mix, OptLevel.O6, policy=policy, eos=eos,
                      late_from=5, kv_block_size=4, kv_pool_blocks=14,
                      paged_attn="kernel")
    assert_tokens_match(ref, kernel, EXACT,
                        f"paged kernel (seed={seed}, {policy})")
    # and the naive O0 rebuild path computes the same function
    if seed == 1:
        naive = _run_mix(mix, OptLevel.O0, policy=policy, eos=eos,
                         late_from=5)
        assert_tokens_match(ref, naive, EXACT, "naive O0")


@pytest.mark.parametrize("seed,policy,chunk", [(21, "fcfs", 2),
                                               (22, "spf", 4),
                                               (23, "fcfs", 16)])
def test_differential_fuzz_chunked_prefill(seed, policy, chunk):
    """Chunked prefill (prompts consumed in multi-token chunks, one
    chunk per tick, interleaved with decode) is a pure scheduling
    change: random mixes with mid-flight arrivals and planted eos stops
    decode to bit-identical greedy tokens on the legacy prestaged O5
    path and every chunked cell — contiguous O5, paged O6 gather, and
    the paged O6 prefill kernel — including a pool small enough to
    queue admissions."""
    cfg, _, _ = _model()
    mix = _random_mix(seed, cfg.vocab)
    ref = _run_mix(mix, OptLevel.O5, policy=policy)
    eos = {k: g[len(g) // 2] for k, g in enumerate(ref) if k % 2 == 0
           and len(g) > 1}
    ref = _run_mix(mix, OptLevel.O5, policy=policy, eos=eos, late_from=5)
    cells = [(OptLevel.O5, {}),
             (OptLevel.O6, dict(kv_block_size=4, kv_pool_blocks=14)),
             (OptLevel.O6, dict(kv_block_size=4, kv_pool_blocks=14,
                                paged_attn="kernel"))]
    for level, kw in cells:
        out = _run_mix(mix, level, policy=policy, eos=eos, late_from=5,
                       prefill_chunk=chunk, **kw)
        assert_tokens_match(ref, out, EXACT,
                            f"chunked prefill (seed={seed}, {policy}, "
                            f"chunk={chunk}, O{int(level)}, {kw})")
    if seed == 21:
        # unfused O0 accepts the knob but degrades to token prefill —
        # same tokens, never an exception
        out = _run_mix(mix, OptLevel.O0, policy=policy, eos=eos,
                       late_from=5, prefill_chunk=chunk)
        assert_tokens_match(ref, out, EXACT, "O0 chunk degrade")


@pytest.mark.parametrize("seed,policy", [(51, "fcfs"), (52, "spf")])
def test_differential_fuzz_quantized_pool(seed, policy):
    """int8 pool vs the contiguous O5 reference: random mixes with
    mid-flight arrivals and planted eos stops decode WITHIN the int8
    tolerance contract (``kvquant.tolerance_contract``) on every
    quantized cell — the gather step, the block-table kernel, chunked
    prefill's windowed requant writer, and O7 verify windows on the
    quantized pool.  Narrow cells are NOT asserted against each other
    (gather attends the current token unquantized, the kernel reads it
    requantized — both only owe the contract vs O5), but each cell IS
    bit-deterministic across runs: quantization is rounding, not
    noise."""
    cfg, _, _ = _model()
    mix = _random_mix(seed, cfg.vocab)
    ref = _run_mix(mix, OptLevel.O5, policy=policy)
    eos = {k: g[len(g) // 2] for k, g in enumerate(ref) if k % 2 == 0
           and len(g) > 1}
    ref = _run_mix(mix, OptLevel.O5, policy=policy, eos=eos, late_from=5)
    pool = dict(kv_block_size=4, kv_pool_blocks=14, kv_dtype="int8")
    cells = {"gather": {}, "kernel": dict(paged_attn="kernel"),
             "chunked": dict(prefill_chunk=4)}
    for name, kw in cells.items():
        out = _run_mix(mix, OptLevel.O6, policy=policy, eos=eos,
                       late_from=5, **pool, **kw)
        assert_tokens_match(ref, out, INT8_TOL,
                            f"int8/{name} (seed={seed}, {policy})")
        if name == "gather":
            again = _run_mix(mix, OptLevel.O6, policy=policy, eos=eos,
                             late_from=5, **pool, **kw)
            assert_tokens_match(out, again, EXACT,
                                f"int8/{name} determinism")
    # O7 verify windows writing/rolling back on the quantized pool
    # (self-draft so acceptance actually exercises multi-token commits)
    spec = _run_mix(mix, OptLevel.O7, policy=policy, eos=eos,
                    late_from=5, draft="self", draft_k=4, **pool)
    assert_tokens_match(ref, spec, INT8_TOL,
                        f"int8/spec (seed={seed}, {policy})")


def test_prefill_chunk_mode_recorded_and_degrades():
    """``prefill_mode`` is the best-effort record: "chunked" at fused
    rungs for families with a prefill step, "token" when the knob is off
    or below O2 — recorded, never an exception, and the degraded engine
    still decodes.  Carried-state families chunk only on the PAGED
    layout (NULL-row parking); the contiguous layout has no indirection
    to park through, so it degrades to token prefill with a recorded
    ``degrade_reason``."""
    eng, _ = _engine(config=BestEffortConfig(level=OptLevel.O5,
                                             prefill_chunk=4))
    assert eng.prefill_mode == "chunked"
    eng2, _ = _engine(config=BestEffortConfig(level=OptLevel.O5))
    assert eng2.prefill_mode == "token"
    eng3, _ = _engine(config=BestEffortConfig(level=OptLevel.O0,
                                              prefill_chunk=4))
    assert eng3.prefill_mode == "token"
    eng4, _ = _engine("rwkv6-3b", B=2, max_seq=24,
                      config=BestEffortConfig(level=OptLevel.O5,
                                              prefill_chunk=4))
    assert eng4.prefill_mode == "token"
    assert "carries recurrent state" in eng4.degrade_reason
    eng4.submit(Request(prompt=[5, 6, 7], max_new_tokens=3))
    assert len(eng4.run()) == 1
    # the paged layout parks carried state on the NULL row, so the same
    # family chunks for real at O6 — no degrade recorded
    eng5, _ = _engine("rwkv6-3b", B=2, max_seq=24,
                      config=BestEffortConfig(level=OptLevel.O6,
                                              kv_block_size=8,
                                              prefill_chunk=4))
    assert eng5.prefill_mode == "chunked"
    assert eng5.degrade_reason is None


@pytest.mark.parametrize("level,kw", [
    (OptLevel.O5, dict(prefill_chunk=4)),
    (OptLevel.O6, dict(prefill_chunk=4, kv_block_size=4)),
    (OptLevel.O6, dict(prefill_chunk=4, kv_block_size=4,
                       paged_attn="kernel")),
    (OptLevel.O0, {}),
], ids=["O5c", "O6c", "O6kc", "O0"])
def test_prefill_insert_generate_matches_prestaged(level, kw):
    """The public prefill->insert->generate phases: prompts prefilled on
    a standalone batch-1 cache, inserted into engine slots (scattered
    through block tables under the paged layout), then drained — greedy
    tokens bit-identical to submitting the same requests through the
    engine's internal admission path."""
    mix = _WORKLOAD[:3]
    ref = _run_mix(mix, level, **kw)
    eng, _ = _engine(B=3, max_seq=32,
                     config=BestEffortConfig(level=level, **kw))
    results = [eng.prefill(p, max_new_tokens=n) for p, n in mix]
    assert [r.length for r in results] == [len(p) for p, _ in mix]
    slots = [eng.insert(r) for r in results]
    assert sorted(slots) == [0, 1, 2]
    fin = {r.rid: r.generated for r in eng.generate()}
    got = [fin[r.request.rid] for r in results]
    assert got == ref, f"prefill->insert->generate diverged ({kw})"
    # first_token is the request's first greedy emission
    assert [r.first_token for r in results] == [g[0] for g in ref]


def test_prefill_insert_mid_flight_and_validation():
    """Insert while other requests decode (continuous batching across
    the API seam), plus the error contract: inserting with no free slot
    raises, a paged pool too full to reserve raises, and prefill
    validates like submit."""
    eng, _ = _engine(B=2, max_seq=32,
                     config=BestEffortConfig(level=OptLevel.O5,
                                             prefill_chunk=4))
    r0 = eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=6))
    for _ in range(3):
        eng.step()
    res = eng.prefill([9, 9], max_new_tokens=4)
    eng.insert(res)
    fin = {r.rid: r.generated for r in eng.generate()}
    assert len(fin[r0]) == 6 and len(fin[res.request.rid]) == 4
    # in-flight tokens match an undisturbed run of each request
    solo = _run_mix([([5, 6, 7], 6), ([9, 9], 4)], OptLevel.O5)
    assert [fin[r0], fin[res.request.rid]] == solo

    with pytest.raises(ValueError, match="empty prompt"):
        eng.prefill([], max_new_tokens=2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.prefill([1, 2], max_new_tokens=0)
    with pytest.raises(ValueError, match="max_seq"):
        eng.prefill([1] * 30, max_new_tokens=6)

    # no free slot: fill both slots with long decodes, then insert
    eng.submit(Request(prompt=[1, 2], max_new_tokens=8))
    eng.submit(Request(prompt=[3, 4], max_new_tokens=8))
    eng.step()
    spare = eng.prefill([7, 7], max_new_tokens=2)
    with pytest.raises(ValueError, match="no free slot"):
        eng.insert(spare)
    eng.generate()
    eng.insert(spare)                      # slot freed: insert succeeds
    fin2 = {r.rid: r.generated for r in eng.generate()}
    assert len(fin2[spare.request.rid]) == 2

    # paged: a pool that cannot hold the reservation refuses the insert
    engp, _ = _engine(B=3, max_seq=16,
                      config=BestEffortConfig(level=OptLevel.O6,
                                              kv_block_size=4,
                                              kv_pool_blocks=5))
    engp.submit(Request(prompt=[1] * 8, max_new_tokens=4))   # 3 blocks
    engp.step()
    big = engp.prefill([2] * 8, max_new_tokens=4)            # 3 more
    with pytest.raises(ValueError, match="insufficient free KV blocks"):
        engp.insert(big)
    engp.generate()
    engp.insert(big)                       # blocks freed: fits now
    fin3 = {r.rid: r.generated for r in engp.generate()}
    assert len(fin3[big.request.rid]) == 4


def test_paged_capacity_queues_and_drains():
    """A pool holding ~2 reservations with B=3 slots must queue (never
    reject) the overflow and still finish everything, bit-identically."""
    mix = [([1, 2, 3, 4, 5, 6], 4)] * 4          # 10-token reservations
    ref = _run_mix(mix, OptLevel.O5, B=3, max_seq=16)
    out = _run_mix(mix, OptLevel.O6, B=3, max_seq=16,
                   kv_block_size=4, kv_pool_blocks=6)  # 2 x 3-block resv
    assert out == ref


@pytest.mark.parametrize("arch", ["rwkv6-3b", "zamba2-2.7b"])
def test_paged_recurrent_state_zeroed_on_slot_reuse(arch):
    """Recurrent-state leaves (RWKV wkv, Mamba conv/ssm) are carried, not
    masked, so the paged manager must still packed-zero them at admission
    — this pins the ``make_packed_zero(skip=...)`` branch that the
    all-leaves-paged transformer fuzz never executes: a leaked previous
    tenant's state corrupts the third request below (it reuses a slot)."""
    mix = [([5, 6, 7], 4), ([9, 9], 5), ([3, 1, 4], 3)]
    ref = [_run_mix(mix, lvl, arch=arch, B=2, max_seq=24, kv_block_size=8)
           for lvl in (OptLevel.O5, OptLevel.O6)]
    assert ref[0] == ref[1], arch


@pytest.mark.parametrize("arch,seed", [("rwkv6-3b", 71),
                                       ("mamba2-2.7b", 72),
                                       ("zamba2-2.7b", 73),
                                       ("whisper-base", 74)])
def test_differential_fuzz_state_pool_per_family(arch, seed):
    """The full-rung O6 contract for every non-transformer family: the
    recurrent/cross state lives in the row pool (``state_impl="rows"``,
    no gather degrade) and random mixes — mid-flight arrivals, planted
    eos stops, a block pool small enough to queue admissions for the
    families that also page attention KV — decode to bit-identical
    greedy tokens on the contiguous O5 path, the O6 gather step, the
    gather-free kernel step, and chunked prefill on both (the NULL-row
    parking path for carried state)."""
    cfg, _, _ = _model(arch)
    mix = _random_mix(seed, cfg.vocab, max_seq=24, prompt_hi=8, new_hi=5)
    ref = _run_mix(mix, OptLevel.O5, arch=arch, B=2, max_seq=24)
    eos = {k: g[len(g) // 2] for k, g in enumerate(ref) if k % 2 == 0
           and len(g) > 1}
    ref = _run_mix(mix, OptLevel.O5, arch=arch, B=2, max_seq=24,
                   eos=eos, late_from=5)
    pool = dict(kv_block_size=4, kv_pool_blocks=10)
    cells = [dict(pool),
             dict(pool, paged_attn="kernel"),
             dict(pool, prefill_chunk=3),
             dict(pool, paged_attn="kernel", prefill_chunk=3)]
    for kw in cells:
        out = _run_mix(mix, OptLevel.O6, arch=arch, B=2, max_seq=24,
                       eos=eos, late_from=5, **kw)
        assert_tokens_match(ref, out, EXACT, f"{arch} O6 {kw}")


def test_paged_kernel_attn_impl_recorded_and_fallback():
    """``paged_attn="kernel"`` builds the gather-free step and records
    ``attn_impl="kernel"`` — for transformers AND for recurrent
    families, whose paged step reads state through row indirection
    (``state_impl="rows"``).  A model genuinely without a paged decode
    step degrades to the gather step — recorded with a loud
    ``degrade_reason``, never an exception, and still bit-identical to
    O5 (the best-effort degradation contract)."""
    import dataclasses

    eng, _ = _engine(B=2, max_seq=16,
                     config=BestEffortConfig(level=OptLevel.O6,
                                             kv_block_size=4,
                                             paged_attn="kernel"))
    assert eng.layout.paged_attn == "kernel"
    assert eng.layout.attn_impl == "kernel"
    assert eng.layout.state_impl == "none"        # all leaves paged
    assert eng.degrade_reason is None

    mix = [([5, 6, 7], 4), ([9, 9], 5), ([3, 1, 4], 3)]
    ref = [_run_mix(mix, lvl, arch="rwkv6-3b", B=2, max_seq=24,
                    kv_block_size=8,
                    **({"paged_attn": "kernel"}
                       if lvl is OptLevel.O6 else {}))
           for lvl in (OptLevel.O5, OptLevel.O6)]
    assert ref[0] == ref[1]
    eng2, _ = _engine("rwkv6-3b", B=2, max_seq=24,
                      config=BestEffortConfig(level=OptLevel.O6,
                                              kv_block_size=8,
                                              paged_attn="kernel"))
    assert eng2.layout.attn_impl == "kernel"      # real kernel rung now
    assert eng2.layout.state_impl == "rows"
    assert eng2.degrade_reason is None

    # strip the paged step to exercise the degrade path itself: the
    # layout falls back to gather and RECORDS why, loudly
    cfg, model, params = _model("rwkv6-3b")
    stripped = dataclasses.replace(model, paged_decode_step=None)
    eng3 = DecodeEngine(stripped, params, batch_size=2, max_seq=24,
                        config=BestEffortConfig(level=OptLevel.O6,
                                                kv_block_size=8,
                                                paged_attn="kernel"))
    assert eng3.layout.attn_impl == "gather"      # degraded, recorded
    assert eng3.layout.state_impl == "rows"
    assert "paged_decode_step" in eng3.degrade_reason

    with pytest.raises(ValueError, match="paged_attn"):
        _engine(B=2, max_seq=16,
                config=BestEffortConfig(level=OptLevel.O6,
                                        paged_attn="flash"))


def test_paged_manager_geometry_and_slot_lengths():
    """The manager's pool-introspection surface (what the serving-ladder
    bytes accounting replays the schedule with): geometry mirrors the
    plan, slot_lengths clips to each slot's reservation and reports 0
    for slots holding nothing, and held_blocks tracks admissions."""
    _, model, _ = _model()
    from repro.serving import PagedCacheManager

    mgr = PagedCacheManager(model, 3, 16, block_size=4)
    geo = mgr.geometry
    assert geo["block_size"] == 4 and geo["blocks_per_seq"] == 4
    assert geo["batch"] == 3 and geo["max_seq"] == 16
    assert geo["pool_rows"] == mgr.plan.pool_rows
    assert geo["token_bytes"] == mgr.plan.token_bytes > 0

    assert mgr.held_blocks == [0, 0, 0]
    assert mgr.slot_lengths([5, 5, 5]) == [0, 0, 0]     # nothing held
    mgr.admit_slot(1, Request(prompt=[1, 2, 3], max_new_tokens=2))
    assert mgr.held_blocks == [0, 2, 0]                 # ceil(5 / 4)
    # position 3 -> length 4; position 9 clips to the 2-block (8-token)
    # reservation; unheld slots stay 0 whatever position is passed
    assert mgr.slot_lengths([7, 3, 7]) == [0, 4, 0]
    assert mgr.slot_lengths([0, 9, 0]) == [0, 8, 0]
    # the bytes estimate is blocks-touched + one append per slot
    tb = geo["token_bytes"]
    assert mgr.plan.kernel_bytes_per_tick([0, 4, 0]) == (4 + 3) * tb
    assert mgr.plan.gather_bytes_per_tick() == (3 * 3 * 16 + 3 * 4) * tb


def test_paged_kernel_compact_mid_flight_preserves_tokens():
    """The kernel path reads whatever rows the (rewritten) tables point
    at, so copy-on-admit defrag must be transparent to it exactly as it
    is to the gather path."""
    mix = _random_mix(13, _model()[0].vocab, n=6)
    ref = _run_mix(mix, OptLevel.O6, kv_block_size=4)

    eng, _ = _engine(B=3, max_seq=32,
                     config=BestEffortConfig(level=OptLevel.O6,
                                             kv_block_size=4,
                                             paged_attn="kernel"))
    rids = [eng.submit(Request(prompt=list(p), max_new_tokens=n))
            for p, n in mix]
    for _ in range(4):
        eng.step()
        eng.cache_mgr.compact()
        eng.cache_mgr.check_conservation()
    fin = {r.rid: r.generated for r in eng.run()}
    assert [fin[rid] for rid in rids] == ref


# ---------------------------------------------------------------------------
# Property test: gather/scatter round-trips bit-exactly (the reference
# semantics the paged kernel is diffed against)
# ---------------------------------------------------------------------------

from hypothesis import given, settings, strategies as st  # noqa: E402


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 8), st.integers(1, 4))
def test_paged_gather_scatter_round_trip(seed, block, row_multiple):
    """``BlockPagingPlan.gather`` o ``scatter`` round-trips bit-exactly:
    with the dense view unmodified, scattering it back must leave every
    real pool row (and the padding rows a sharded placement adds — they
    are never in any table) bit-identical; only the NULL row may absorb
    garbage.  Holds under partially-filled final blocks and positions
    anywhere in the slot's reservation.  This pins the reference
    semantics the gather-free kernel is differentially fuzzed against."""
    from repro.serving.paged import NULL_BLOCK, BlockPagingPlan, blocks_for

    rng = np.random.default_rng(seed)
    _, model, _ = _model()
    B, max_seq = 3, 24
    nb = blocks_for(max_seq, block)
    pool_blocks = B * nb
    plan = BlockPagingPlan(model, B, max_seq, block, pool_blocks,
                           row_multiple=row_multiple)
    assert plan.pool_rows % row_multiple == 0

    key = jax.random.PRNGKey(seed)
    pool, _ = plan.init_pool(model)
    pool = jax.tree.map(
        lambda leaf: jax.random.normal(key, leaf.shape).astype(leaf.dtype),
        pool)

    # random occupancy: each slot holds a random token reservation
    held_tokens = rng.integers(1, max_seq + 1, B)
    tables = np.full((B, nb), NULL_BLOCK, np.int32)
    free = list(range(1, pool_blocks + 1))
    rng.shuffle(free)
    for b in range(B):
        for j in range(blocks_for(int(held_tokens[b]), block)):
            tables[b, j] = free.pop()
    positions = jnp.asarray([int(rng.integers(0, h)) for h in held_tokens],
                            jnp.int32)
    tables_dev = jnp.asarray(tables)

    dense = plan.gather(pool, tables_dev)
    pool2 = plan.scatter(pool, tables_dev, dense, positions)

    for before, after, (bax, paged) in zip(jax.tree.leaves(pool),
                                           jax.tree.leaves(pool2),
                                           plan.plans):
        b_np, a_np = np.asarray(before), np.asarray(after)
        if not paged:
            np.testing.assert_array_equal(a_np, b_np)   # state: replaced
            continue
        for row in range(plan.pool_rows):
            if row == NULL_BLOCK:
                continue                  # garbage sink, by design
            idx = [slice(None)] * b_np.ndim
            idx[bax] = row
            np.testing.assert_array_equal(
                a_np[tuple(idx)], b_np[tuple(idx)],
                err_msg=f"row {row} changed (referenced: "
                        f"{row in set(tables.flatten())})")

    # and the re-gathered view matches the original at every position
    # inside each slot's reservation (outside it the view reads NULL)
    dense2 = plan.gather(pool2, tables_dev)
    for g1, g2, (bax, paged) in zip(jax.tree.leaves(dense),
                                    jax.tree.leaves(dense2), plan.plans):
        if not paged:
            continue
        g1, g2 = np.asarray(g1), np.asarray(g2)
        for b in range(B):
            idx = [slice(None)] * g1.ndim
            idx[bax] = b
            idx[bax + 1] = slice(0, int(held_tokens[b]))
            np.testing.assert_array_equal(g1[tuple(idx)], g2[tuple(idx)])


def test_paged_step_fn_combination_rejected():
    """A caller-supplied fused step cannot thread block tables; silently
    downgrading to the contiguous cache would misreport the paged rung."""
    _, model, params = _model()
    with pytest.raises(ValueError, match="step_fn"):
        DecodeEngine(model, params, batch_size=2, max_seq=16,
                     config=BestEffortConfig(level=OptLevel.O6),
                     step_fn=lambda p, c, t, pos: (t, c))


def test_paged_pe_degrades_gracefully_on_single_device():
    """Layout x placement: a paged engine asking for pe>1 on one device
    must degrade to the replicated plan (pe=1) — no exception, no silent
    layout downgrade — and still decode bit-identically to O5.  (The
    sharded cell itself is pinned by the dist-tier oracle.)"""
    mix = [([5, 6, 7], 4), ([9], 5), ([3, 1, 4, 1], 3)]
    ref = _run_mix(mix, OptLevel.O5)
    eng, _ = _engine(B=3, max_seq=32,
                     config=BestEffortConfig(level=OptLevel.O6, pe=4,
                                             kv_block_size=4))
    assert eng.layout.name == "paged"
    assert eng.config.kv_layout == "paged"
    assert not eng.placement.sharded
    assert eng.placement.n_devices == 1
    rids = [eng.submit(Request(prompt=list(p), max_new_tokens=n))
            for p, n in mix]
    fin = {r.rid: r.generated for r in eng.run()}
    assert [fin[rid] for rid in rids] == ref


def test_paged_tables_device_cache_invalidated_on_lifecycle():
    """``step_extras`` re-uses one device upload of the block tables
    across steady-state ticks and drops it whenever admission /
    retirement / compaction rewrites the tables — a stale table would
    scatter a live request's KV into a retired request's blocks."""
    eng, _ = _engine(B=2, max_seq=16,
                     config=BestEffortConfig(level=OptLevel.O6,
                                             kv_block_size=4))
    mgr = eng.cache_mgr
    assert mgr.step_extras()[0] is mgr.step_extras()[0]   # cached
    eng.submit(Request(prompt=[1, 2], max_new_tokens=2))
    eng.step()                                            # admits
    dev0 = mgr.step_extras()[0]
    np.testing.assert_array_equal(np.asarray(dev0), mgr.tables)
    assert mgr.step_extras()[0] is dev0                   # still cached
    eng.run()                                             # retires
    dev1 = mgr.step_extras()[0]
    assert dev1 is not dev0                               # invalidated
    np.testing.assert_array_equal(np.asarray(dev1), mgr.tables)

    # A REAL compaction move must drop the cache too: fresh manager,
    # slot 0 takes block 1, slot 1 block 2; releasing slot 0 leaves a
    # gap so compact() relocates slot 1's block down to id 1.
    _, model, _ = _model()
    from repro.serving import PagedCacheManager
    mgr2 = PagedCacheManager(model, 2, 16, block_size=4)
    mgr2.admit_slot(0, Request(prompt=[1], max_new_tokens=2))
    mgr2.admit_slot(1, Request(prompt=[1], max_new_tokens=2))
    mgr2.release_slot(0)
    dev2 = mgr2.step_extras()[0]
    mgr2.compact()
    assert mgr2.tables[1, 0] == 1                         # block moved
    dev3 = mgr2.step_extras()[0]
    assert dev3 is not dev2                               # invalidated
    np.testing.assert_array_equal(np.asarray(dev3), mgr2.tables)


def test_paged_tables_upload_is_a_snapshot():
    """The uploaded block tables must not change when the host tables
    are rewritten in place: retirement rewrites them while a dispatched
    step may still read the upload, and the CPU backend aliases
    64-byte-aligned host arrays instead of copying them (that alias
    gave requests a garbage last token under the async front end)."""
    _, model, _ = _model()
    from repro.serving import PagedCacheManager
    mgr = PagedCacheManager(model, 2, 16, block_size=4)
    buf = np.empty(mgr.tables.size + 16, np.int32)
    off = (-buf.ctypes.data % 64) // 4
    aligned = buf[off:off + mgr.tables.size].reshape(mgr.tables.shape)
    aligned[:] = mgr.tables
    mgr.tables = aligned
    req = Request(prompt=[1, 2], max_new_tokens=2)
    mgr.admit_slot(0, req)
    dev = mgr.step_extras()[0]
    before = np.asarray(dev).copy()
    mgr.release_slot(0, req)                   # rewrites row 0 to NULL
    np.testing.assert_array_equal(np.asarray(dev), before)


def test_step_cache_does_not_pin_dead_models():
    """The shared-step cache is weakref-keyed: constructing and dropping
    more than _STEP_CACHE_MAX engines (each with its own model) must not
    keep any dead model alive — the old id()-keyed cache pinned every
    model until LRU churn evicted it."""
    import gc
    import weakref

    from repro.serving import layout as layout_mod

    refs = []
    for k in range(layout_mod._STEP_CACHE_MAX + 2):
        cfg = get_smoke("qwen3-8b")
        model = get_model(cfg)
        params = model.init(RNG)
        eng = DecodeEngine(model, params, batch_size=2, max_seq=16,
                           config=BestEffortConfig(level=OptLevel.O5))
        refs.append(weakref.ref(model))
        del cfg, model, params, eng
    gc.collect()
    assert all(r() is None for r in refs), (
        f"{sum(r() is not None for r in refs)} dead models still pinned")


def test_paged_compact_mid_flight_preserves_tokens():
    """Copy-on-admit defrag: after churn fragments the pool, ``compact``
    relocates live blocks to the lowest ids (physically copying pool
    rows, rewriting tables) without disturbing in-flight generations."""
    mix = _random_mix(7, _model()[0].vocab, n=6)
    ref = _run_mix(mix, OptLevel.O6, kv_block_size=4)

    eng, _ = _engine(B=3, max_seq=32,
                     config=BestEffortConfig(level=OptLevel.O6,
                                             kv_block_size=4))
    rids = [eng.submit(Request(prompt=list(p), max_new_tokens=n))
            for p, n in mix]
    for _ in range(4):                    # fragment: some retire/admit
        eng.step()
        eng.cache_mgr.compact()
        eng.cache_mgr.check_conservation()
        held = sorted({b for row, n in zip(eng.cache_mgr.tables,
                                           eng.cache_mgr.held_blocks)
                       for b in row[:n].tolist()})
        assert held == list(range(1, len(held) + 1))   # packed prefix
    fin = {r.rid: r.generated for r in eng.run()}
    assert [fin[rid] for rid in rids] == ref


def test_paged_pool_smaller_than_max_seq_rejects_at_submit():
    """A pool smaller than one worst-case reservation is a legal
    memory-saving config — the engine BUILDS — but a request whose
    reservation can never fit the TOTAL pool is rejected at submit()
    with a clear error instead of queueing forever (it would be gated
    out every admission wave, so run() would spin its whole tick budget
    doing nothing and then report success).  A short request through
    the same engine still admits and drains."""
    eng, _ = _engine(B=2, max_seq=32,
                     config=BestEffortConfig(level=OptLevel.O6,
                                             kv_block_size=4,
                                             kv_pool_blocks=7))
    # 28 tokens needs 7 blocks == the whole pool: feasible (barely)
    ok = Request(prompt=[1] * 8, max_new_tokens=20)
    # 32 tokens needs 8 blocks > 7 total: can NEVER be admitted
    with pytest.raises(ValueError, match="never fit the total pool"):
        eng.submit(Request(prompt=[2] * 12, max_new_tokens=20))
    eng.submit(ok)
    fin = eng.run()
    assert len(fin) == 1 and len(fin[0].generated) == 20


def test_run_raises_on_tick_budget_and_marks_survivors_truncated():
    """Satellite regression: run(max_ticks) used to return `finished`
    silently on tick exhaustion, leaving in-flight slots active and
    queued requests unreported.  Now every survivor is marked truncated
    and TickBudgetExceeded carries them; the engine state is intact, so
    resuming with another run() finishes the drain."""
    from repro.serving import TickBudgetExceeded

    eng, _ = _engine(B=1, max_seq=32)
    eng.submit(Request(prompt=[1, 2], max_new_tokens=8))
    eng.submit(Request(prompt=[3], max_new_tokens=4))      # stays queued
    with pytest.raises(TickBudgetExceeded) as ei:
        eng.run(max_ticks=3)
    survivors = ei.value.survivors
    assert len(survivors) == 2
    assert all(r.truncated for r in survivors)
    in_flight = next(r for r in survivors if r.generated)
    assert 0 < len(in_flight.generated) < 8      # partial output intact
    fin = eng.run()                              # resume: budget refreshed
    assert len(fin) == 2 and all(r.done for r in fin)


def test_run_exact_tick_budget_no_false_truncation():
    """A drain that finishes exactly at the budget edge must NOT raise:
    the exhaustion check looks at remaining work, not loop count."""
    eng, _ = _engine(B=1, max_seq=32)
    eng.submit(Request(prompt=[1, 2], max_new_tokens=3))
    ticks_needed = 2 + 3  # prompt + generated tokens, serial path
    fin = eng.run(max_ticks=ticks_needed)
    assert len(fin) == 1 and not fin[0].truncated


def test_spec_stats_window_resets_between_snapshots():
    """Satellite regression: lifetime spec counters drift stale on a
    long-running server — the windowed snapshot isolates intervals."""
    api, dparams = _drafter()
    eng, _ = _engine(B=2, max_seq=32,
                     config=BestEffortConfig(level=OptLevel.O7,
                                             draft_model="smollm-360m",
                                             draft_k=2),
                     draft_model=api, draft_params=dparams)
    assert eng.spec_mode == "draft"
    eng.submit(Request(prompt=[5, 6], max_new_tokens=6))
    eng.run()
    w1 = eng.spec_stats_window(reset=True)
    assert w1["drafted"] == eng.spec_stats["drafted"] > 0
    # idle window: all-zero deltas, lifetime untouched
    w2 = eng.spec_stats_window(reset=True)
    assert w2["drafted"] == w2["emitted"] == 0
    assert w2["accept_rate"] == 0.0
    life_before = eng.spec_stats["drafted"]
    eng.submit(Request(prompt=[7], max_new_tokens=6))
    eng.run()
    w3 = eng.spec_stats_window(reset=True)
    assert w3["drafted"] == eng.spec_stats["drafted"] - life_before > 0
    # lifetime view accumulates across both windows
    assert eng.spec_stats["drafted"] == w1["drafted"] + w3["drafted"]


# ---------------------------------------------------------------------------
# CacheManager: the O0 rebuild path preserves survivors exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("level", [OptLevel.O0, OptLevel.O1, OptLevel.O5],
                         ids=lambda l: f"O{int(l)}")
def test_cache_reset_preserves_neighbor_slots_exactly(level):
    """reset_slots admitting into slot 1 must leave slots 0/2's cache
    slices bit-identical and zero slot 1 — at O0 via the full rebuild
    (fresh tree + copy-back), at O1 via in-place zeroing, at O5 via the
    packed donated call.  Previously only covered indirectly through
    end-to-end generation."""
    _, model, _ = _model()
    B = 3
    mgr = CacheManager(model, B, 16, level)
    key = jax.random.PRNGKey(42)
    filled = jax.tree.map(
        lambda leaf: jax.random.normal(key, leaf.shape).astype(leaf.dtype),
        mgr.cache)
    mgr.cache = filled
    before = jax.tree.map(np.asarray, filled)

    mgr.reset_slots([1], live=[0, 1, 2])

    for got, ref, bax in zip(jax.tree.leaves(mgr.cache),
                             jax.tree.leaves(before), mgr.batch_axes):
        got = np.asarray(got)
        for i in (0, 2):                          # survivors: bit-exact
            idx = [slice(None)] * got.ndim
            idx[bax] = i
            np.testing.assert_array_equal(got[tuple(idx)],
                                          np.asarray(ref)[tuple(idx)])
        idx = [slice(None)] * got.ndim
        idx[bax] = 1                              # admitted slot: zeroed
        assert not np.any(got[tuple(idx)])


def test_cache_rebuild_multi_admission_wave():
    """O0 rebuild with several slots admitted in one wave: every survivor
    preserved, every admitted slot zeroed."""
    _, model, _ = _model()
    mgr = CacheManager(model, 4, 16, OptLevel.O0)
    mgr.cache = jax.tree.map(
        lambda leaf: jnp.ones(leaf.shape, leaf.dtype), mgr.cache)
    before = jax.tree.map(np.asarray, mgr.cache)
    mgr.reset_slots([0, 3], live=[0, 1, 2, 3])
    for got, ref, bax in zip(jax.tree.leaves(mgr.cache),
                             jax.tree.leaves(before), mgr.batch_axes):
        got = np.asarray(got)
        for i, keep in enumerate((False, True, True, False)):
            idx = [slice(None)] * got.ndim
            idx[bax] = i
            if keep:
                np.testing.assert_array_equal(got[tuple(idx)],
                                              np.asarray(ref)[tuple(idx)])
            else:
                assert not np.any(got[tuple(idx)]), i


# ---------------------------------------------------------------------------
# Admission validation + retirement edges (regressions)
# ---------------------------------------------------------------------------

def test_request_too_long_rejected_at_submit():
    eng, _ = _engine(B=1, max_seq=8)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(Request(prompt=[1] * 6, max_new_tokens=6))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(prompt=[], max_new_tokens=2))


def test_zero_max_new_tokens_retires_immediately():
    """Regression: a max_new_tokens=0 request used to occupy a slot (and
    generate a token it never asked for); now it retires at submit with an
    empty completion and never blocks other traffic."""
    eng, _ = _engine(B=1, max_seq=8)
    rid0 = eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=0))
    assert eng.finished and eng.finished[0].rid == rid0
    assert eng.finished[0].generated == [] and eng.finished[0].done
    # a prompt filling the engine to the brim with nothing to generate
    rid1 = eng.submit(Request(prompt=[1] * 8, max_new_tokens=0))
    rid2 = eng.submit(Request(prompt=[4, 5], max_new_tokens=3))
    fin = {r.rid: r for r in eng.run()}
    assert set(fin) == {rid0, rid1, rid2}
    assert fin[rid1].generated == []
    assert len(fin[rid2].generated) == 3          # the slot was never pinned
    assert eng.n_steps == 4                       # only rid2's ticks


def test_prompt_ending_at_max_seq_boundary_retires():
    """A request whose prompt + budget lands exactly on max_seq finishes
    (possibly short) and frees its slot."""
    eng, _ = _engine(B=1, max_seq=8)
    rid = eng.submit(Request(prompt=[1] * 6, max_new_tokens=2))
    fin = eng.run()
    assert fin[0].rid == rid and 1 <= len(fin[0].generated) <= 2
    assert not any(s.active for s in eng.slots)
    # engine still serves after the boundary case
    eng.submit(Request(prompt=[2], max_new_tokens=2))
    assert len(eng.run()) == 2


# ---------------------------------------------------------------------------
# Scheduler policies + samplers
# ---------------------------------------------------------------------------

def test_spf_policy_admits_shortest_prompt_first():
    s = Scheduler(1, 32, policy="spf")
    s.submit(Request(prompt=[1] * 5, max_new_tokens=1))
    s.submit(Request(prompt=[1] * 2, max_new_tokens=1))
    s.submit(Request(prompt=[1] * 9, max_new_tokens=1))
    s.admit()
    assert s.slots[0].req.n_prompt == 2
    assert [r.n_prompt for r in s.queue] == [5, 9]   # order preserved
    with pytest.raises(ValueError, match="policy"):
        Scheduler(1, 32, policy="lifo")


def test_spf_end_to_end_matches_fcfs_outputs():
    eng, _ = _engine(B=2, max_seq=24, policy="spf")
    eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=4))
    eng.submit(Request(prompt=[9], max_new_tokens=3))
    eng.submit(Request(prompt=[3, 1, 4, 1], max_new_tokens=2))
    fin = {tuple(r.prompt): r.generated for r in eng.run()}
    ref_eng, _ = _engine(B=2, max_seq=24, policy="fcfs")
    for p in fin:
        ref_eng.submit(Request(prompt=list(p), max_new_tokens=10))
    ref = {tuple(r.prompt): r.generated for r in ref_eng.run()}
    for p, g in fin.items():
        assert ref[p][: len(g)] == g, p   # same greedy continuations


def test_stochastic_samplers_deterministic_per_seed():
    def gen(seed, kind="temperature", **kw):
        eng, _ = _engine(B=2, max_seq=24, sampler=SamplerConfig(
            kind=kind, seed=seed, **kw))
        eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=5))
        return eng.run()[0].generated

    a, b = gen(0, temperature=1.3), gen(0, temperature=1.3)
    assert a == b                         # same seed -> same tokens
    assert gen(1, temperature=1.3) != a   # different seed -> different
    cfg = _model()[0]
    topk = gen(0, kind="top_k", top_k=4, temperature=1.0)
    assert all(0 <= t < cfg.vocab for t in topk)
    with pytest.raises(ValueError, match="unknown sampler"):
        SamplerConfig(kind="beam")


@pytest.mark.parametrize("kind,kw", [("temperature", dict(temperature=1.3)),
                                     ("top_k", dict(top_k=4))])
def test_stochastic_samplers_deterministic_on_paged_paths(kind, kw):
    """Seeded temperature/top-k sampling on the paged O6 engine: the
    same seed draws the SAME tokens run-over-run on both the gather
    step and the block-table kernel (what lets the autotuner's
    interleaved repeats assert equal tokens under stochastic sampling),
    the two paged paths draw identical streams (their bf16 logits are
    bit-identical, so the seeded draw must be too), and a different
    seed actually moves the stream."""
    cfg = _model()[0]

    def gen(seed, paged_attn):
        eng, _ = _engine(B=2, max_seq=24,
                         config=BestEffortConfig(level=OptLevel.O6,
                                                 kv_block_size=4,
                                                 paged_attn=paged_attn),
                         sampler=SamplerConfig(kind=kind, seed=seed, **kw))
        eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=5))
        eng.submit(Request(prompt=[9, 2], max_new_tokens=4))
        return [r.generated for r in eng.run()]

    a = gen(0, "gather")
    assert gen(0, "gather") == a            # same seed -> same tokens
    k0 = gen(0, "kernel")
    assert gen(0, "kernel") == k0           # kernel path deterministic too
    assert k0 == a                          # identical logits, identical draw
    assert gen(7, "gather") != a            # seed actually steers the draw
    assert all(0 <= t < cfg.vocab for g in a for t in g)


# ---------------------------------------------------------------------------
# Speculative decoding (O7): pairing, gating, differential fuzz, properties
# ---------------------------------------------------------------------------

def test_compatible_drafter_resolves_and_validates():
    """The (target, drafter) pairing resolves at the target's scale and
    is vocab-checked: smoke cells share one token space, full-scale
    smollm/qwen3 tokenizers do not — that pair must fail loudly naming
    both vocab sizes, and unknown targets must name the known pairs."""
    from repro.configs import get_config
    from repro.models.model_zoo import DRAFTER_PAIRS, compatible_drafter

    tgt = get_smoke("qwen3-8b")
    d = compatible_drafter(tgt)                   # DRAFTER_PAIRS default
    assert d.name == "smollm-360m" and d.vocab == tgt.vocab
    assert compatible_drafter(tgt, "smollm-360m") == d   # explicit name
    assert compatible_drafter(tgt, d) == d        # ArchConfig passthrough

    # full scale: the real tokenizers diverge -> ValueError, both sizes
    full_t, full_d = get_config("qwen3-8b"), get_config("smollm-360m")
    assert full_t.vocab != full_d.vocab
    with pytest.raises(ValueError) as ei:
        compatible_drafter("qwen3-8b")
    assert str(full_t.vocab) in str(ei.value)
    assert str(full_d.vocab) in str(ei.value)

    # no pairing on file for this target -> actionable error
    assert "rwkv6-3b" not in DRAFTER_PAIRS
    with pytest.raises(ValueError, match="pairing"):
        compatible_drafter(get_smoke("rwkv6-3b"))


def test_spec_gating_degrades_never_fails():
    """Every missing precondition — no drafter, K=0, a stochastic
    sampler, a rung below O7, a model family without a verify step —
    turns speculation OFF (recorded in ``spec_mode``) while the engine
    keeps decoding the plain path."""
    api, dparams = _drafter()
    kw = dict(B=2, max_seq=24)

    on, _ = _engine(config=BestEffortConfig(level=OptLevel.O7),
                    draft_model=api, draft_params=dparams, **kw)
    assert on.spec_mode == "draft"

    cases = {
        "no drafter": _engine(
            config=BestEffortConfig(level=OptLevel.O7), **kw)[0],
        "draft_k=0": _engine(
            config=BestEffortConfig(level=OptLevel.O7, draft_k=0),
            draft_model=api, draft_params=dparams, **kw)[0],
        "stochastic": _engine(
            config=BestEffortConfig(level=OptLevel.O7),
            sampler=SamplerConfig(kind="temperature", temperature=1.3),
            draft_model=api, draft_params=dparams, **kw)[0],
        "below rung": _engine(
            config=BestEffortConfig(level=OptLevel.O5),
            draft_model=api, draft_params=dparams, **kw)[0],
        "no verify step": _engine(
            "rwkv6-3b",
            config=BestEffortConfig(level=OptLevel.O7,
                                    draft_model="smollm-360m"), **kw)[0],
    }
    for why, eng in cases.items():
        assert eng.spec_mode == "off", why
        assert eng.spec_stats["draft_k"] == 0, why
        eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=4))
        assert len(eng.run()[0].generated) == 4, why
    # off-engines decode exactly what the spec engine decodes (greedy)
    on.submit(Request(prompt=[5, 6, 7], max_new_tokens=4))
    assert on.run()[0].generated == cases["no drafter"].finished[0].generated


@pytest.mark.parametrize("seed,policy,k", [(31, "fcfs", 2), (32, "spf", 4),
                                           (33, "fcfs", 8)])
def test_differential_fuzz_speculative(seed, policy, k):
    """O7 draft/verify is bit-identical to the O5 reference on random
    request mixes — mid-flight arrivals, planted eos stops that land
    inside speculation windows, a pool small enough to queue admissions,
    both drafters (near-zero and full acceptance), and both paged
    attention steps.  Greedy rejection accepts exactly the target's
    argmax prefix, so ANY wrong acceptance would change tokens here."""
    cfg, _, _ = _model()
    mix = _random_mix(seed, cfg.vocab)
    ref = _run_mix(mix, OptLevel.O5, policy=policy)
    eos = {j: g[len(g) // 2] for j, g in enumerate(ref) if j % 2 == 0
           and len(g) > 1}
    ref = _run_mix(mix, OptLevel.O5, policy=policy, eos=eos, late_from=5)
    pool = dict(kv_block_size=4, kv_pool_blocks=14)
    for draft in ("zoo", "self"):
        spec = _run_mix(mix, OptLevel.O7, policy=policy, eos=eos,
                        late_from=5, draft=draft, draft_k=k, **pool)
        assert_tokens_match(ref, spec, EXACT,
                            f"spec/{draft} (seed={seed}, K={k})")
    kernel = _run_mix(mix, OptLevel.O7, policy=policy, eos=eos,
                      late_from=5, draft="self", draft_k=k,
                      paged_attn="kernel", **pool)
    assert_tokens_match(ref, kernel, EXACT,
                        f"spec/kernel (seed={seed}, K={k})")
    if seed == 31:
        # K=0 degeneracy: the O7 engine with speculation disabled IS O6
        off = _run_mix(mix, OptLevel.O7, policy=policy, eos=eos,
                       late_from=5, draft="zoo", draft_k=0, **pool)
        assert_tokens_match(ref, off, EXACT, "spec K=0 degeneracy")


def test_spec_self_draft_hits_the_acceptance_ceiling():
    """The target drafting for itself proposes exactly its own argmax,
    so greedy rejection accepts every window in full: accept_rate pins
    at 1.0 (the mechanism's ceiling) and each verify window emits more
    than one token.  Together with the zoo drafter's near-zero
    acceptance below, this pins BOTH directions of the rejection rule —
    never reject a matching draft, never accept a mismatched one (the
    fuzz above catches the latter as a token divergence)."""
    _, model, params = _model()
    eng, _ = _engine(B=2, max_seq=32,
                     config=BestEffortConfig(level=OptLevel.O7, draft_k=4),
                     draft_model=model, draft_params=params)
    for p, n in _WORKLOAD[:4]:
        eng.submit(Request(prompt=list(p), max_new_tokens=n))
    eng.run()
    st = eng.spec_stats
    assert st["spec_mode"] == "draft" and st["draft_k"] == 4
    assert st["drafted"] > 0 and st["accept_rate"] == 1.0
    assert st["eff_tok_per_step"] > 1.0


def test_spec_counters_consistent_and_blocks_conserved():
    """Under the rejecting zoo drafter: counters stay coherent
    (accepted <= drafted, >= one emitted token per verify window) and
    the paged block pool conserves after EVERY tick — rejected drafts
    roll the cache back but must never leak or double-free a block —
    with all blocks returned once the workload drains."""
    api, dparams = _drafter()
    eng, cfg = _engine(B=3, max_seq=32,
                       config=BestEffortConfig(level=OptLevel.O7,
                                               draft_k=4, kv_block_size=4,
                                               kv_pool_blocks=14),
                       draft_model=api, draft_params=dparams)
    assert eng.spec_mode == "draft"
    for p, n in _random_mix(41, cfg.vocab):
        eng.submit(Request(prompt=list(p), max_new_tokens=n))
    while eng.step() or eng.queue:
        eng.cache_mgr.check_conservation()
    st = eng.spec_stats
    assert 0.0 <= st["accept_rate"] <= 1.0
    assert st["accepted"] <= st["drafted"]
    assert st["emitted"] >= eng.counters.spec_windows >= 1
    eng.cache_mgr.check_conservation()
    assert all(h == 0 for h in eng.cache_mgr.held_blocks)
