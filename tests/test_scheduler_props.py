"""Property-based scheduler + block-allocator tests (satellite of the O6
paged-cache work): random admit/retire/eos traffic must preserve the
bookkeeping invariants the serving engine's correctness rests on —

  * no slot double-occupancy (an active request lives in exactly one slot);
  * admission order respects the policy (fcfs: arrival order, no
    head-of-line bypass even when the block gate queues the head; spf:
    the admitted request has the shortest prompt in the queue);
  * block free-list conservation under the paged path: held + free ==
    total, no block held twice or both held and free, retired slots hold
    nothing — across BOTH tick protocols (serial ``advance`` and the
    overlapped ``tick_advance``/``finalize`` split).
"""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.serving import PagedAllocator, Request, Scheduler
from repro.serving.paged import BlockAllocator, blocks_for


# ---------------------------------------------------------------------------
# BlockAllocator: the free list itself
# ---------------------------------------------------------------------------

def test_block_allocator_basics():
    a = BlockAllocator(4)
    assert a.free_blocks == 4 and a.used_blocks == 0
    got = a.allocate(3)
    assert len(got) == len(set(got)) == 3
    assert all(1 <= b <= 4 for b in got)        # block 0 is NULL, reserved
    assert a.free_blocks == 1
    with pytest.raises(RuntimeError, match="exhausted"):
        a.allocate(2)
    a.release(got[:2])
    assert a.free_blocks == 3
    with pytest.raises(RuntimeError, match="free"):
        a.release([got[0]])                      # double free
    with pytest.raises(RuntimeError, match="free"):
        a.release([99])                          # out of range
    b = a.append()
    assert 1 <= b <= 4 and a.free_blocks == 2


def test_block_allocator_defrag_takes_lowest_ids():
    a = BlockAllocator(8, defrag=True)
    first = a.allocate(6)
    a.release(first)                             # free list now shuffled
    assert a.allocate(3) == [1, 2, 3]


def test_blocks_for_arithmetic():
    assert blocks_for(0, 4) == 0
    assert blocks_for(1, 4) == 1
    assert blocks_for(4, 4) == 1
    assert blocks_for(5, 4) == 2


def test_paged_allocator_small_pool_gates_at_submit_not_construction():
    """A pool smaller than one max_seq reservation is a legal config
    (real mixes rarely reserve the full horizon).  The never-fits check
    moved to the SUBMIT boundary: ``infeasible_reason`` names requests
    whose reservation exceeds the total pool, and a scheduler wired with
    it rejects them at submit() — feasible requests still queue/admit."""
    pa = PagedAllocator(2, 32, block_size=4, pool_blocks=7)
    sched = Scheduler(2, 32, policy="fcfs")
    sched.admission_gate = pa.can_admit
    sched.submit_gate = pa.infeasible_reason
    sched.on_admit = pa.admit_slot
    sched.on_retire = pa.release_slot
    # needs 8 blocks > 7 in the whole pool: rejected with a clear error
    with pytest.raises(ValueError, match="never fit the total pool"):
        sched.submit(Request(prompt=[1] * 16, max_new_tokens=16))
    assert not sched.queue and not sched.finished
    # 28-token reservation = 7 blocks = the whole pool: feasible
    sched.submit(Request(prompt=[2] * 20, max_new_tokens=8))
    assert sched.admit() == [0]
    _check_invariants(sched, pa)


def test_submit_without_gate_still_static_only():
    """No submit_gate wired (contiguous layout): only the static
    max_seq validation applies, exactly as before."""
    sched = Scheduler(2, 32)
    sched.submit(Request(prompt=[1] * 16, max_new_tokens=16))
    assert len(sched.queue) == 1


# ---------------------------------------------------------------------------
# Random traffic against the real Scheduler + PagedAllocator wiring
# ---------------------------------------------------------------------------

def _check_invariants(sched, pa):
    # no double occupancy: an active request sits in exactly one slot
    active = [s.req for s in sched.slots if s.active]
    assert len({id(r) for r in active}) == len(active)
    assert not any(r.done for r in active)
    # free-list conservation + table/held consistency
    pa.check_conservation()
    for i, s in enumerate(sched.slots):
        if not s.active:
            assert pa._held[i] == 0, f"retired slot {i} still holds blocks"
        else:
            assert pa._held[i] == pa.blocks_needed(s.req)


def _run_scenario(seed: int, policy: str, split_protocol: bool):
    rng = np.random.default_rng(seed)
    n_slots = int(rng.integers(1, 5))
    block_size = int(rng.integers(1, 6))
    max_seq = int(rng.integers(8, 33))
    per_seq = blocks_for(max_seq, block_size)
    # pool between "one max request" and "every slot maxed": small pools
    # force the admission gate to queue
    pool = int(rng.integers(per_seq, n_slots * per_seq + 1))
    pa = PagedAllocator(n_slots, max_seq, block_size=block_size,
                        pool_blocks=pool)
    sched = Scheduler(n_slots, max_seq, policy=policy)
    sched.admission_gate = pa.can_admit
    admitted_log = []

    def on_admit(i, req):
        pa.admit_slot(i, req)
        admitted_log.append(req)
        if policy == "fcfs":
            # no head-of-line bypass: everything still queued arrived later
            assert all(req.rid < q.rid for q in sched.queue)
        else:
            # spf with aging: nothing EFFECTIVELY shorter (prompt length
            # minus waves spent queued, rid tiebreak) was left behind
            key = sched.effective_prompt_len
            assert all((key(req), req.rid) <= (key(q), q.rid)
                       for q in sched.queue)

    sched.on_admit = on_admit
    sched.on_retire = pa.release_slot

    EOS = 7
    submitted = 0
    for _ in range(int(rng.integers(10, 40))):
        # random submissions (some degenerate / eos-bearing)
        for _ in range(int(rng.integers(0, 3))):
            plen = int(rng.integers(1, max_seq))
            new = int(rng.integers(0, max_seq - plen + 1))
            sched.submit(Request(
                prompt=[int(t) for t in rng.integers(1, 50, plen)],
                max_new_tokens=new,
                eos_id=EOS if rng.random() < 0.5 else None))
            submitted += 1
        sched.admit()
        _check_invariants(sched, pa)
        active = sched.active_indices
        toks = {i: int(rng.integers(1, 10)) for i in active}  # may hit EOS
        if split_protocol:
            emissions = sched.tick_advance(active)
            _check_invariants(sched, pa)          # freed under running step
            sched.admit()                         # overlapped refill
            _check_invariants(sched, pa)
            sched.finalize(emissions, toks)
        else:
            for i in active:
                sched.advance(i, toks[i])
        _check_invariants(sched, pa)

    # drain: every submitted request eventually finishes and every block
    # comes home
    for _ in range(10_000):
        if not sched.has_work():
            break
        sched.admit()
        active = sched.active_indices
        toks = {i: int(rng.integers(1, 10)) for i in active}
        if split_protocol:
            emissions = sched.tick_advance(active)
            sched.finalize(emissions, toks)
        else:
            for i in active:
                sched.advance(i, toks[i])
        _check_invariants(sched, pa)
    assert not sched.has_work(), "scenario failed to drain (deadlock?)"
    assert len(sched.finished) == submitted
    assert pa.free_blocks == pool, "blocks leaked after full drain"
    # fcfs admitted exactly in arrival order
    if policy == "fcfs":
        rids = [r.rid for r in admitted_log]
        assert rids == sorted(rids)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_random_traffic_fcfs_serial(seed):
    _run_scenario(seed, "fcfs", split_protocol=False)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_random_traffic_fcfs_split(seed):
    _run_scenario(seed, "fcfs", split_protocol=True)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_random_traffic_spf_serial(seed):
    _run_scenario(seed, "spf", split_protocol=False)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_random_traffic_spf_split(seed):
    _run_scenario(seed, "spf", split_protocol=True)


# ---------------------------------------------------------------------------
# Chunked prefill bookkeeping: random chunked-prefill + decode traffic
# through the exact protocol the engine drives (one chunk grant per tick
# to the head of ``prefill_queue``; the final chunk ends in ``advance``).
# ---------------------------------------------------------------------------

def _run_chunked_scenario(seed: int, policy: str):
    rng = np.random.default_rng(seed)
    n_slots = int(rng.integers(1, 5))
    block_size = int(rng.integers(1, 6))
    max_seq = int(rng.integers(8, 33))
    C = int(rng.integers(1, 9))                   # prefill chunk width
    per_seq = blocks_for(max_seq, block_size)
    pool = int(rng.integers(per_seq, n_slots * per_seq + 1))
    pa = PagedAllocator(n_slots, max_seq, block_size=block_size,
                        pool_blocks=pool)
    sched = Scheduler(n_slots, max_seq, policy=policy)
    sched.admission_gate = pa.can_admit
    sched.on_admit = pa.admit_slot
    sched.on_retire = pa.release_slot

    grants = {}          # rid -> prefill chunk grants received
    admit_tick = {}      # rid -> tick the slot was admitted
    first_emit = {}      # rid -> tick of the first generated token
    submitted = 0
    tick = 0

    def serve_one_tick():
        nonlocal tick
        tick += 1
        sched.admit()
        for i, s in enumerate(sched.slots):
            if s.active and s.req.rid not in admit_tick:
                admit_tick[s.req.rid] = tick
        _check_invariants(sched, pa)
        # prefill-queue ordering respects the admission policy
        pf = sched.prefill_queue()
        assert all(sched.slots[i].active
                   and sched.slots[i].pos < sched.slots[i].req.n_prompt
                   for i in pf)
        if policy == "fcfs":
            rids = [sched.slots[i].req.rid for i in pf]
            assert rids == sorted(rids), "fcfs prefill queue out of order"
        else:
            rem = [(sched.slots[i].req.n_prompt - sched.slots[i].pos,
                    sched.slots[i].req.rid) for i in pf]
            assert rem == sorted(rem), "spf prefill queue out of order"
        # one chunk grant to the head (the engine's _prefill_tick)
        if pf:
            i = pf[0]
            s = sched.slots[i]
            r = s.req
            grants[r.rid] = grants.get(r.rid, 0) + 1
            n = min(C, r.n_prompt - s.pos)
            if s.pos + n == r.n_prompt:
                sched.advance_chunk(i, n - 1)
                sched.advance(i, int(rng.integers(1, 10)))
                first_emit.setdefault(r.rid, tick)
            else:
                sched.advance_chunk(i, n)
        # decode tick for every generating slot (pos past the prompt)
        for i in sched.active_indices:
            s = sched.slots[i]
            if s.req is not None and s.pos >= s.req.n_prompt:
                sched.advance(i, int(rng.integers(1, 10)))
        _check_invariants(sched, pa)

    EOS = 7
    for _ in range(int(rng.integers(10, 40))):
        for _ in range(int(rng.integers(0, 3))):
            plen = int(rng.integers(1, max_seq))
            new = int(rng.integers(1, max_seq - plen + 1))
            sched.submit(Request(
                prompt=[int(t) for t in rng.integers(1, 50, plen)],
                max_new_tokens=new,
                eos_id=EOS if rng.random() < 0.5 else None))
            submitted += 1
        serve_one_tick()

    for _ in range(10_000):
        if not sched.has_work():
            break
        serve_one_tick()
    assert not sched.has_work(), "chunked scenario failed to drain"
    assert len(sched.finished) == submitted
    assert pa.free_blocks == pool, "blocks leaked after chunked drain"
    # the stall bound: a slot's prefill occupies EXACTLY
    # ceil(n_prompt / C) chunk grants — no slot re-enters the prefill
    # queue once generating, none is starved into extra grants
    by_rid = {r.rid: r for r in sched.finished}
    for rid, g in grants.items():
        P = by_rid[rid].n_prompt
        assert g == -(-P // C), (
            f"rid {rid}: {g} chunk grants for prompt {P} at chunk {C}")
    # every admitted slot emitted within (queue-serialized) bound: its
    # own grants plus every grant spent on other slots while it waited
    for rid, t0 in admit_tick.items():
        assert rid in first_emit, f"rid {rid} admitted but never emitted"


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_random_chunked_traffic_fcfs(seed):
    _run_chunked_scenario(seed, "fcfs")


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_random_chunked_traffic_spf(seed):
    _run_chunked_scenario(seed, "spf")


def test_advance_chunk_rejects_overrun():
    sched = Scheduler(1, 16)
    sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
    sched.admit()
    with pytest.raises(AssertionError, match="overruns"):
        sched.advance_chunk(0, 3)          # chunk may not consume token 2
    sched.advance_chunk(0, 2)
    assert sched.slots[0].pos == 2


def test_place_occupies_at_post_prompt_position():
    """``place`` (the insert phase) occupies a free slot at
    ``pos = n_prompt - 1`` — the next ``advance`` emits — fires
    ``on_admit`` exactly once, and refuses occupied slots."""
    pa = PagedAllocator(2, 16, block_size=4, pool_blocks=8)
    sched = Scheduler(2, 16)
    sched.on_admit = pa.admit_slot
    sched.on_retire = pa.release_slot
    req = Request(prompt=[1, 2, 3], max_new_tokens=2)
    req.rid = 0
    sched.place(req, 1)
    assert sched.slots[1].pos == 2 and pa.held_blocks == [0, 2]
    assert sched.prefill_queue() == [1]    # last prompt token pending
    sched.advance(1, 5)                    # emits the first token
    assert req.generated == [5] and sched.prefill_queue() == []
    with pytest.raises(ValueError, match="occupied"):
        sched.place(Request(prompt=[9], max_new_tokens=1, rid=1), 1)
    sched.advance(1, 6)                    # budget reached: retires
    assert req.done and pa.free_blocks == 8


# ---------------------------------------------------------------------------
# grow_slot: the chunked-admission block arithmetic
# ---------------------------------------------------------------------------

def test_grow_slot_never_double_counts_shared_block():
    """Growing by TOTALS: a chunk ending mid-block shares its active
    block with the next chunk, so consecutive grows allocate
    ``blocks_for(total) - held`` — never per-chunk ceil sums."""
    pa = PagedAllocator(1, 32, block_size=4, pool_blocks=8)
    assert pa.grow_slot(0, 6) == 2         # covers tokens 0..5
    assert pa.grow_slot(0, 7) == 0         # same final block: no alloc
    assert pa.grow_slot(0, 9) == 1         # one more block
    assert pa._held[0] == 3 and pa.free_blocks == 5
    assert pa.grow_slot(0, 9) == 0         # idempotent
    assert pa.grow_slot(0, 100) == 5       # clips to max_seq (32 tokens)
    assert pa._held[0] == 8
    pa.check_conservation()


def test_grow_slot_queue_then_admit_neither_leaks_nor_deadlocks():
    """Queue-then-admit under a constrained pool: a reservation the gate
    defers admits after retirements free blocks, and a full drain
    returns every block (the reservation arithmetic leaks nothing)."""
    pa = PagedAllocator(2, 16, block_size=4, pool_blocks=5)
    sched = Scheduler(2, 16, policy="fcfs")
    sched.admission_gate = pa.can_admit
    sched.on_admit = pa.admit_slot
    sched.on_retire = pa.release_slot
    sched.submit(Request(prompt=[1] * 10, max_new_tokens=2))  # 3 blocks
    sched.submit(Request(prompt=[2] * 10, max_new_tokens=2))  # must queue
    assert sched.admit() == [0] and sched.admit() == []
    # chunked prefill (C=4) on the admitted slot; the queued request
    # stays gated throughout
    C = 4
    for _ in range(20):
        pf = sched.prefill_queue()
        if pf:
            i = pf[0]
            s = sched.slots[i]
            n = min(C, s.req.n_prompt - s.pos)
            if s.pos + n == s.req.n_prompt:
                sched.advance_chunk(i, n - 1)
                sched.advance(i, 3)
            else:
                sched.advance_chunk(i, n)
        else:
            for i in sched.active_indices:
                sched.advance(i, 3)
        _check_invariants(sched, pa)
        sched.admit()
        if not sched.has_work():
            break
    assert not sched.has_work(), "constrained pool deadlocked"
    assert len(sched.finished) == 2
    assert pa.free_blocks == 5, "blocks leaked"


# ---------------------------------------------------------------------------
# The block-granularity admission gate (the satellite fix): a request that
# fits max_seq but not the free blocks queues — never raises — and admits
# once retirements free the pool.
# ---------------------------------------------------------------------------

def test_block_exhaustion_queues_instead_of_raising():
    pa = PagedAllocator(2, 16, block_size=4, pool_blocks=5)
    sched = Scheduler(2, 16, policy="fcfs")
    sched.admission_gate = pa.can_admit
    sched.on_admit = pa.admit_slot
    sched.on_retire = pa.release_slot

    # 12-token reservation = 3 blocks; two of them exceed the 5-block pool
    sched.submit(Request(prompt=[1] * 8, max_new_tokens=4))
    sched.submit(Request(prompt=[2] * 8, max_new_tokens=4))   # must queue
    assert sched.admit() == [0]
    assert len(sched.queue) == 1 and pa.free_blocks == 2
    assert sched.admit() == []                 # still gated, still queued
    # drain the first request; its retirement frees the blocks
    for _ in range(11):
        for i in sched.active_indices:
            sched.advance(i, 3)
    assert not sched.slots[0].active
    assert sched.admit() == [0]                # queued request admits now
    assert sched.queue == type(sched.queue)()
    pa.check_conservation()


def test_gate_preserves_fcfs_no_bypass():
    """A small request behind a gated big one must NOT jump the queue
    under fcfs."""
    pa = PagedAllocator(2, 16, block_size=4, pool_blocks=5)
    sched = Scheduler(2, 16, policy="fcfs")
    sched.admission_gate = pa.can_admit
    sched.on_admit = pa.admit_slot
    sched.on_retire = pa.release_slot
    sched.submit(Request(prompt=[1] * 8, max_new_tokens=4))   # 3 blocks
    sched.submit(Request(prompt=[2] * 8, max_new_tokens=4))   # gated head
    sched.submit(Request(prompt=[3], max_new_tokens=2))       # 1 block
    assert sched.admit() == [0]
    assert sched.admit() == []                 # head gated; no bypass
    assert [r.n_prompt for r in sched.queue] == [8, 1]


# ---------------------------------------------------------------------------
# spf aging (satellite fix): under sustained open-loop arrivals of short
# requests, pure shortest-prompt-first starves a long prompt FOREVER —
# every wave a fresh shorter request outranks it.  With aging, a queued
# request's effective length decays one token per admission wave, so every
# request is admitted within a bounded number of waves.
# ---------------------------------------------------------------------------

def _spf_starvation_scenario(seed: int) -> int:
    """One slot, adversarial traffic: every tick submits a fresh 1-token
    request (always the spf minimum by raw length) that completes in one
    advance.  Returns the number of waves until the long prompt admits —
    under pure spf this loop never terminates."""
    rng = np.random.default_rng(seed)
    max_seq = 64
    long_len = int(rng.integers(8, 32))
    sched = Scheduler(1, max_seq, policy="spf")
    long_req = Request(prompt=[9] * long_len, max_new_tokens=2)
    sched.submit(long_req)
    bound = long_len + 3     # aging decays one token per wave, + slack
    for wave in range(bound):
        sched.submit(Request(prompt=[int(rng.integers(1, 9))],
                             max_new_tokens=1))
        sched.admit()
        i = sched.active_indices[0]
        if sched.slots[i].req is long_req:
            return wave
        # the short admitted: drain it in one advance so the slot frees
        sched.advance(i, 3)
        assert not sched.slots[i].active
    raise AssertionError(
        f"long prompt ({long_len} tokens) starved for {bound} waves "
        f"(queue lengths: {[r.n_prompt for r in sched.queue]})")


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_spf_aging_prevents_starvation(seed):
    _spf_starvation_scenario(seed)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_spf_every_queued_request_eventually_admitted(seed):
    """The aging guarantee under random mixed traffic: run a bounded
    number of adversarial waves (fresh short arrivals each tick), then
    count that every request submitted in the FIRST wave has been
    admitted within n_prompt + queue-drain slack waves."""
    rng = np.random.default_rng(seed)
    max_seq = 32
    sched = Scheduler(2, max_seq, policy="spf")
    first_wave = [Request(prompt=[1] * int(rng.integers(2, max_seq - 2)),
                          max_new_tokens=1) for _ in range(3)]
    for r in first_wave:
        sched.submit(r)
    admitted = set()

    def on_admit(i, req):
        admitted.add(req.rid)

    sched.on_admit = on_admit
    # worst case: every first-wave request must out-age the adversarial
    # stream one after another, at one slot-free wave each
    bound = sum(r.n_prompt for r in first_wave) + 3 * len(first_wave)
    for _ in range(bound):
        sched.submit(Request(prompt=[2], max_new_tokens=1))
        sched.admit()
        for i in sched.active_indices:
            sched.advance(i, 3)          # max_new=1: retires immediately
        if all(r.rid in admitted for r in first_wave):
            break
    assert all(r.rid in admitted for r in first_wave), (
        f"first-wave requests starved after {bound} waves: "
        f"{[(r.rid, r.n_prompt) for r in first_wave if r.rid not in admitted]}")


def test_deadline_policy_admits_edf_order():
    """The deadline policy admits earliest-deadline-first regardless of
    arrival order; requests without a deadline sort last."""
    sched = Scheduler(1, 32, policy="deadline")
    a = Request(prompt=[1, 1], max_new_tokens=1)               # no deadline
    b = Request(prompt=[2, 2], max_new_tokens=1, deadline_s=50.0)
    c = Request(prompt=[3, 3], max_new_tokens=1, deadline_s=10.0)
    for r in (a, b, c):
        sched.submit(r)
    order = []
    sched.on_admit = lambda i, req: order.append(req)
    for _ in range(20):
        sched.admit()
        for i in sched.active_indices:
            sched.advance(i, 4)
            sched.advance(i, 4)
        if not sched.has_work():
            break
    assert order == [c, b, a]


def test_deadline_policy_prefill_queue_orders_by_deadline():
    sched = Scheduler(3, 32, policy="deadline")
    a = Request(prompt=[1] * 4, max_new_tokens=2)
    b = Request(prompt=[2] * 4, max_new_tokens=2, deadline_s=5.0)
    c = Request(prompt=[3] * 4, max_new_tokens=2, deadline_s=1.0)
    for r in (a, b, c):
        sched.submit(r)
    sched.admit()
    pf = sched.prefill_queue()
    assert [sched.slots[i].req for i in pf] == [c, b, a]


# ---------------------------------------------------------------------------
# StatePool: the state-row sibling of the block allocator (recurrent
# families).  Same conservation discipline, but a slot holds exactly one
# O(1) row for its whole lifetime — no reservation arithmetic.
# ---------------------------------------------------------------------------

from repro.serving.paged import NULL_ROW, StatePool  # noqa: E402


def test_state_pool_basics():
    pool = StatePool(3, n_rows=2)
    assert pool.free_rows == 2 and pool.used_rows == 0
    assert pool.can_admit()
    assert pool.infeasible_reason(Request(prompt=[1] * 30,
                                          max_new_tokens=100)) is None

    pool.admit_slot(0)
    pool.admit_slot(2)
    pool.check_conservation()
    assert pool.free_rows == 0 and pool.used_rows == 2
    assert not pool.can_admit()
    assert int(pool.rows[1]) == NULL_ROW
    # distinct real rows, handed out lowest-first
    assert sorted(int(r) for r in pool.rows if r != NULL_ROW) == [1, 2]

    with pytest.raises(RuntimeError, match="admitted while holding"):
        pool.admit_slot(0)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.admit_slot(1)

    pool.release_slot(1)                     # releasing an empty slot: no-op
    assert pool.free_rows == 0
    pool.release_slot(0)
    pool.check_conservation()
    assert pool.free_rows == 1 and pool.used_rows == 1
    pool.admit_slot(0)

    # a corrupted alias (two slots claiming one row) must trip the
    # double-free guard on the second release
    pool.rows[1] = pool.rows[0]
    pool.release_slot(0)
    with pytest.raises(RuntimeError, match="double/invalid free"):
        pool.release_slot(1)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_state_pool_random_traffic_conserves_rows(seed):
    """held + free == total and no double-occupancy under random
    admit/retire/eos traffic driven through the scheduler hooks (the
    wiring the paged layout uses for recurrent families)."""
    rng = np.random.default_rng(seed)
    B = int(rng.integers(2, 5))
    pool = StatePool(B, n_rows=int(rng.integers(1, B + 1)))
    sched = Scheduler(B, 16)
    sched.admission_gate = pool.can_admit
    sched.on_admit = pool.admit_slot
    sched.on_retire = pool.release_slot

    for _ in range(60):
        if rng.random() < 0.5:
            sched.submit(Request(
                prompt=[1] * int(rng.integers(1, 6)),
                max_new_tokens=int(rng.integers(1, 6)), eos_id=0))
        for i in sched.admit():
            assert int(pool.rows[i]) != NULL_ROW
        pool.check_conservation()
        # every active slot holds exactly one real row; idle slots none
        for i, slot in enumerate(sched.slots):
            held = int(pool.rows[i]) != NULL_ROW
            assert held == slot.active, (i, slot)
        for i in list(sched.active_indices):
            # advance; sometimes force a surprise eos mid-generation
            tok = 0 if rng.random() < 0.1 else int(rng.integers(3, 9))
            sched.advance(i, tok)
        pool.check_conservation()
    assert pool.used_rows == sum(s.active for s in sched.slots)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_state_pool_defrag_packs_rows_and_preserves_mapping(seed):
    """compaction_moves packs held rows into the lowest ids in slot
    order; apply_moves rewrites the map consistently (bit-exactness of
    the device copies is covered by the serving differential tests —
    here we pin that the *plan* is a permutation the manager can apply)."""
    rng = np.random.default_rng(seed)
    B = int(rng.integers(2, 8))
    pool = StatePool(B)
    # random churn to fragment the row map
    for _ in range(40):
        i = int(rng.integers(0, B))
        if int(pool.rows[i]) == NULL_ROW and pool.can_admit():
            pool.admit_slot(i)
        elif rng.random() < 0.6:
            pool.release_slot(i)
    pool.check_conservation()
    before = {i: int(r) for i, r in enumerate(pool.rows) if r != NULL_ROW}

    moves = pool.compaction_moves()
    # valid plan for the manager's simultaneous snapshot copy
    # (``leaf.at[dst].set(leaf[src])``): sources held, destinations
    # distinct, and no destination clobbers a held row that is NOT
    # itself relocated by the same plan.
    held = set(before.values())
    assert set(moves) <= held
    assert len(set(moves.values())) == len(moves)
    assert not set(moves.values()) & (held - set(moves))
    pool.apply_moves(moves)
    pool.check_conservation()

    after = {i: int(r) for i, r in enumerate(pool.rows) if r != NULL_ROW}
    assert set(after) == set(before)          # same slots occupied
    n = len(after)
    assert sorted(after.values()) == list(range(1, n + 1))
    # slot order preserved: lower slot index -> lower packed row id
    packed = [after[i] for i in sorted(after)]
    assert packed == sorted(packed)
    # idempotent: a second plan is empty
    assert pool.compaction_moves() == {}
