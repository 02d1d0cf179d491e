"""The serving program's own measurement (``serving/tracing.py``): spans
in the profiler's host plane, the counters, the request stamps, and that
a running profiler changes no token."""

import asyncio
import glob
import math
import os

import jax
import pytest

from repro.configs import get_smoke
from repro.core.optlevel import BestEffortConfig, OptLevel
from repro.launch.server import AsyncServer
from repro.models import get_model
from repro.serving import DecodeEngine, Request, tracing

CHUNK = 4
JOBS = [([5, 6, 7, 8, 9, 10, 11], 5), ([9, 3], 4), ([2] * 11, 3),
        ([11, 12, 13, 14], 6), ([4, 8, 1, 1, 3], 2)]
TICK_CHILDREN = {"serving.finalize", "serving.admit", "serving.fill",
                 "serving.decode", "serving.prefill", "serving.advance",
                 "serving.prestage"}


def _engine():
    cfg = get_smoke("qwen3-8b")
    model = get_model(cfg)
    return DecodeEngine(
        model, model.init(jax.random.PRNGKey(0)), batch_size=3, max_seq=32,
        config=BestEffortConfig(level=OptLevel.O6, kv_block_size=4,
                                prefill_chunk=CHUNK))


def _serve(eng):
    async def _run():
        async with AsyncServer(eng) as server:
            handles = [server.submit(p, max_new_tokens=n) for p, n in JOBS]
            done = await asyncio.gather(*(h.done for h in handles))
        return done, server.ticks

    return asyncio.run(_run())


def _host_events(logdir):
    """[(name, start_ns, end_ns, {arg: value})] of the program's spans."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serving.", "server.")):
                    out.append((e.name, int(e.start_ns), int(e.end_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    logdir = str(tmp_path_factory.mktemp("trace"))
    eng = _engine()
    with jax.profiler.trace(logdir):
        done, ticks = _serve(eng)
    return eng, done, ticks, _host_events(logdir)


def test_ticks_nest_their_phases(traced):
    _, _, ticks, events = traced
    tick_spans = [e for e in events if e[0] == "serving.tick"]
    assert len(tick_spans) >= ticks
    children = set()
    for name, a, b, _ in events:
        if name.startswith("serving.") and name != "serving.tick":
            assert any(ta <= a and b <= tb for _, ta, tb, _ in tick_spans), \
                name
            children.add(name)
    assert TICK_CHILDREN <= children
    assert {"serving.first_token", "serving.tables"} <= children
    for name, a, b, _ in events:
        parent = {"serving.first_token": "serving.prefill",
                  "serving.fetch": "serving.finalize"}.get(name)
        if parent is not None:
            assert any(n == parent and pa <= a and b <= pb
                       for n, pa, pb, _ in events), f"{name} nests"
    assert "serving.fetch" in children
    assert all({"n", "gen", "parked"} <= set(args)
               for *_, args in tick_spans[:ticks])


def test_request_spans_carry_their_rid(traced):
    _, done, _, events = traced
    rids = {r.rid for r in done}
    submits = [args["rid"] for n, *_, args in events if n == "server.submit"]
    assert sorted(submits) == sorted(rids)
    chunks = [args for n, *_, args in events if n == "serving.prefill"]
    assert {a["rid"] for a in chunks} == rids
    for r in done:
        mine = [a for a in chunks if a["rid"] == r.rid]
        assert [a["start"] for a in mine] == list(
            range(0, r.n_prompt, CHUNK))
        assert [a["final"] for a in mine] == [0] * (len(mine) - 1) + [1]
        assert sum(a["n"] for a in mine) == r.n_prompt
    firsts = [args["rid"] for n, *_, args in events
              if n == "serving.first_token"]
    assert sorted(firsts) == sorted(rids)
    assert any(n == "server.publish" for n, *_ in events)


def test_counters_add_up(traced):
    eng, done, ticks, events = traced
    c = eng.counters
    assert c.admissions == len(JOBS)
    assert c.prefill_chunks == sum(math.ceil(len(p) / CHUNK)
                                   for p, _ in JOBS)
    assert c.prefill_tokens == sum(len(p) for p, _ in JOBS)
    assert c.first_token_syncs == len(JOBS)
    # every token but a request's first comes from a decode dispatch
    assert c.decode_slots == sum(len(r.generated) for r in done) - len(done)
    tick_gen = [args.get("gen", 0) for n, *_, args in events
                if n == "serving.tick"]
    assert c.decode_slots == sum(tick_gen)
    assert c.decode_dispatches == sum(1 for n, *_ in events
                                      if n == "serving.decode")
    assert c.ticks == ticks
    assert c.table_uploads == sum(1 for n, *_ in events
                                  if n == "serving.tables") >= 1
    assert c.admit_gated_slots >= 1          # five requests, three slots
    assert c.spec_windows == 0


def test_stamps_are_ordered(traced):
    _, done, _, _ = traced
    for r in done:
        assert (r.arrival_s <= r.admit_s <= r.prefill_start_s
                <= r.first_token_s <= r.finish_s), r.rid


def test_a_profiler_changes_no_token(traced):
    _, done, _, _ = traced
    plain, _ = _serve(_engine())
    assert ([r.generated for r in plain] == [r.generated for r in done])


def test_pool_usage_reserved_against_context():
    eng = _engine()
    for p, n in JOBS[:3]:
        eng.submit(Request(prompt=list(p), max_new_tokens=n))
    eng.step()
    eng.step()
    mgr = eng.cache_mgr
    lengths = [s.pos if s.active else 0 for s in eng.slots]
    u = mgr.pool_usage(lengths)
    assert u["pool_blocks"] == mgr.pool_blocks
    assert u["reserved_blocks"] == sum(mgr.held_blocks) > 0
    assert 0 < u["context_blocks"] <= u["reserved_blocks"]
    eng.run()
    assert mgr.pool_usage([0] * eng.B)["reserved_blocks"] == 0


def test_counter_intervals_and_compiles(traced):
    c = tracing.Counters(ticks=5, admissions=2)
    snap = c.snapshot()
    c.ticks += 3
    assert c.since(snap) == tracing.Counters(ticks=3)
    assert snap.ticks == 5
    names = tracing.compiles()
    assert any("_fused" in n for n in names)
    assert all(v >= 1 for v in names.values())
    assert tracing.compile_seconds() > 0


def test_the_cache_manager_counts_into_the_engines_record():
    eng = _engine()
    assert eng.cache_mgr.counters is eng.counters
    eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=2))
    eng.run()
    assert eng.counters.table_uploads >= 1
