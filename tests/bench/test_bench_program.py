"""What the serving program records of itself, as the benchmark reads
it: the request stamps the TTFT readers read (``bench/stamps.py``), the
tick reader, and the reductions of the program's spans
(``bench/program.py``) on a hand-made trace, on 0.4 s of a real chip
trace kept in ``data/`` (``qwen3_program_trace.json``) and on a traced
run on the CPU."""

import asyncio
import json
import types
from pathlib import Path

import pytest

from bench import program, run, stamps, trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000                     # nanoseconds


def _read(name, view):
    return run._load_module(run.BENCH / "metrics" / f"{name}.py").read(view)


# ---------------------------------------------------------------------------
# the stamps
# ---------------------------------------------------------------------------

def _rec(due, stamps, times=(), refused=False):
    """A client record whose request the program stamped with
    ``stamps`` (a dict of ``Request`` attributes)."""
    r = run.Rec(due=due, submit=due + 0.01, item=None)
    r.times = list(times)
    if not refused:
        r.handle = types.SimpleNamespace(
            request=types.SimpleNamespace(**stamps))
    return r


def _stamped(due, wait, span):
    arrival = due + 0.01
    return _rec(due, {"arrival_s": arrival, "admit_s": arrival,
                      "prefill_start_s": arrival + wait,
                      "first_token_s": arrival + wait + span},
                times=[arrival + wait + span + 0.001])


def _reader(name, recs, t_end=10.0):
    w = run.Window(t0=0.0, t_end=t_end, recs=recs, steps=[], quiet=[])
    view = run.RunView(window=w, trace=None, cfg={}, peaks={},
                       memory_peak_bytes=0)
    return _read(name, view)


def test_ttft_readers_split_the_programs_stamps():
    # ten requests due in the window waiting 0.1..1.0 s and spanning
    # 2.0..2.9 s; one from the lead-in (due before t0) is not read
    recs = [_stamped(0.5 * k, 0.1 * (k + 1), 2.0 + 0.1 * k)
            for k in range(10)]
    recs.append(_stamped(-5.0, 100.0, 100.0))
    assert _reader("prefill_wait_p90_s", recs) == pytest.approx(0.91)
    assert _reader("prefill_span_p90_s", recs) == pytest.approx(2.81)


def test_a_stamp_that_never_came_counts_to_the_close():
    late = _rec(1.0, {"arrival_s": 1.0, "admit_s": None,
                      "prefill_start_s": None, "first_token_s": None})
    recs = [_stamped(0.5, 0.1, 2.0), late, _rec(2.0, {}, refused=True)]
    recs[0].times.append(12.0)                  # the run's last token
    w = run.Window(t0=0.0, t_end=10.0, recs=recs, steps=[], quiet=[])
    view = types.SimpleNamespace(window=w)
    assert stamps.stamp_gaps(view, "arrival_s", "prefill_start_s") == \
        pytest.approx([0.1, 11.0, 12.0 - 2.01])
    assert stamps.stamp_gaps(view, "prefill_start_s", "first_token_s") == \
        pytest.approx([2.0, 0.0, 12.0 - 2.01])


def test_a_program_without_the_stamps_gives_nothing():
    """An older program's ``Request`` has no ``prefill_start_s``: the
    readers read nothing and the metrics are left out."""
    old = _rec(1.0, {"arrival_s": 1.0, "first_token_s": 2.0}, times=[2.0])
    for name in ("prefill_wait_p90_s", "prefill_span_p90_s"):
        assert _reader(name, [old]) is None, name
        assert _reader(name, []) is None, name


# ---------------------------------------------------------------------------
# the spans
# ---------------------------------------------------------------------------

def _hand():
    """A 100 ms window: the device busy 10-35, 36-40, 50-70 and 80-95 ms;
    a tick 5-45 ms that decodes and closes a prompt, a publish and a
    submit, then a tick 48-97 ms that only waits for the device."""
    ops = [["fusion.1", 10 * MS, 25 * MS], ["fusion.2", 36 * MS, 4 * MS],
           ["fusion.3", 50 * MS, 20 * MS], ["fusion.1", 80 * MS, 15 * MS]]
    host = [["bench.trace_window", 0, 100 * MS],
            ["bench.step", 5 * MS, 40 * MS], ["bench.step", 48 * MS, 49 * MS]]
    span = lambda name, a, b, **args: [name, a * MS, (b - a) * MS, args]
    prog = [span("serving.tick", 5, 45, n=2, gen=2, parked=0),
            span("serving.finalize", 5, 12),
            span("serving.fill", 12, 13),
            span("serving.decode", 13, 15),
            span("serving.tables", 13, 14),
            span("serving.prefill", 15, 40, rid=7, slot=1, start=128, n=64,
                 final=1),
            span("serving.first_token", 20, 40, rid=7),
            span("serving.advance", 40, 41),
            span("serving.admit", 41, 44, admitted=1, queued=0, gated=0),
            span("server.publish", 45, 46),
            span("server.submit", 46, 47, rid=8),
            span("serving.tick", 48, 97, n=1, gen=0, parked=1),
            span("serving.finalize", 48, 97)]
    return {"device": {trace.OPS: ops, trace.MODULES: []}, "host": host,
            "program": prog}


def test_idle_time_is_named_by_the_innermost_program_span():
    tr = _hand()
    got = program.idle_by_span(tr)
    want = {"serving.finalize": 19, program.OUTSIDE: 9, "serving.admit": 3,
            "serving.first_token": 1, "serving.advance": 1,
            "serving.tick": 1, "server.publish": 1, "server.submit": 1}
    assert got == pytest.approx({k: v / 1e3 for k, v in want.items()})
    assert list(got)[0] == "serving.finalize"
    assert sum(got.values()) == pytest.approx(
        trace.window_s(tr) - trace.busy_s(tr))
    assert program.program_doing(tr, 35 * MS, 36 * MS) == \
        "serving.first_token"
    assert program.program_doing(tr, 40 * MS, 50 * MS) == "serving.admit"
    assert program.program_doing(tr, 0, 5 * MS) == program.OUTSIDE


def test_ticks_their_children_and_the_decode_ticks():
    tr = _hand()
    ticks = program.program_spans(tr, "serving.tick")
    assert [(a, b) for a, b, _ in ticks] == [(5 * MS, 45 * MS),
                                             (48 * MS, 97 * MS)]
    assert ticks[0][2] == {"n": 2, "gen": 2, "parked": 0}
    assert [a for a, _, _ in program.decode_ticks(tr)] == [5 * MS]
    got = program.tick_anatomy(tr, top=1)
    assert got["ticks"] == 2
    assert got["child_share"] == pytest.approx(1 - 1 / 89)
    slow = got["slowest"][0]
    assert slow["ms"] == pytest.approx(49.0)
    assert slow["children_ms"] == pytest.approx({"serving.finalize": 49.0})
    assert slow["idle_ms"] == pytest.approx(2 + 10 + 2)


def _view(tr, steps, cfg=None):
    from bench import costs
    w = run.Window(t0=0.0, t_end=1.0, recs=[], steps=steps, quiet=[])
    return run.RunView(window=w, trace=tr, cfg=cfg or {},
                       peaks=costs.peaks("TPU v5 lite"),
                       memory_peak_bytes=14_000_000_000)


@pytest.mark.parametrize("active, want", [
    ((2, 1), 40.0 + 0.99 * 9.0),       # both ticks live: p99 of 40, 49 ms
    ((2, 0), 40.0),                    # an idle tick is not read
    ((0, 0), None),
])
def test_tick_reader_reads_the_live_ticks(active, want):
    steps = [run.Tick(0.0, 0.0, n, [], True) for n in active]
    got = _read("tick_p99_ms", _view(_hand(), steps))
    assert got == (None if want is None else pytest.approx(want))


def test_an_excerpt_keeps_the_events_that_overlap_it():
    tr = _hand()
    cut = program.excerpt(tr, 0.01, 30 * MS)
    assert trace.window(cut) == (30 * MS, 40 * MS)
    assert [n for n, *_ in cut["program"]] == [
        "serving.tick", "serving.prefill", "serving.first_token"]
    assert len(cut["device"][trace.OPS]) == 2
    assert program.idle_by_span(cut) == pytest.approx(
        {"serving.first_token": 1e-3})
    # the slowest decode tick starts at 5 ms: the cut starts 20 ms before
    assert trace.window(program.excerpt_slowest_tick(tr, 0.1))[0] == -15 * MS


def _recorded():
    return json.loads((DATA / "qwen3_program_trace.json").read_text())


def test_recorded_chip_trace_ticks_are_covered_by_their_phases():
    """On the chip, the phases of a tick cover at least 95% of its time,
    and each device idle gap of 1 ms or more lies in named program spans
    or outside every tick: at most 5% of it in a tick but no phase."""
    tr = _recorded()
    assert trace.window_s(tr) == pytest.approx(0.4)
    got = program.tick_anatomy(tr)
    assert got["ticks"] >= 2 and got["child_share"] >= 0.95
    segs = program.program_segments(tr)
    long_gaps = [(a, b) for a, b in trace.idle_gaps(tr) if b - a >= 1 * MS]
    assert long_gaps
    for a, b in long_gaps:
        # what is not under a phase is outside every tick, not in one
        unnamed = program.cover(segs, [(a, b)]).get("serving.tick", 0)
        assert unnamed <= 0.05 * (b - a), (a, b)
    names = {n for n, *_ in tr["program"]}
    assert {"serving.tick", "serving.finalize", "serving.fetch",
            "serving.decode", "serving.advance", "serving.admit",
            "serving.prestage"} <= names


def test_recorded_chip_trace_slowest_tick_waits_twice():
    """The excerpt holds the window's slowest tick: it closes a prompt, so
    it waits for the device in ``serving.fetch`` (the previous decode's
    tokens) and again in ``serving.first_token``, about two and a half
    ticks' device work."""
    tr = _recorded()
    slow = program.tick_anatomy(tr, top=1)["slowest"][0]
    assert slow["ms"] > 250
    top2 = list(slow["children_ms"])[:2]
    assert set(top2) == {"serving.first_token", "serving.fetch"}
    assert sum(slow["children_ms"][n] for n in top2) > 0.95 * slow["ms"]


def test_recorded_chip_trace_feeds_every_reader():
    tr = _recorded()
    idle = program.idle_by_span(tr)
    assert sum(idle.values()) == pytest.approx(
        trace.window_s(tr) - trace.busy_s(tr))
    assert 0 < trace.busy_s(tr) <= trace.window_s(tr)
    dec = trace.programs(tr, trace.DECODE_PROGRAM)
    assert len(dec) >= 2
    n_steps = sum(1 for n, *_ in tr["host"] if n == trace.STEP_SPAN)
    steps = [run.Tick(0.0, 0.0, 16, [2048] * 16, True)] * n_steps
    view = _view(tr, steps, run.load_config("qwen3-8b-9l"))
    assert 0 < _read("paged_attn_ms", view) < _read("decode_step_ms", view)
    assert _read("host_gap_ms", view) >= 0
    # each harness step span holds one program tick, 0.1 ms longer at most
    steps = [(t, t + d) for n, t, d in tr["host"] if n == trace.STEP_SPAN]
    for x, y, _ in program.program_spans(tr, program.TICK):
        a, b = next((a, b) for a, b in steps if a <= x and y <= b)
        assert (b - a) - (y - x) < 0.1 * MS


@pytest.mark.parametrize("name", ["qwen3_long_trace.json",
                                  "qwen3_program_trace.json"])
def test_tick_reader_on_the_recorded_chip_traces(name):
    """A tick lasts at least one decode step and at most the window; the
    tail is the tick that closed a prompt, two and a half steps long."""
    tr = json.loads((DATA / name).read_text())
    n_steps = sum(1 for n, *_ in tr["host"] if n == trace.STEP_SPAN)
    steps = [run.Tick(0.0, 0.0, 16, [2048] * 16, True)] * n_steps
    view = _view(tr, steps, run.load_config("qwen3-8b-9l"))
    got = _read("tick_p99_ms", view)
    step = _read("decode_step_ms", view)
    assert step < got <= 1e3 * trace.window_s(tr)
    if tr.get("program"):
        assert got > 2.5 * step


# ---------------------------------------------------------------------------
# one traced run on the CPU, its program spans kept beside the harness's
# ---------------------------------------------------------------------------

def test_a_traced_run_keeps_the_programs_record(tmp_path):
    """The tiny engine behind the front end, with the harness's own
    instrument, under a profiler: ``trace.record`` keeps the ``bench.*``
    spans as before, ``record_program`` the program's; each harness step
    holds one program tick, which the tick reader reads."""
    import jax

    from repro.core.optlevel import BestEffortConfig, OptLevel
    from repro.launch.server import AsyncServer
    from repro.models import get_model
    from repro.serving import DecodeEngine

    cfg = run.load_config("tiny-dense", DATA / "configs")
    model = get_model(run.arch_config(cfg))
    engine = DecodeEngine(
        model, model.init(jax.random.PRNGKey(0)), batch_size=3, max_seq=64,
        config=BestEffortConfig(level=OptLevel.O6, kv_block_size=8,
                                prefill_chunk=8))
    jobs = [([3 + k] * (5 + 7 * k), 4 + k) for k in range(5)]

    async def serve():
        server = AsyncServer(engine)
        inst = run.Instrument(engine, server, annotate=True)
        await server.start()
        inst.tracing = True
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            handles = [server.submit(p, max_new_tokens=n) for p, n in jobs]
            await asyncio.gather(*(h.done for h in handles))
        inst.tracing = False
        await server.stop()
        return inst.steps

    logdir = str(tmp_path / "trace")
    with jax.profiler.trace(logdir):
        steps = asyncio.run(serve())
    tr = trace.record(logdir)
    tr["program"] = program.record_program(logdir)

    assert all(n.startswith("bench.") for n, *_ in tr["host"])
    assert all(n.startswith(program.PREFIXES) for n, *_ in tr["program"])
    bench_steps = [(t, t + d) for n, t, d in tr["host"]
                   if n == trace.STEP_SPAN]
    ticks = program.program_spans(tr, program.TICK)
    assert len(bench_steps) == len(ticks) == len(steps)
    for (a, b), (x, y, _) in zip(bench_steps, ticks):
        assert a <= x and y <= b
    prefills = program.program_spans(tr, "serving.prefill")
    assert len({args["rid"] for _, _, args in prefills}) == len(jobs)
    # no device plane on the CPU: the whole window is idle, all named
    idle = program.idle_by_span(tr)
    assert sum(idle.values()) == pytest.approx(trace.window_s(tr))
    assert program.tick_anatomy(tr)["child_share"] > 0.5
    got = _read("tick_p99_ms", _view(tr, steps))
    assert 0 < got <= 1e3 * trace.window_s(tr)
