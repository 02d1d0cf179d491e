"""The serving program's own spans in a profiler trace, reduced.

The program marks each phase of a tick with a host span (``serving.*``,
``server.*``; ``serving/tracing.py``), with its metadata (a request's
``rid``, a tick's slot counts).  ``trace.record`` keeps only the
benchmark's own ``bench.*`` spans; a caller that keeps the trace
directory of a traced run reads the program's spans beside them::

    tr = trace.record(logdir)
    tr["program"] = program.record_program(logdir)
    program.idle_by_span(tr)        # device idle, by innermost span
    program.tick_anatomy(tr)        # what the slowest ticks were doing

No per-layer metric reads these yet; ``PERF.md`` names the harness edit
that would print them with every traced run.
"""

from __future__ import annotations

import glob
import os

from bench import trace

PREFIXES = ("serving.", "server.")
TICK = "serving.tick"
DECODE = "serving.decode"
OUTSIDE = "outside"      # under no program span: between ticks, event loop


def record_program(logdir: str) -> list:
    """[[name, start, duration, {arg: value}], ...] of the program's host
    spans in the trace ``jax.profiler`` wrote under ``logdir``, in
    nanoseconds on the trace's clock."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append([e.name, int(e.start_ns), int(e.duration_ns),
                                {k: v for k, v in e.stats}])
    return sorted(out, key=lambda e: (e[1], -e[2]))


def program_spans(tr: dict, name: str) -> list:
    """[(start, end, args)] of each program span ``name`` that starts
    inside the window."""
    lo, hi = trace.window(tr)
    return [(t, t + d, args) for n, t, d, args in tr.get("program", [])
            if n == name and lo <= t < hi]


def program_segments(tr: dict) -> list:
    """[(start, end, name)]: time cut wherever a program span starts or
    ends, each piece named by the innermost span open over it (the spans
    of the one serving thread nest); time under no span is left out."""
    spans = sorted(((t, t + d, n) for n, t, d, _ in tr.get("program", [])),
                   key=lambda s: (s[0], -s[1]))
    points = sorted({x for a, b, _ in spans for x in (a, b)})
    out, stack, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(spans) and spans[i][0] <= a:
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            out.append((a, b, stack[-1][2]))
    return out


def cover(segments: list, intervals: list) -> dict:
    """{name: ns} of the sorted ``intervals``' overlap with the named
    ``segments``; what no segment covers counts as ``OUTSIDE``."""
    out, j = {}, 0
    for a, b in intervals:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        covered, k = 0, j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            c = min(b, e) - max(a, s)
            out[name] = out.get(name, 0) + c
            covered += c
            k += 1
        if b - a > covered:
            out[OUTSIDE] = out.get(OUTSIDE, 0) + (b - a - covered)
    return out


def program_doing(tr: dict, a: int, b: int) -> str:
    """The innermost program span that covers most of [a, b), or
    ``OUTSIDE``."""
    got = cover(program_segments(tr), [(a, b)])
    return max(got, key=got.get)


def idle_by_span(tr: dict) -> dict:
    """Seconds of device idle time in the window, summed by the innermost
    program span the host was in, most first."""
    got = cover(program_segments(tr), trace.idle_gaps(tr))
    return {n: ns / 1e9 for n, ns in sorted(got.items(),
                                            key=lambda kv: -kv[1])}


def decode_ticks(tr: dict) -> list:
    """[(start, end, args)] of the window's ``serving.tick`` spans that
    dispatched a decode step (hold a ``serving.decode`` span)."""
    starts = [a for a, _, _ in program_spans(tr, DECODE)]
    out, i = [], 0
    for a, b, args in program_spans(tr, TICK):
        while i < len(starts) and starts[i] < a:
            i += 1
        if i < len(starts) and starts[i] < b:
            out.append((a, b, args))
    return out


def tick_anatomy(tr: dict, top: int = 5) -> dict:
    """The share of the window's ``serving.tick`` time that the ticks'
    child spans cover, and the ``top`` longest ticks, each with the time
    of its children by name and the device's idle time inside it."""
    ticks = program_spans(tr, TICK)
    if not ticks:
        return {}
    segs = program_segments(tr)
    total = sum(b - a for a, b, _ in ticks)
    inside = cover(segs, [(a, b) for a, b, _ in ticks])
    busy = trace.busy(tr)

    def idle_in(a, b):
        return (b - a) - sum(max(0, min(b, y) - max(a, x)) for x, y in busy)

    slow = []
    for a, b, args in sorted(ticks, key=lambda t: t[0] - t[1])[:top]:
        parts = cover(segs, [(a, b)])
        slow.append({"ms": (b - a) / 1e6, "args": args,
                     "idle_ms": idle_in(a, b) / 1e6,
                     "children_ms": {n: ns / 1e6 for n, ns in sorted(
                         parts.items(), key=lambda kv: -kv[1])}})
    return {"ticks": len(ticks), "tick_ms_total": total / 1e6,
            "child_share": 1 - inside.get(TICK, 0) / total,
            "slowest": slow}


def excerpt(tr: dict, seconds: float, start: int) -> dict:
    """``seconds`` of the trace from ``start`` (ns): every event that
    overlaps them, and the window span cut to them."""
    lo, hi = start, start + int(seconds * 1e9)
    keep = lambda t, d: t < hi and t + d > lo
    host = [[n, t, d] for n, t, d in tr["host"]
            if n != trace.WINDOW_SPAN and keep(t, d)]
    return {"device": {line: [e for e in evs if keep(e[1], e[2])]
                       for line, evs in tr["device"].items()},
            "host": [[trace.WINDOW_SPAN, lo, hi - lo]] + host,
            "program": [e for e in tr.get("program", [])
                        if keep(e[1], e[2])]}


def excerpt_slowest_tick(tr: dict, seconds: float = 0.4) -> dict:
    """``excerpt`` from 20 ms before the window's slowest decode tick (or
    from the window's start, without one)."""
    ticks = decode_ticks(tr)
    if not ticks:
        return excerpt(tr, seconds, trace.window(tr)[0])
    a, _, _ = max(ticks, key=lambda t: t[1] - t[0])
    return excerpt(tr, seconds, a - 20 * 1_000_000)
