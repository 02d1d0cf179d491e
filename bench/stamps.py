"""The serving program's request stamps, as the per-layer readers read
them.

The program stamps each request (``arrival_s``, ``admit_s``,
``prefill_start_s``, ``first_token_s``, ``finish_s`` on
``serving/scheduler.Request``, on ``time.monotonic``); the harness keeps
each request's handle, whose ``request`` is that record.  A program
without such a stamp gives nothing to read, and the metric is left out.
"""

from __future__ import annotations


def stamp_gaps(run, first: str, last: str):
    """Seconds from each request's stamp ``first`` to its stamp ``last``,
    over the requests due in the window (those ``ttft_p90_s`` reads).  A
    stamp that never came counts as the run's close (its last token); a
    request the program refused counts from its submit.  None when the
    program has no such stamps."""
    w = run.window
    due = [r for r in w.recs if r.due >= w.t0]
    reqs = [r.handle.request if r.handle is not None else None for r in due]
    if not due or any(q is not None and not (hasattr(q, first)
                                             and hasattr(q, last))
                      for q in reqs):
        return None
    close = max([w.t_end] + [t for r in w.recs for t in r.times[-1:]])
    out = []
    for rec, q in zip(due, reqs):
        a = rec.submit if q is None else getattr(q, first)
        b = None if q is None else getattr(q, last)
        a = close if a is None else a
        out.append((close if b is None else b) - a)
    return out
