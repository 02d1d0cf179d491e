"""Tick loop (``serving/engine.py``): the 99th percentile of how long one
engine tick took, over the traced window's ticks that had requests in
flight: the lengths of their ``bench.step`` spans (one ``engine.step``,
one ``serving.tick``).  On the overlapped path every such tick
dispatched a decode step, so this is the tick behind the tail of
``itl_p99_ms``."""

import numpy as np

from bench import trace


def read(run):
    steps = trace.active_steps(run.trace, [t.active > 0
                                           for t in run.traced_steps()])
    if not steps:
        return None
    return float(np.percentile([b - a for a, b in steps], 99)) / 1e6
