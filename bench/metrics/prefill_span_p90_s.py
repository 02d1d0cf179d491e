"""Prefill step as scheduled (``serving/engine.py``: chunks interleaved
with decode ticks): the 90th percentile of how long a request's prompt
took from its first processed token to its first generated token,
``first_token_s - prefill_start_s`` of the program's own stamps (host
clock), over the requests ``ttft_p90_s`` reads."""

import numpy as np

from bench import stamps


def read(run):
    gaps = stamps.stamp_gaps(run, "prefill_start_s", "first_token_s")
    if not gaps:
        return None
    return float(np.percentile(gaps, 90))
