"""Scheduler (``serving/scheduler.py``: admission and the prefill queue):
the 90th percentile of how long a request waited in the program before
any of its prompt was processed, ``prefill_start_s - arrival_s`` of the
program's own stamps (host clock), over the requests ``ttft_p90_s``
reads."""

import numpy as np

from bench import stamps


def read(run):
    gaps = stamps.stamp_gaps(run, "arrival_s", "prefill_start_s")
    if not gaps:
        return None
    return float(np.percentile(gaps, 90))
