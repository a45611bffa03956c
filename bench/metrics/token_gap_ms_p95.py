"""token_gap_ms_p95: the 95th percentile of the gap between consecutive
tokens of a request, over every request and step of the window.  Requests
decode in lockstep, so each step's gap (from the previous step's tokens,
or the window's start, to this step's) counts once per request."""
import numpy as np


def read(run):
    if not run.steps:
        return None
    ends = np.array([0.0] + [s.t1 for s in run.steps])
    gaps = np.repeat(np.diff(ends), [s.rows for s in run.steps])
    return float(np.percentile(gaps, 95)) * 1e3
