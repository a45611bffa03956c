"""plan_dp_ms: per event, the time of the SSM jit backend's device DP
(``plan.dp``: input copy, the DP's layers, the fetch of each state's
choice and near-tie flag), mean; None where the planner took the numpy
path.

Reads the window's records (``bench/program_spans.py``): the last
``len(run.steps)`` ``serve.step`` and ``len(run.events)``
``elastic.scale`` spans and their descendants; None where the recorder
dropped any of them."""
from bench.program_spans import window


def read(run):
    win = window(run)
    return win.event_ms("plan.dp") if win else None
