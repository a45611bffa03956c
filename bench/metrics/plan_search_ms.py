"""plan_search_ms: per event, the time of the planner's search
(``plan.search``, ``ElasticPlanner.plan``: SSM's preparation, tables,
device DP, rebuild and decode, every τ relaxation included), mean.

Reads the window's records (``bench/program_spans.py``): the last
``len(run.steps)`` ``serve.step`` and ``len(run.events)``
``elastic.scale`` spans and their descendants; None where the recorder
dropped any of them."""
from bench.program_spans import window


def read(run):
    win = window(run)
    return win.event_ms("plan.search") if win else None
