"""plan_check_ms: per event, the time of the strict plan check
(``plan.check``, ``MigrationExecutor._verify``), mean.

Reads the window's records (``bench/program_spans.py``): the last
``len(run.steps)`` ``serve.step`` and ``len(run.events)``
``elastic.scale`` spans and their descendants; None where the recorder
dropped any of them."""
from bench.program_spans import window


def read(run):
    win = window(run)
    return win.event_ms("plan.check") if win else None
