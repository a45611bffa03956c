"""decode_row_share: over the steps that fired no event, the rows that
serve a request (``serve.step`` counter ``rows_live``) over the rows
decoded (``rows_decoded``: ``cap`` rows on every node that holds a
request), in percent.

Reads the window's records (``bench/program_spans.py``): the last
``len(run.steps)`` ``serve.step`` and ``len(run.events)``
``elastic.scale`` spans and their descendants; None where the recorder
dropped any of them."""
from bench.program_spans import window


def read(run):
    win = window(run)
    steps = win.quiet_steps(run) if win else []
    decoded = sum(s.counts.get("rows_decoded", 0) for s in steps)
    if not decoded:
        return None
    return 100.0 * sum(s.counts.get("rows_live", 0) for s in steps) / decoded
