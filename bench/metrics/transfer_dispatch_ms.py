"""transfer_dispatch_ms: per event, the host's time enqueueing the
transfer (the sum of its ``migrate.dispatch`` spans: per (src, dst) pair,
the rows' gather, ``device_put`` and scatter), mean.  Its share of
``transfer_ms`` is the share of the transfer the host sets the pace of.

Reads the window's records (``bench/program_spans.py``): the last
``len(run.steps)`` ``serve.step`` and ``len(run.events)``
``elastic.scale`` spans and their descendants; None where the recorder
dropped any of them."""
from bench.program_spans import window


def read(run):
    win = window(run)
    return win.event_ms("migrate.dispatch") if win else None
