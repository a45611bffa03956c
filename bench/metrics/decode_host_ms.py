"""decode_host_ms: over the steps that fired no event, the mean of a
``serve.step`` span less the sum of its ``serve.fetch`` spans: the host's
time in the step outside the waits for each node's tokens, in which the
device waits for the next node's program.

Reads the window's records (``bench/program_spans.py``): the last
``len(run.steps)`` ``serve.step`` and ``len(run.events)``
``elastic.scale`` spans and their descendants; None where the recorder
dropped any of them."""
from bench.program_spans import window


def read(run):
    win = window(run)
    steps = win.quiet_steps(run) if win else []
    if not steps:
        return None
    host = sum(s.dur_s - sum(f.dur_s for f in win.below(s, "serve.fetch"))
               for s in steps)
    return host / len(steps) * 1e3
