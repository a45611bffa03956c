"""setup_s: seconds from the start of the process to the window's start:
imports, device start-up, weights, prefill and warm-up (and, in a run
that compiles, compilation)."""


def read(run):
    return run.setup_s
