"""steady_step_ms: mean host time of a decode step over the window's
steps that fired no event (``_decode_nodes`` over every node, tokens on
the host)."""


def read(run):
    steps = [s for s in run.steps if s.event is None]
    if not steps:
        return None
    return sum(s.t1 - s.t0 for s in steps) / len(steps) * 1e3
