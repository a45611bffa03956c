"""resize_ms: window time spent in event steps over the number of events.
An event step runs from the step boundary at which the event fires,
through plan, plan check and transfer, to that step's tokens."""


def read(run):
    steps = [s for s in run.steps if s.event is not None]
    if not steps:
        return None
    return sum(s.t1 - s.t0 for s in steps) / len(steps) * 1e3
