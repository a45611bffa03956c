"""plan_ms: per event, the program's resize wall time less its transfer
wall time (controller, SSM planner and strict plan check), mean."""


def read(run):
    if not run.events:
        return None
    return sum(e["resize_s_wall"] - e["transfer_s_wall"]
               for e in run.events) / len(run.events) * 1e3
