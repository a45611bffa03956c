"""transfer_ms: per event, the executor's measured transfer wall time
(``JaxBackend`` over ``DeviceBucketedState.run_phase``), mean."""


def read(run):
    if not run.events:
        return None
    return sum(e["transfer_s_wall"] for e in run.events) / len(
        run.events) * 1e3
