"""device_idle_share: 1 - (union of the device's operation intervals) /
(traced window), averaged over the cell's chips, in percent."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    busy = run.trace.busy_mean_s(run.device_ids)
    return 100.0 * (1.0 - busy / run.trace.window_s)
