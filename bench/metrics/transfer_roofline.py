"""transfer_roofline: the least time of the events' transfers over their
measured transfer time, in percent.  The rows an event moved are those
whose node changed, each counted as its K and V by the configuration's
reference (``row_bytes``); the least time (``bench/counts.py``) bounds
the HBM reads and writes and the ICI traffic of each device."""
from bench import counts


def read(run):
    if not run.events:
        return None
    t = sum(e["transfer_s_wall"] for e in run.events)
    if t <= 0:
        return None
    least = sum(counts.transfer_least_s(e["row_moves"], run.peaks)
                for e in run.events)
    return 100.0 * least / t
