"""peak_hbm_bytes: the largest peak_bytes_in_use over the cell's devices,
read at the end of the window."""


def read(run):
    return run.peak_bytes or None
