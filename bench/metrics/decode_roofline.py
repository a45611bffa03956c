"""decode_roofline: the least time of the decode steps in the traced part
of the window over the device time of the decode programs there, in
percent.  A step's least time is the larger of its operations over the
peak FLOP/s and its least HBM bytes (the weights read once, each live
row's K and V) over the HBM bandwidth, both counted by the
configuration's reference.  The decode program is the program's jitted
decode step (``decode_step_fn``: ``jit(<lambda>)``); a trace with no such
program gives nothing."""
PROGRAM = r"^jit__lambda|decode_step"


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace.module_time_s(PROGRAM)
    if not device_s:
        return None
    m, p = run.model, run.peaks
    least = sum(max(m.decode_flops(run.sizes, s.rows, s.ctx) / p.flops,
                    m.decode_min_bytes(run.sizes, s.rows, s.ctx) / p.hbm_bw)
                for s in run.steps if s.t1 <= run.trace_end)
    return 100.0 * least / device_s
