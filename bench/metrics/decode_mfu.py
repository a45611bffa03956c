"""decode_mfu: the operations the decode steps that fired no event need
(the reference's count: every layer's matrices and the LM head for each
live row, plus attention over the context it holds), over their host
time, as a share of the peak of the cell's chips."""


def read(run):
    steps = [s for s in run.steps if s.event is None]
    t = sum(s.t1 - s.t0 for s in steps)
    if not steps or t <= 0:
        return None
    flops = sum(run.model.decode_flops(run.sizes, s.rows, s.ctx)
                for s in steps)
    return 100.0 * flops / t / (run.peaks.flops * run.chips)
