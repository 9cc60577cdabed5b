"""collective_share.train (%): device time in which an all-gather,
reduce-scatter, all-reduce, all-to-all or collective-permute ran (the ops
line and the asynchronous line, as one union) over the traced window, mean
over chips. About zero on one chip: that cell is the control."""


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return 100.0 * trace.collective_s / trace.window_s
