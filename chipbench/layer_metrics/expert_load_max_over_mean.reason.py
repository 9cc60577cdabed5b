"""expert_load_max_over_mean.reason (ratio): the straggler among the held
experts: the largest load of a held expert in a decode step over the mean
load, both summed over the window's decode steps and expert layers by the
program's counters (so a step counts by the pairs it brought). 1 is an even
spread; with expert parallelism the slowest expert sets the layer's time."""


def read(trace, spans, counters, cell):
    w = counters["window"]
    mean = w.get("moe_decode_load_mean")
    if not mean:
        return None
    return w["moe_decode_load_max"] / mean
