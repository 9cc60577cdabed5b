"""expert_load_max_over_mean.agent (ratio): the straggler among a layer's
experts: the largest load of an expert in a decode step over the mean load,
both summed over the window's decode steps and expert layers by the program's
counters (``expert_load_max_over_mean.reason``'s, over the 128 experts this
configuration holds). 1 is an even spread; at 512 pairs over 128 experts a
step the largest of 128 Poisson loads of mean 4 is about 10."""


def read(trace, spans, counters, cell):
    w = counters["window"]
    mean = w.get("moe_decode_load_mean")
    if not mean:
        return None
    return w["moe_decode_load_max"] / mean
