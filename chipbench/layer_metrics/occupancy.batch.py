"""occupancy.batch (%): decode tokens over decode-step lanes in the window —
(tokens out - first tokens, which prefill makes) / (chunks x decode_chunk x
max_batch), from ServingMetrics' counts."""


def read(trace, spans, counters, cell):
    w = counters["window"]
    lanes = w["chunks"] * counters["decode_chunk"] * counters["max_batch"]
    if lanes <= 0:
        return None
    return 100.0 * (w["tokens_out"] - counters["first_tokens"]) / lanes
