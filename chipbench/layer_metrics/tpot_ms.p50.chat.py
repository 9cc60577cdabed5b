"""tpot_ms.p50.chat (ms): median time per output token after the first, per
request, on the client's clock. For reading only."""


def read(trace, spans, counters, cell):
    return counters.get("tpot_ms_p50")
