"""ttft_ms.p50.chat (ms): median time to first token from the due time, on
the client's clock. For reading only: the tail decides, not the median."""


def read(trace, spans, counters, cell):
    return counters.get("ttft_ms_p50")
