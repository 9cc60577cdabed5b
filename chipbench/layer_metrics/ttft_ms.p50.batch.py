"""ttft_ms.p50.batch (ms): median time from SENDING a request to its first
token in the closed loop (queueing behind the full lanes included). For
reading only: latency decides nothing in a throughput cell."""


def read(trace, spans, counters, cell):
    return counters.get("ttft_ms_p50")
