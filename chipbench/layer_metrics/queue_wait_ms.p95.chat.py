"""queue_wait_ms.p95.chat (ms): 95th percentile, over requests due in the
window, of submit -> prefill start, from the front end's TraceLog
(queue_wait_s per request, taken by a terminal listener)."""

from chipbench.harness import percentile


def read(trace, spans, counters, cell):
    waits = counters.get("queue_wait_ms")
    if not waits:
        return None
    return percentile(waits, 95)
