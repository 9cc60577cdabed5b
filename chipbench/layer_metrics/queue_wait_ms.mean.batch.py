"""queue_wait_ms.mean.batch (ms): mean, over requests that finished in the
window, of submit -> lane granted — telemetry span request/queued, recorded
from the front end's TraceLog when a request finishes."""


def read(trace, spans, counters, cell):
    queued = spans.get("request/queued")
    if not queued or queued["count"] <= 0:
        return None
    return 1e3 * queued["total_s"] / queued["count"]
