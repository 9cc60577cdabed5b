"""lane_to_first_token_ms.mean.batch (ms): mean, over requests that finished
in the window, of lane granted -> first token at the caller's handle — the
prefill and whatever it queued behind on the device; telemetry span
request/prefill, recorded from the front end's TraceLog when a request
finishes."""


def read(trace, spans, counters, cell):
    prefill = spans.get("request/prefill")
    if not prefill or prefill["count"] <= 0:
        return None
    return 1e3 * prefill["total_s"] / prefill["count"]
